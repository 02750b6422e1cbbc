"""Model FLOPs of a dense GQA decoder, from the configuration's shapes
(Hugging Face key names).  2 FLOPs per multiply-add; elementwise work,
norms and the softmax are not counted."""


def matmul_flops_per_token(c: dict) -> float:
    """The layers' projections and MLP for one token (biases omitted)."""
    d, ff = c["hidden_size"], c["intermediate_size"]
    Hq, Hkv, D = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    per_layer = d * (Hq + 2 * Hkv) * D + Hq * D * d + 3 * d * ff
    return 2.0 * c["num_hidden_layers"] * per_layer


def attention_flops(c: dict, keys: int) -> float:
    """One query token against ``keys`` keys in every layer: q k^T and
    p v, each 2 * Hq * D per key."""
    return 4.0 * c["num_hidden_layers"] * c["num_attention_heads"] \
        * c["head_dim"] * keys


def logits_flops(c: dict) -> float:
    return 2.0 * c["hidden_size"] * c["vocab_size"]


def chunk_flops(c: dict, start: int, tokens: int, logits_rows: int = 1
                ) -> float:
    """A prefill chunk of ``tokens`` tokens at positions start.. (each
    attends causally to every earlier position), with logits for
    ``logits_rows`` of them."""
    keys = tokens * start + tokens * (tokens + 1) // 2
    return tokens * matmul_flops_per_token(c) + attention_flops(c, 1) \
        * keys + logits_rows * logits_flops(c)


def decode_flops(c: dict, keys) -> float:
    """One decode step: a token per active slot, slot s attending to
    ``keys[s]`` keys, with logits for each."""
    n = len(keys)
    return n * (matmul_flops_per_token(c) + logits_flops(c)) \
        + attention_flops(c, 1) * sum(keys)
