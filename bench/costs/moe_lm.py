"""Model FLOPs and the expert layer's bytes of a Qwen3-MoE decoder, from
the configuration's shapes (Hugging Face key names).  2 FLOPs per
multiply-add; elementwise work, norms and softmaxes are not counted.
Active FLOPs only: a token counts its ``num_experts_per_tok`` experts."""


def expert_flops_per_pair(c: dict) -> float:
    """One (token, expert) pair through gate, up and down."""
    return 2.0 * 3 * c["hidden_size"] * c["moe_intermediate_size"]


def dense_flops_per_token(c: dict) -> float:
    """One token's attention projections and router in every layer."""
    d, E = c["hidden_size"], c["num_experts"]
    Hq, Hkv, D = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    per_layer = d * (Hq + 2 * Hkv) * D + Hq * D * d + d * E
    return 2.0 * c["num_hidden_layers"] * per_layer


def token_flops(c: dict) -> float:
    """One token through every layer, its experts included."""
    return dense_flops_per_token(c) + c["num_hidden_layers"] \
        * c["num_experts_per_tok"] * expert_flops_per_pair(c)


def attention_flops(c: dict, keys: int) -> float:
    """Query tokens against ``keys`` (query, key) pairs in every layer:
    q k^T and p v, each 2 * Hq * D per pair."""
    return 4.0 * c["num_hidden_layers"] * c["num_attention_heads"] \
        * c["head_dim"] * keys


def logits_flops(c: dict) -> float:
    return 2.0 * c["hidden_size"] * c["vocab_size"]


def chunk_flops(c: dict, start: int, tokens: int) -> float:
    """A prefill chunk of ``tokens`` tokens at positions start.. (each
    attends causally to every earlier position), logits for its last."""
    keys = tokens * start + tokens * (tokens + 1) // 2
    return tokens * token_flops(c) + attention_flops(c, keys) \
        + logits_flops(c)


def decode_flops(c: dict, keys) -> float:
    """One decode step: a token per served slot, slot s attending to
    ``keys[s]`` keys, with logits for each."""
    n = len(keys)
    return n * (token_flops(c) + logits_flops(c)) \
        + attention_flops(c, sum(keys))


def expert_layer_cost(c: dict, pairs: int, touched: int,
                      w_bytes: int = 2, a_bytes: int = 2):
    """(FLOPs, bytes) the expert layers' grouped matmuls need for one step,
    from its counts summed over layers: ``pairs`` (token, expert) pairs and
    ``touched`` experts that received one.  Bytes: each touched expert's
    gate, up and down weights once; per pair, its row read by gate and by
    up, their float32 outputs written, the activation read by down and
    down's float32 output written."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    flops = pairs * expert_flops_per_pair(c)
    weights = touched * 3 * d * f * w_bytes
    acts = pairs * (2 * d * a_bytes + 2 * f * 4 + f * a_bytes + d * 4)
    return flops, weights + acts
