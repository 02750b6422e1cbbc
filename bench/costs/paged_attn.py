"""FLOPs and bytes the paged-attention algorithm needs for one call (one
layer), from its shapes: only the live key/value pages are read, not the
pages a kernel's grid may walk.

Bytes: the live pages' K and V (``kv_bytes`` each element) and their
position rows (int32), the queries and the output (``q_bytes`` each).
FLOPs: q k^T and p v, 2 * D each per (query head, key) pair, causal.
"""
from typing import Sequence, Tuple


def call_cost(c: dict, rows: Sequence[Tuple[int, int]], page: int,
              kv_bytes: int = 2, q_bytes: int = 2) -> Tuple[float, float]:
    """``rows``: (query tokens, keys the last of them sees) per slot in
    the call; query i of n sees ``keys - (n - 1 - i)`` keys.  Returns
    (flops, bytes)."""
    Hq, Hkv, D = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    flops = byts = 0.0
    for n, keys in rows:
        seen = n * keys - n * (n - 1) // 2
        flops += 4.0 * Hq * D * seen
        pages = -(-keys // page)
        byts += pages * page * (2 * Hkv * D * kv_bytes + 4) \
            + 2 * n * Hq * D * q_bytes
    return flops, byts
