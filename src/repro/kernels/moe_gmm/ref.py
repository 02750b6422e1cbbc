"""Pure-jnp oracle of ``moe_gmm``: each expert's rows times its weight,
one expert at a time, in float32."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def moe_gmm_ref(x, w, sizes) -> jnp.ndarray:
    """x (m, k) rows sorted by expert, w (E, k, n), sizes (E,) -> (m, n)
    float32, with rows past ``sizes.sum()`` zero."""
    x = jnp.asarray(x, jnp.float32)
    w = jnp.asarray(w, jnp.float32)
    out = jnp.zeros((x.shape[0], w.shape[2]), jnp.float32)
    start = 0
    for e, n in enumerate(np.asarray(sizes)):
        n = int(n)
        if n:
            out = out.at[start:start + n].set(x[start:start + n] @ w[e])
        start += n
    return out
