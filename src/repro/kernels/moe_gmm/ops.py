"""jit'd wrapper: the grouped matmul of the dropless expert layer.

``moe_gmm(x, w, sizes)`` multiplies each group of rows of ``x`` by its
expert's weight: rows ``[off[e], off[e] + sizes[e])`` by ``w[e]``, where
``off`` is the running sum of ``sizes`` (rows sorted by expert).

On the TPU the body is ``grouped_matmul``, the megablox grouped-matmul
Pallas kernel that ships with JAX
(``jax.experimental.pallas.ops.tpu.megablox``): its grid visits, for
each block of ``TM`` rows, only the experts whose rows lie in that
block, so an expert that received no row has no weight block read.  Each
visit copies the expert's whole (k, n) weight into VMEM (one block,
``tk = k`` and ``tn = n``), so an expert's weight is read once per row
block it spans.  The kernel builder is called un-jitted inside this
wrapper, so the compiler names the custom call after it and the device
trace labels every call ``moe_gmm.<n>``.  Elsewhere the same product is
``jax.lax.ragged_dot``.  Weights are read in the dtype they are stored
in: the rows are cast to the weight's dtype, never the weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _megablox

from repro.kernels.common import use_interpret, use_moe_gmm_kernel

TM = 128            # rows a kernel block holds; fewer rows make one block


def grouped_matmul(x, w, sizes, layer=None, *, interpret: bool):
    """The kernel: x (m, k), w (E, k, n) or (L, E, k, n) with ``layer``,
    sizes (E,) int32 -> (m, n) float32.  A stack is seen as L*E experts
    of which only layer ``layer``'s hold rows, so that layer's weights
    are read where they lie (a Pallas call would take ``w[layer]`` as a
    copy)."""
    if layer is not None:
        L, E = w.shape[:2]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((L * E,), jnp.int32), sizes, (layer * E,))
        w = w.reshape((L * E,) + w.shape[2:])
    m, k = x.shape
    tm = TM if m >= TM else -(-m // 16) * 16
    pad = -m % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = _megablox.__wrapped__(
        x, w, sizes, preferred_element_type=jnp.float32,
        tiling=(tm, k, w.shape[2]), interpret=interpret)
    return out[:m]


@jax.jit
def moe_gmm(x: jax.Array, w: jax.Array, sizes: jax.Array,
            layer=None) -> jax.Array:
    """x (m, k) rows sorted by expert, w (E, k, n), sizes (E,) int32 rows
    an expert holds (summing to at most m) -> (m, n) float32.  Rows past
    ``sizes.sum()`` are left undefined.  With ``layer``, ``w`` is a stack
    (L, E, k, n) and layer ``layer``'s experts are used, in place."""
    x = x.astype(w.dtype)
    sizes = sizes.astype(jnp.int32)
    if use_moe_gmm_kernel():
        return grouped_matmul(x, w, sizes, layer, interpret=use_interpret())
    if layer is not None:
        w = w[layer]
    return jax.lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.float32)
