"""Shared kernel-harness policy: interpret-mode and dispatch decisions.

Every ``ops.py`` wrapper used to snapshot ``jax.default_backend()`` into a
module-level ``INTERPRET`` constant at import time — which froze the
backend before any later platform selection and read
``REPRO_PALLAS_INTERPRET`` exactly once.  Both decisions live here now and
are evaluated LAZILY (at trace time, inside the jitted wrappers), so they
see the backend and environment of the call that actually lowers the
kernel.

Contract:

* ``use_interpret()`` — True means ``pl.pallas_call(..., interpret=True)``
  (the Pallas interpreter, any backend); False means native Mosaic
  lowering.  ``REPRO_PALLAS_INTERPRET=1`` forces the interpreter even on
  TPU (debugging); ``REPRO_PALLAS_INTERPRET=0`` forces native lowering
  even off-TPU (lowering tests only — it will fail at compile time on
  backends without Mosaic).  Unset: interpret everywhere but TPU.
* ``use_paged_attn_kernel()`` — whether the paged-attention serve paths
  in ``models/attention.py`` dispatch to the fused Pallas kernel triple
  (``kernels/paged_attn``) instead of the lax ``gather_pages`` +
  ``attend_cached`` fallback.  ``REPRO_PAGED_ATTN=1|fused`` forces the
  kernel (interpret mode included — how CPU CI smokes the path);
  ``REPRO_PAGED_ATTN=0|lax`` forces the fallback; unset/``auto``: the
  kernel on TPU (where it is the fast path), the fallback elsewhere
  (interpret mode is a correctness tool, not a fast path).
* ``use_moe_gmm_kernel()`` — whether the dropless expert layer's
  grouped matmul (``kernels/moe_gmm``) is the megablox Pallas kernel
  (on TPU) or ``jax.lax.ragged_dot`` (elsewhere).  Tests that need the
  kernel off-TPU call ``moe_gmm.ops.grouped_matmul`` directly.

Both are read at TRACE time: a jitted wrapper bakes the decision into its
compiled executable, so flipping the environment variable affects new
traces (new shapes, new engine instances), not already-compiled calls.
"""
from __future__ import annotations

import os

import jax


def use_interpret() -> bool:
    """Run Pallas kernels under the interpreter?  (lazy, per-trace)."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env != "0"
    return jax.default_backend() != "tpu"


def use_paged_attn_kernel() -> bool:
    """Dispatch paged attention to the fused Pallas kernel?  (lazy)."""
    env = os.environ.get("REPRO_PAGED_ATTN", "auto").lower()
    if env in ("1", "fused", "on"):
        return True
    if env in ("0", "lax", "off"):
        return False
    return jax.default_backend() == "tpu"


def use_moe_gmm_kernel() -> bool:
    """Run the expert layer's grouped matmul as the Pallas kernel?  (lazy)"""
    return jax.default_backend() == "tpu"
