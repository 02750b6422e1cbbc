"""Logical-axis sharding rule engine.

The paper's two-level distribution strategy (watersheds -> nodes, CNN heads
-> devices) is generalized here as a logical-axis rule table:

  * ``batch``  -> the watershed / input-pipeline axis: ("pod", "data")
  * ``heads`` / ``kv_heads`` / ``experts`` / ``ffn`` / ``inner`` /
    ``pix_heads`` -> the head-partitioning axis: "model"
  * everything else (embed, seq, state, conv, ...) replicated.

Parameters are built through :class:`ParamFactory`, which can run in
``init`` mode (returns initialized arrays) or ``spec`` mode (returns the
logical-axes tuple), so a single ``params(cfg, mk)`` definition yields both
the param pytree and a structurally identical pytree of logical specs.

Rules resolve to :class:`jax.sharding.PartitionSpec`; a mesh-axis
assignment is dropped (replicated) whenever the dim is not divisible by the
mesh-axis size — the documented fallback for e.g. 24 heads on a 16-way
model axis (tp_mode="ffn" archs avoid relying on head sharding entirely).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axes = Tuple[Optional[str], ...]
MeshAxis = Union[None, str, Tuple[str, ...]]


def is_axes(x: Any) -> bool:
    """True for a logical-axes tuple leaf (the ParamFactory spec leaves) —
    the canonical ``is_leaf`` predicate for traversing spec trees."""
    return isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)


# ---------------------------------------------------------------------------
# Rule tables
# ---------------------------------------------------------------------------
def make_rules(cfg=None, *, mesh: Optional[Mesh] = None,
               tp_mode: Optional[str] = None,
               fsdp: bool = False) -> dict[str, MeshAxis]:
    """Logical axis -> mesh axis assignment.

    ``cfg`` (a ModelConfig) supplies ``tp_mode``; ``mesh`` determines whether
    a "pod" axis exists.  ``fsdp=True`` is the PARAMETER rule variant:
    the ``embed`` dim of params/optimizer state shards over the data axes
    (ZeRO-3-style; GSPMD inserts the weight all-gathers) — use it for the
    param/opt trees only, never for activation constraints.
    """
    tp = tp_mode or (getattr(cfg, "tp_mode", None) or "heads")
    axis_names = tuple(mesh.axis_names) if mesh is not None else ("data", "model")
    batch: MeshAxis = tuple(a for a in ("pod", "data") if a in axis_names) or None
    if isinstance(batch, tuple) and len(batch) == 1:
        batch = batch[0]
    model = "model" if "model" in axis_names else None

    rules: dict[str, MeshAxis] = {
        "batch": batch,
        "seq": None,
        "embed": batch if fsdp else None,
        "heads": model if tp == "heads" else None,
        "kv_heads": model if tp == "heads" else None,
        "head_dim": None,
        "ffn": model,
        "vocab": model,
        "experts": model,
        "inner": model,          # ssm / rglru channel dim
        "state": None,
        "conv": None,
        "pix_heads": model,      # Dom-ST spatial heads (the paper's partition)
        "pixels": None,
        "time": None,
        "hidden": model,         # lstm / mlp hidden
        # decode KV-cache sequence axis: sharded over model whenever the KV
        # heads can't shard there (ffn-mode archs, or kv_heads % ways != 0)
        # so a 32k cache never replicates 16x.
        "cache_seq": model if cache_needs_seq_shard(cfg, mesh, tp) else None,
        # paged-KV page pool (serve): the page axis distributes over the
        # batch axes like request slots did, while the within-page offset
        # axis reuses "cache_seq" above — so both cache_needs_seq_shard
        # branches carry over to the paged layout unchanged.
        "pages": batch,
    }
    return rules


def cache_needs_seq_shard(cfg, mesh, tp_mode: Optional[str] = None) -> bool:
    """True when the decode KV cache must shard its SEQUENCE axis.

    The head axes of a ``ffn``-mode arch (or one whose kv_heads don't
    divide the model axis) can't shard over "model", so the cache would
    replicate model-ways times; ``make_rules`` then routes ``cache_seq``
    onto "model" instead.  Public so the serve engine and its mesh tests
    can assert which branch a config takes."""
    tp = tp_mode or (getattr(cfg, "tp_mode", None) or "heads")
    if tp == "ffn":
        return True
    if cfg is None or mesh is None:
        return False
    kv = getattr(cfg, "num_kv_heads", 0)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ways = sizes.get("model", 1)
    return bool(kv) and kv % ways != 0


# back-compat alias (pre-PR-3 tests import the underscored name)
_cache_needs_seq_shard = cache_needs_seq_shard


def resolve_pspec(axes: Axes, shape: Sequence[int], mesh: Mesh,
                  rules: Mapping[str, MeshAxis]) -> P:
    """Map a logical-axes tuple to a PartitionSpec, dropping indivisible axes."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    entries: list[MeshAxis] = []
    for dim, ax in zip(shape, axes):
        assignment = rules.get(ax) if ax is not None else None
        if assignment is None:
            entries.append(None)
            continue
        names = assignment if isinstance(assignment, tuple) else (assignment,)
        total = int(np.prod([sizes[n] for n in names]))
        if dim % total == 0:
            entries.append(assignment)
        else:
            # jit argument shardings require exact divisibility (GSPMD's
            # uneven padding is not allowed at the pjit boundary), so
            # indivisible dims replicate.  Archs whose head counts don't
            # divide the model axis use tp_mode="ffn"; vocabs are padded
            # to multiples of 128 (configs/base.py padded_vocab).
            entries.append(None)

    # PartitionSpec forbids reusing a mesh axis across dims
    seen: set[str] = set()
    final: list[MeshAxis] = []
    for e in entries:
        names = e if isinstance(e, tuple) else (e,) if e else ()
        if any(n in seen for n in names):
            final.append(None)
        else:
            final.append(e)
            seen.update(names)
    return P(*final)


# ---------------------------------------------------------------------------
# ParamFactory: one definition -> params AND specs
# ---------------------------------------------------------------------------
class ParamFactory:
    """Builds parameters (``mode='init'``) or logical-axis specs (``mode='spec'``).

    Keys are derived deterministically from a root key and a call counter,
    so init/spec traversals stay structurally aligned.
    """

    def __init__(self, key: Optional[jax.Array] = None, mode: str = "init",
                 dtype: Any = jnp.float32):
        assert mode in ("init", "spec")
        self.mode = mode
        self.key = key
        self.dtype = dtype
        self._n = 0

    def _next_key(self) -> jax.Array:
        k = jax.random.fold_in(self.key, self._n)
        self._n += 1
        return k

    def __call__(self, shape: Sequence[int], axes: Axes,
                 init: str = "normal", scale: Optional[float] = None) -> Any:
        shape = tuple(int(s) for s in shape)
        assert len(shape) == len(axes), (shape, axes)
        if self.mode == "spec":
            self._n += 1
            return tuple(axes)
        k = self._next_key()
        if init == "zeros":
            return jnp.zeros(shape, self.dtype)
        if init == "ones":
            return jnp.ones(shape, self.dtype)
        if init == "normal":
            if scale is None:
                # fan-in scaling on the penultimate dim (lecun-normal-ish)
                fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
                scale = 1.0 / np.sqrt(max(fan_in, 1))
            return scale * jax.random.normal(k, shape, self.dtype)
        if init == "embed":
            return (scale or 1.0) * jax.random.normal(k, shape, self.dtype)
        if init == "uniform":
            lim = scale or 1.0 / np.sqrt(max(shape[-1], 1))
            return jax.random.uniform(k, shape, self.dtype, -lim, lim)
        raise ValueError(f"unknown init '{init}'")


def tree_pspecs(spec_tree: Any, shape_tree: Any, mesh: Mesh,
                rules: Mapping[str, MeshAxis]) -> Any:
    """Resolve a pytree of logical-axes tuples into PartitionSpecs."""
    def _one(axes, arr):
        shape = arr.shape if hasattr(arr, "shape") else arr
        return resolve_pspec(axes, shape, mesh, rules)
    return jax.tree.map(_one, spec_tree, shape_tree, is_leaf=is_axes)


def tree_shardings(spec_tree: Any, shape_tree: Any, mesh: Mesh,
                   rules: Mapping[str, MeshAxis]) -> Any:
    return jax.tree.map(lambda p: NamedSharding(mesh, p),
                        tree_pspecs(spec_tree, shape_tree, mesh, rules),
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Activation sharding constraints (no-op outside a logical_sharding context)
# ---------------------------------------------------------------------------
_CTX = threading.local()


@contextlib.contextmanager
def logical_sharding(mesh: Mesh, rules: Mapping[str, MeshAxis]):
    """Enable ``constrain`` inside model code for this mesh/rule table."""
    prev = getattr(_CTX, "val", None)
    _CTX.val = (mesh, rules)
    try:
        yield
    finally:
        _CTX.val = prev


@contextlib.contextmanager
def suspend_logical_sharding():
    """Disable ``constrain`` (used inside shard_map bodies, where mesh axes
    are manual and with_sharding_constraint is disallowed)."""
    prev = getattr(_CTX, "val", None)
    _CTX.val = None
    try:
        yield
    finally:
        _CTX.val = prev


def constrain(x: jax.Array, axes: Axes) -> jax.Array:
    """``with_sharding_constraint`` by logical axes; identity if no context."""
    ctx = getattr(_CTX, "val", None)
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = resolve_pspec(axes, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
