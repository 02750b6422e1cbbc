"""Mesh construction — the one place a ``jax.sharding.Mesh`` is built.

FUNCTIONS, not module-level constants — importing this module never
touches jax device state, so a process can still set its own
``XLA_FLAGS`` host-device count after importing it.

Every axis is ``AxisType.Auto``: the sharding rules in
``distributed/sharding.py`` place arrays with ``NamedSharding`` and let
the compiler propagate the rest.  ``jax.make_mesh`` defaults to
``Explicit`` axes, under which eager ops on sharded arrays (page-table
updates) and contractions over two sharded dims raise
``ShardingTypeError`` instead of compiling a collective.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_mesh(shape, *, devices=None):
    """Mesh of ``shape`` over ``devices`` (default: the first
    ``prod(shape)`` of ``jax.devices()``), all axes ``Auto``: axes
    ("data", "model"), or ("pod", "data", "model") for a 3-D shape."""
    shape = tuple(int(s) for s in shape)
    n = math.prod(shape)
    if devices is None:
        devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — on "
            "the CPU, set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} before any jax import")
    return jax.make_mesh(shape, ("pod", "data", "model")[-len(shape):],
                         devices=list(devices)[:n],
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    return make_mesh((2, 16, 16) if multi_pod else (16, 16))


def make_host_mesh():
    """1-device mesh for CPU smoke runs (same axis names, sizes 1x1)."""
    return make_mesh((1, 1))


def make_local_mesh():
    """("data", "model") = (all local devices, 1): the watershed / batch
    axis spans every chip of this host."""
    devices = jax.local_devices()
    return make_mesh((len(devices), 1), devices=devices)
