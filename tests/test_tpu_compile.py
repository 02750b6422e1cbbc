"""Native (Mosaic) compiles of the serving kernels for a described TPU v5e.

Interpret mode accepts block shapes that the chip's compiler refuses, so
these tests lower ``paged_attention_pallas(..., interpret=False)`` at real
model widths for a v5e chip that is described, not attached, and assert
that the compiled program holds the kernel (``tpu_custom_call``).  Nothing
runs; each compile takes a second or two.

The topology is described inside a module fixture, never at import or in
a ``skipif``/``parametrize`` argument: only one process at a time may load
the TPU library, and the test workers all import this module.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attn.kernel import paged_attention_pallas

PS = 16                                  # served page size
SLOTS = 8
MAX_LEN = 576                            # 512-token prompt + 64 new tokens
SPEC_K = 4
CHUNK = 256

# name -> (Hkv, G, D, window, softcap), the arch's published widths
WIDTHS = {
    "qwen2-1.5b": (2, 6, 128, 0, 0.0),
    "gemma2-2b": (4, 2, 256, 4096, 50.0),
    "qwen3-moe-30b-a3b": (4, 8, 128, 0, 0.0),
}
N = -(-MAX_LEN // PS)                    # pages a slot: 36, no multiple of 8
# the three serve shapes: (slots B, query tokens T, pages a slot n); the
# decode and verify calls carry an ``active`` mask, a chunk does not
SHAPES = {"decode": (SLOTS, 1, N), "verify": (SLOTS, SPEC_K + 1, N),
          "chunk": (1, CHUNK, N),
          # twice the chunk: its query rows split into tiles (``q_tiles``)
          "chunk-512": (1, 2 * CHUNK, N),
          # the long-document cell's 16 slots, with 531 pages a slot: the
          # last of its 67 blocks of 8 pages is short
          "decode-long": (16, 1, 531)}


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2.  Its compiles stay out of the
    persistent compilation cache, which cannot read them back without a
    chip."""
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:              # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cached = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cached)


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("arch", list(WIDTHS))
def test_paged_attention_compiles_natively(one_chip, arch, shape):
    Hkv, G, D, window, softcap = WIDTHS[arch]
    B, T, n = SHAPES[shape]
    P = max(B, SLOTS) * n

    def spec(shp, dtype):
        return jax.ShapeDtypeStruct(shp, dtype, sharding=one_chip)

    fn = jax.jit(lambda q, k, v, pos, rows, qpos, active: (
        paged_attention_pallas(q, k, v, pos, rows, qpos, active,
                               window=window, softcap=softcap,
                               interpret=False)))
    compiled = fn.lower(
        spec((B, T, Hkv, G, D), jnp.bfloat16),
        spec((P, PS, Hkv, D), jnp.bfloat16),
        spec((P, PS, Hkv, D), jnp.bfloat16),
        spec((P, PS), jnp.int32), spec((B, n), jnp.int32),
        spec((B, T), jnp.int32),
        spec((B,), jnp.bool_) if B > 1 else None).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's instruction carries the pallas_call's name, which is
    # how a device trace names it (``bench/kernels.py``: ^paged_attention)
    assert re.search(r"%paged_attention(\.\d+)? = [^\n]*tpu_custom_call",
                     text)


# the served cells' stacks: (layers, pages, slots, pages a slot)
STACKS = {"qwen2-1.5b": (28, 8448, 16, 528),
          "qwen3-moe-30b-a3b": (6, 5120, 64, 80)}


@pytest.mark.parametrize("shape", ["decode", "chunk"])
@pytest.mark.parametrize("arch", list(STACKS))
def test_stacked_paged_attention_compiles_natively(one_chip, arch, shape):
    """Inside the layer scan the kernel reads one layer of the served
    cells' whole K/V stacks in place, by a layer index in scalar-prefetch
    memory: the compiled program holds the kernel and no copy of a
    layer's pool."""
    Hkv, G, D, window, softcap = WIDTHS[arch]
    L, P, slots, n = STACKS[arch]
    B, T = (slots, 1) if shape == "decode" else (1, CHUNK)

    def spec(shp, dtype):
        return jax.ShapeDtypeStruct(shp, dtype, sharding=one_chip)

    fn = jax.jit(lambda q, k, v, pos, rows, qpos, active, layer: (
        paged_attention_pallas(q, k, v, pos, rows, qpos, active, layer,
                               window=window, softcap=softcap,
                               interpret=False)))
    text = fn.lower(
        spec((B, T, Hkv, G, D), jnp.bfloat16),
        spec((L, P, PS, Hkv, D), jnp.bfloat16),
        spec((L, P, PS, Hkv, D), jnp.bfloat16),
        spec((L, P, PS), jnp.int32), spec((B, n), jnp.int32),
        spec((B, T), jnp.int32), spec((B,), jnp.bool_),
        spec((), jnp.int32)).compile().as_text()
    assert re.search(r"%paged_attention(\.\d+)? = [^\n]*tpu_custom_call",
                     text)
    layer_pool = f"bf16[{P},{PS},{Hkv},{D}]"
    assert not [line for line in text.splitlines()
                if f"= {layer_pool}" in line or
                f"= bf16[1,{P},{PS},{Hkv},{D}]" in line]


# the served qwen3-moe-30b-a3b cell: hidden 2,048, expert hidden 768, 128
# experts, top-8; (token, expert) pairs of a 64-slot decode step and of a
# 256-token prefill chunk; gate/up (d -> f) and down (f -> d)
MOE_ROWS = {"decode": 64 * 8, "chunk": 256 * 8}
MOE_PROJ = {"gate_up": (2048, 768), "down": (768, 2048)}


@pytest.mark.parametrize("proj", list(MOE_PROJ))
@pytest.mark.parametrize("rows", list(MOE_ROWS))
def test_moe_gmm_compiles_natively(one_chip, rows, proj):
    """The served layer reads its experts in place from the 6-layer stack
    (768 experts, this layer's 128 holding rows)."""
    from repro.kernels.moe_gmm import ops
    k, n = MOE_PROJ[proj]

    def spec(shp, dtype):
        return jax.ShapeDtypeStruct(shp, dtype, sharding=one_chip)

    def moe_gmm(x, w, sizes, layer):    # named as the program's wrapper
        return ops.grouped_matmul(x, w, sizes, layer, interpret=False)

    compiled = jax.jit(moe_gmm).lower(
        spec((MOE_ROWS[rows], k), jnp.bfloat16),
        spec((6, 128, k, n), jnp.bfloat16),
        spec((128,), jnp.int32), spec((), jnp.int32)).compile()
    text = compiled.as_text()
    # the custom call takes its enclosing jit's name, which is how a
    # device trace names it (the expert-layer readers match ^moe_gmm)
    assert re.search(r"%moe_gmm(\.\d+)? = [^\n]*tpu_custom_call", text)
