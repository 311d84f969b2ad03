"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

Interpret mode (every other kernel test) runs the kernel body in Python and
never applies the TPU's tiling rules, so a kernel can pass all of those and
still be refused by the chip's compiler. Here each kernel is lowered and
compiled, not run, for one chip of a described ``v5e:2x2`` topology at
InternVL2-2B widths (16 query heads, 8 KV heads, head_dim 128, bf16, KV
pages of 16 positions). Nothing executes, so these say nothing about
results or speed; they fail when the compiler refuses a block shape, a
memory space or the fast-memory budget.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as dec
from repro.kernels import flash_attention as flash
from repro.kernels import flash_attention_bwd as flash_bwd
from repro.kernels import router_scores as router_mod

# InternVL2-2B decoder widths (configs/internvl2_2b.py) at serving shapes
B, H, KV, DH = 8, 16, 8, 128
PAGE, NB = 16, 24                     # 384-position context per slot
POOL = B * NB + 1                     # full capacity + scratch block 0
CHUNK, SPAN, SEQ = 64, 4, 2048
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _cases():
    """name → (kernel fn, argument shapes as (shape, dtype) pairs)."""
    pool = ((POOL, KV, PAGE, DH), BF16)
    i32 = jnp.int32
    return {
        "paged_decode_attention": (
            functools.partial(dec.paged_decode_attention, interpret=False),
            [((B, H, DH), BF16), pool, pool, ((B,), i32), ((B, NB), i32)]),
        "paged_decode_attention_bps4": (
            functools.partial(dec.paged_decode_attention, blocks_per_step=4,
                              interpret=False),
            [((B, H, DH), BF16), pool, pool, ((B,), i32), ((B, NB), i32)]),
        "chunk_prefill_attention": (
            functools.partial(dec.chunk_prefill_attention, interpret=False),
            [((CHUNK, H, DH), BF16), pool, pool, ((), i32), ((NB,), i32)]),
        "paged_verify_attention": (
            functools.partial(dec.paged_verify_attention, interpret=False),
            [((B, SPAN, H, DH), BF16), pool, pool, ((B,), i32),
             ((B, NB), i32)]),
        "decode_attention": (
            functools.partial(dec.decode_attention, interpret=False),
            [((B, H, DH), BF16), ((B, 512, KV, DH), BF16),
             ((B, 512, KV, DH), BF16), ((B,), i32)]),
        "flash_attention": (
            functools.partial(flash.flash_attention, interpret=False),
            [((1, SEQ, H, DH), BF16), ((1, SEQ, KV, DH), BF16),
             ((1, SEQ, KV, DH), BF16)]),
        "flash_attention_bwd": (
            functools.partial(flash_bwd.flash_attention_bwd,
                              interpret=False),
            [((1, SEQ, H, DH), BF16), ((1, SEQ, KV, DH), BF16),
             ((1, SEQ, KV, DH), BF16), ((1, SEQ, H, DH), BF16),
             ((1, SEQ, H), jnp.float32), ((1, SEQ, H, DH), BF16)]),
        "router_scores": (
            functools.partial(router_mod.router_scores, temperature=10.0,
                              interpret=False),
            [((256, 1024), jnp.float32), ((2, 1024), jnp.float32)]),
    }


@pytest.mark.parametrize("name", sorted(_cases()))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, args = _cases()[name]
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    # a write to the persistent cache here could not be read back without
    # a chip, so compile around it; the suite's float64 mode would make
    # the index maps return int64, which the TPU kernel compiler rejects
    prev = (jax.config.jax_enable_compilation_cache,
            jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    try:
        compiled = jax.jit(fn).lower(*shapes).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev[0])
        jax.config.update("jax_enable_x64", prev[1])
    assert "tpu_custom_call" in compiled.as_text()
