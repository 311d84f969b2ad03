"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

Interpret mode (every other kernel test) runs the kernel body in Python and
never applies the TPU's tiling rules, so a kernel can pass all of those and
still be refused by the chip's compiler. Here each kernel is lowered and
compiled, not run, for one chip of a described ``v5e:2x2`` topology at
InternVL2-2B widths (16 query heads, 8 KV heads, head_dim 128, bf16, KV
pages of 16 positions, a 3-layer pool). Nothing executes, so these say
nothing about results or speed; they fail when the compiler refuses a
block shape, a memory space or the fast-memory budget.

The serving step programs are compiled the same way, and their compiled
text is read for what they do to the paged KV pool: the pool is donated
and updated in place, so no instruction may produce a pool-sized array
except the loop that carries it and the in-place row writes.
"""
import functools
import re

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
LAYERS = 3                            # the kernels read one pool layer
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
    pool = ((LAYERS, POOL, KV, PAGE, DH), BF16)
    i32 = jnp.int32
    layer = ((), i32)
    return {
        "paged_decode_attention": (
            functools.partial(dec.paged_decode_attention, interpret=False),
            [((B, H, DH), BF16), pool, pool, layer, ((B,), i32),
             ((B, NB), i32)]),
        "paged_decode_attention_bps4": (
            functools.partial(dec.paged_decode_attention, blocks_per_step=4,
                              interpret=False),
            [((B, H, DH), BF16), pool, pool, layer, ((B,), i32),
             ((B, NB), i32)]),
        "chunk_prefill_attention": (
            functools.partial(dec.chunk_prefill_attention, interpret=False),
            [((CHUNK, H, DH), BF16), pool, pool, layer, ((), i32),
             ((NB,), i32)]),
        "paged_verify_attention": (
            functools.partial(dec.paged_verify_attention, interpret=False),
            [((B, SPAN, H, DH), BF16), pool, pool, layer, ((B,), i32),
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


def _compile(fn, *args):
    """``fn`` (jitted) lowered and compiled for the described chip. A write
    to the persistent cache here could not be read back without a chip, so
    compile around it; the suite's float64 mode would make the index maps
    return int64, which the TPU kernel compiler rejects."""
    prev = (jax.config.jax_enable_compilation_cache,
            jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    try:
        return fn.lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev[0])
        jax.config.update("jax_enable_x64", prev[1])


@pytest.mark.parametrize("name", sorted(_cases()))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, args = _cases()[name]
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    compiled = _compile(jax.jit(fn), *shapes)
    assert "tpu_custom_call" in compiled.as_text()


# --- the serving step programs and the paged pool -------------------------

# InternVL2-2B at every width but depth (2 layers) and vocabulary (1024:
# sampling's full-vocabulary sort takes most of a 92553-word program's
# compile time and never touches the pool), at the benchmark's serving
# shapes: 8 slots, page 128, cache 1024, prefill chunk 128, K = 2 experts
STEP_LAYERS, STEP_VOCAB, SLOTS, STEP_PAGE, CACHE, STEP_CHUNK, K = \
    2, 1024, 8, 128, 1024, 128, 2
STEP_NB = CACHE // STEP_PAGE
STEP_POOL = SLOTS * STEP_NB + 1

# what may produce a pool-shaped array: the loop that carries the pool,
# its parameters and tuples, and writes that update it in place
IN_PLACE_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast",
                "while", "dynamic-update-slice"}
_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\w+\[[\d,]*\]"
                    r"\{[^}]*\}) ([\w\-]+)\(")
_ARRAY = re.compile(r"\w+\[([\d,]*)\]")


def _computations(text):
    """{computation name: [instruction lines]} of compiled HLO text."""
    comps, cur = {}, None
    for line in text.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            head = line.split()
            cur = (head[1] if head[0] == "ENTRY" else head[0]).lstrip("%")
            comps[cur] = []
        elif cur is not None and line.startswith("  "):
            comps[cur].append(line)
    return comps


def _shapes(out):
    return {tuple(int(d) for d in m.group(1).split(",") if d)
            for m in _ARRAY.finditer(out)}


def _writes_in_place(comp_lines):
    """A fusion whose every pool-sized output is a dynamic-update-slice of
    one of its parameters (through bitcasts): XLA runs it in place."""
    instrs = {}
    root = None
    for line in comp_lines:
        m = _INSTR.match(line)
        if m:
            instrs[m.group(1)] = (m.group(3), line)
            if line.lstrip().startswith("ROOT"):
                root = m.group(1)

    def operands(name):
        line = instrs[name][1]
        return re.findall(r"%([\w.\-]+)", line.split("(", 1)[1])

    def in_place(name):
        op = instrs[name][0]
        if op == "bitcast":
            return in_place(operands(name)[0])
        if op == "dynamic-update-slice":
            base = operands(name)[0]
            while instrs[base][0] == "bitcast":
                base = operands(base)[0]
            return instrs[base][0] == "parameter"
        if op == "tuple":
            return all(in_place(o) for o in operands(name))
        return False
    return root is not None and in_place(root)


def _pool_copies(text, pool_shapes):
    """The instructions of the compiled program, outside fused bodies,
    that produce a pool-shaped array other than by carrying the pool or
    writing it in place: a copy, a slice, a transpose, a re-layout."""
    comps = _computations(text)
    fused = {}
    for lines in comps.values():
        for line in lines:
            m = re.search(r" fusion\(.*calls=%([\w.\-]+)", line)
            if m:
                fused[m.group(1)] = comps.get(m.group(1), [])
    bad = []
    for name, lines in comps.items():
        if name in fused:
            continue
        for line in lines:
            m = _INSTR.match(line)
            if not m or not (_shapes(m.group(2)) & pool_shapes):
                continue
            op = m.group(3)
            if op in IN_PLACE_OPS:
                continue
            if op == "fusion":
                callee = re.search(r"calls=%([\w.\-]+)", line).group(1)
                if _writes_in_place(fused[callee]):
                    continue
            bad.append(f"{m.group(1)} = {op} {m.group(2)[:60]}")
    return bad


@pytest.fixture(scope="module")
def step_programs(one_chip):
    """{name: (jitted program, argument shapes, pool bytes)} of the step
    programs the chip benchmark runs, at the shapes above."""
    from repro.configs.base import get_config
    from repro.core.ensemble import (decode_param_axes, make_stacked_chunk_fns,
                                     make_stacked_fused, stacked_cache_axes)
    from repro.models import build_model
    from repro.serve.scheduler import make_fused_fns

    cfg = get_config("internvl2_2b").reduced(n_layers=STEP_LAYERS,
                                             vocab=STEP_VOCAB)
    model = build_model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    def a(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32, f32 = jnp.int32, jnp.float32
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    cache = model.paged_cache_shapes(SLOTS, STEP_POOL, STEP_PAGE, CACHE)
    state = {"tok": a((SLOTS,), i32), "pos": a((SLOTS,), i32),
             "active": a((SLOTS,), jnp.bool_), "temps": a((SLOTS,), f32),
             "top_ks": a((SLOTS,), i32), "seeds": a((SLOTS,), jnp.uint32),
             "counts": a((SLOTS,), i32), "max_new": a((SLOTS,), i32),
             "stop_ids": a((SLOTS, 1), i32),
             "tables": a((SLOTS, STEP_NB), i32)}
    chunk = (a((1, STEP_CHUNK, cfg.d_model), cfg.cdtype), a((), i32),
             a((), i32), a((STEP_NB,), i32))
    pick = (a((1,), f32), a((1,), i32), a((1,), jnp.uint32))
    carry = on_chip(jax.eval_shape(
        lambda: model.init_chunk_carry(None, None, CACHE)))
    decode, decode_chunk, chunk_only = make_fused_fns(
        model, CACHE, STEP_CHUNK, use_kernel=True, paged=True)

    p_axes = decode_param_axes(params)
    stacked = jax.tree.map(lambda s, ax: a(s.shape[:ax] + (K,)
                                           + s.shape[ax:], s.dtype),
                           params, p_axes)
    caches = jax.tree.map(lambda s, ax: a(s.shape[:ax] + (K,)
                                          + s.shape[ax:], s.dtype),
                          cache, stacked_cache_axes(model, True))
    _, chunk_all = make_stacked_chunk_fns(model, None, p_axes, CACHE,
                                          STEP_CHUNK, use_kernel=True)
    mixture_decode, mixture_decode_chunk, mixture_chunk_only = \
        make_stacked_fused(model, p_axes, CACHE, chunk_all=chunk_all,
                           use_kernel=True, paged=True)
    # the mixture's chunk: each expert's embedded rows and carry (K at
    # axis 1 of the carry, as make_stacked_chunk_fns builds it), and the
    # slot's router weights
    m_chunk = (jax.tree.map(lambda s: a(s.shape[:1] + (K,) + s.shape[1:],
                                        s.dtype), carry),
               a((K,) + chunk[0].shape, cfg.cdtype)) + chunk[1:] \
        + (a((1, K), f32),)
    m_state = dict(state, weights=a((SLOTS, K), f32))
    nbytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(cache))
    params, cache = on_chip(params), on_chip(cache)
    return {
        "top1_fused_decode": (decode, (params, cache, state), nbytes),
        "top1_fused_decode_chunk": (
            decode_chunk, (params, cache, state, carry) + chunk + pick,
            nbytes),
        "top1_chunk_only": (chunk_only, (params, cache, carry) + chunk
                            + pick, nbytes),
        "mixture_fused_decode": (
            mixture_decode, (stacked, caches, m_state), K * nbytes),
        "mixture_fused_decode_chunk": (
            mixture_decode_chunk, (stacked, caches, m_state) + m_chunk
            + pick, K * nbytes),
        "mixture_chunk_only": (
            mixture_chunk_only, (stacked, caches) + m_chunk + pick,
            K * nbytes),
    }


@pytest.mark.parametrize("name", ["top1_fused_decode",
                                  "top1_fused_decode_chunk",
                                  "top1_chunk_only", "mixture_fused_decode",
                                  "mixture_fused_decode_chunk",
                                  "mixture_chunk_only"])
def test_step_updates_pool_in_place(name, step_programs, monkeypatch):
    """The step program donates its cache and XLA takes the donation
    (the alias covers the whole pool), and no instruction makes a
    pool-sized array but the layer loop that carries the pool and the
    in-place row writes: no per-layer slice, no token-major re-layout for
    a scatter and back, no transpose of a vmapped carry, no copy."""
    from repro.kernels import ops
    # the program picks interpret mode by the platform it runs on, which
    # is the CPU here; the chip compiles the kernels
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    fn, args, pool_bytes = step_programs[name]
    compiled = _compile(fn, *args)
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    pools = [s.shape for s in jax.tree.leaves(args[1])
             if len(s.shape) >= 5 and s.shape[-2:] == (STEP_PAGE, DH)]
    assert pools, "no pool leaf among the program's cache argument"
    shapes = set()
    for shp in pools:
        # the whole pool, one layer of it, and (mixture) one expert's
        shapes |= {shp, shp[1:], shp[:1] + shp[2:]}
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _pool_copies(text, shapes) == []
