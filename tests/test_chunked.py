"""Chunked-prefill continuous batching correctness.

* The prefix-aware chunked-prefill Pallas kernel must match the jnp paged
  oracle (gather-then-contiguous, one query row per chunk position).
* A chunked ``SlotServer`` must produce greedy outputs identical to the
  monolithic-prefill path for EVERY model family — including chunk
  boundaries that straddle page blocks and final chunks shorter than the
  chunk size.
* The token-budget step loop must never starve decode: every decoding slot
  makes progress on every step while a long prompt prefills, and a budget
  too small to co-schedule defers the chunk (not the decode).
* Exhausting ``serve(max_steps=…)`` with a request still mid-prefill
  reports it as dropped WITH its partial position (the regression this PR
  fixes: such a request was neither queued nor decoding).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.core.router import CentroidRouter, RouterConfig
from repro.kernels import ref
from repro.kernels.decode_attention import chunk_prefill_attention
from repro.models import build_model
from repro.serve.scheduler import (MixtureSlotServer, Request, SlotServer)

from test_scheduler import make_requests

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}
LAYERS = 3          # the kernels read one layer of a layer-stacked pool


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# Chunked-prefill kernel vs jnp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("C,NB,block,H,KV,dh,start", [
    (8, 4, 16, 4, 4, 64, 0),      # MHA, chunk 0
    (8, 4, 16, 4, 4, 64, 24),     # MHA, mid-prompt chunk
    (6, 8, 8, 8, 2, 64, 34),      # GQA 4:1, chunk straddles a block
    (16, 4, 32, 4, 1, 128, 112),  # MQA, final chunk ends at capacity
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("layer,bps", [(0, 1), (LAYERS - 1, 4)])
def test_chunk_prefill_kernel(C, NB, block, H, KV, dh, start, dtype, layer,
                              bps):
    """The kernel reads ``layer`` of a 3-layer pool (the first and the
    last) through its scalar-prefetched layer index."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    P = NB + 3                            # pool bigger than needed
    q = rand(ks[0], (C, H, dh), dtype)
    kp = rand(ks[1], (LAYERS, P, KV, block, dh), dtype)
    vp = rand(ks[2], (LAYERS, P, KV, block, dh), dtype)
    rng = np.random.default_rng(0)
    bt = jnp.asarray(rng.permutation(np.arange(1, P))[:NB], jnp.int32)
    out = chunk_prefill_attention(q, kp, vp, jnp.int32(layer),
                                  jnp.int32(start), bt, blocks_per_step=bps,
                                  interpret=True)
    want = ref.chunk_prefill_attention_ref(q, kp, vp, layer,
                                           jnp.int32(start), bt)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("block,NB,start", [(8, 6, 0), (8, 6, 19),
                                            (16, 4, 33), (32, 3, 5)])
@pytest.mark.parametrize("bps", [2, 3, 4])
def test_chunk_prefill_kernel_blocks_per_step(block, NB, start, bps):
    """Multi-block grid steps must be bit-identical to bps=1 — the horizon
    here is the last query position's block (start + C - 1), and the
    padded tail when bps does not divide NB is killed by ``ki < nb``."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    C, H, KV, dh = 5, 8, 4, 32
    P = NB + 2
    q = rand(ks[0], (C, H, dh), jnp.float32)
    kp = rand(ks[1], (1, P, KV, block, dh), jnp.float32)
    vp = rand(ks[2], (1, P, KV, block, dh), jnp.float32)
    rng = np.random.default_rng(2)
    bt = jnp.asarray(rng.permutation(np.arange(1, P))[:NB], jnp.int32)
    base = chunk_prefill_attention(q, kp, vp, 0, jnp.int32(start), bt,
                                   interpret=True)
    want = ref.chunk_prefill_attention_ref(q, kp, vp, 0, jnp.int32(start),
                                           bt)
    out = chunk_prefill_attention(q, kp, vp, 0, jnp.int32(start), bt,
                                  blocks_per_step=bps, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_chunk_prefill_ref_row0_is_decode_ref():
    """A one-row chunk IS a single decode query: the chunk oracle must
    degenerate to the paged decode oracle."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    NB, block, H, KV, dh = 4, 8, 4, 2, 32
    P = NB + 1
    q = rand(ks[0], (1, H, dh), jnp.float32)
    kp = rand(ks[1], (LAYERS, P, KV, block, dh), jnp.float32)
    vp = rand(ks[2], (LAYERS, P, KV, block, dh), jnp.float32)
    bt = jnp.arange(1, NB + 1, dtype=jnp.int32)
    start = jnp.int32(13)
    got = ref.chunk_prefill_attention_ref(q, kp, vp, 1, start, bt)
    want = ref.paged_decode_attention_ref(q, kp, vp, 1, start[None],
                                          bt[None])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Chunked == monolithic greedy, per family
# ---------------------------------------------------------------------------

# chunk straddles page_block=8 for attention families; ssm/hybrid need the
# chunk aligned to the chunkwise-scan length (16 on the smoke configs)
CHUNKED_FAMILY_ARCHS = [
    ("qwen3_8b", "dense", 6),
    ("deepseek_moe_16b", "moe", 6),
    ("internvl2_2b", "vlm", 8),
    ("whisper_small", "audio", 6),
    ("zamba2_2_7b", "hybrid", 16),
    ("xlstm_125m", "ssm", 16),    # no pageable leaves: carry-only chunks
]


@pytest.mark.parametrize("arch,family,chunk", CHUNKED_FAMILY_ARCHS)
def test_chunked_slot_server_matches_monolithic(arch, family, chunk):
    """Prompt lengths straddle chunk boundaries both ways (shorter than one
    chunk, non-multiples) and the queue overcommits the slots."""
    cfg = get_smoke_config(arch).reduced(vocab=256)
    assert cfg.family == family
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache_len = 48
    lens, budgets = (7, 11, 5), (4, 3, 5)

    ref_srv = SlotServer(model, params, n_slots=2, cache_len=cache_len,
                         page_block=8)
    want = ref_srv.serve(make_requests(cfg, lens, budgets))

    srv = SlotServer(model, params, n_slots=2, cache_len=cache_len,
                     page_block=8, chunk=chunk)
    chunked_q = make_requests(cfg, lens, budgets)
    got = srv.serve(chunked_q)
    assert set(got) == set(want)
    for rid in want:
        assert got[rid] == want[rid], (arch, rid, got[rid], want[rid])
    assert srv.active == []
    if srv.paged:     # every block returned at retirement
        assert srv.allocator.n_free == srv.allocator.n_blocks - 1
    # TTFT / completion stamps populated by the scheduler
    assert all(0 < r.t_first <= r.t_done for r in chunked_q)


def test_chunk_boundaries_straddle_page_blocks():
    """chunk=6 over page_block=4: every chunk write crosses a physical
    block boundary, and the final chunk is a partial one."""
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=256)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    for n in (3, 6, 10, 13):              # <1 chunk, exact, straddling
        q = [Request(0, np.random.default_rng(n).integers(
            0, cfg.vocab, size=n).astype(np.int32), 5)]
        want = SlotServer(model, params, n_slots=1, cache_len=32,
                          page_block=4).serve(list(q))
        got = SlotServer(model, params, n_slots=1, cache_len=32,
                         page_block=4, chunk=6).serve(
            [Request(0, q[0].tokens, 5)])
        assert got == want, (n, got, want)


def test_chunked_use_kernel_parity():
    """The prefix-aware chunk kernel (interpret mode on CPU) must be
    reachable from continuous batching and agree with both jnp paths."""
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def queue():
        return make_requests(cfg, (8, 8), (3, 3), seed=7)

    want = SlotServer(model, params, n_slots=2, cache_len=16,
                      page_block=8).serve(queue())
    jnp_c = SlotServer(model, params, n_slots=2, cache_len=16, page_block=8,
                       chunk=4).serve(queue())
    ker_c = SlotServer(model, params, n_slots=2, cache_len=16, page_block=8,
                       chunk=4, use_kernel=True).serve(queue())
    assert want == jnp_c == ker_c


def test_chunked_edge_budgets_and_context_fill():
    """max_new == 1 retires straight out of the prefill transition, and a
    prompt that fills the context keeps its single token and retires
    truncated without decoding — matching monolithic semantics."""
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.random.default_rng(4).integers(0, cfg.vocab, size=16) \
        .astype(np.int32)

    one = Request(0, prompt, max_new=1)
    out = SlotServer(model, params, n_slots=1, cache_len=32, page_block=8,
                     chunk=6).serve([one])
    want = SlotServer(model, params, n_slots=1, cache_len=32,
                      page_block=8).serve([Request(0, prompt, max_new=1)])
    # the budget is exactly the prefill token (the monolithic path used to
    # decode one token PAST the budget here)
    assert out == want and len(out[0]) == 1 and not one.truncated

    fill = Request(1, prompt, max_new=4)
    srv = SlotServer(model, params, n_slots=1, cache_len=16, page_block=8,
                     chunk=6)
    out2 = srv.serve([fill])
    wref = SlotServer(model, params, n_slots=1, cache_len=16,
                      page_block=8).serve([Request(1, prompt, max_new=4)])
    assert out2 == wref
    assert len(out2[1]) == 1 and fill.truncated
    assert srv.active == []
    assert srv.allocator.n_free == srv.allocator.n_blocks - 1


# ---------------------------------------------------------------------------
# Token budget: decode never starves while a long prompt prefills
# ---------------------------------------------------------------------------

def test_token_budget_starvation_freedom():
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    srv = SlotServer(model, params, n_slots=3, cache_len=64, page_block=8,
                     chunk=8)
    # two short requests reach decode first
    for rid in (0, 1):
        assert srv.admit(Request(
            rid, rng.integers(0, cfg.vocab, size=4).astype(np.int32), 40))
    while srv.prefill_order:
        srv.step()
    assert len(srv.decoding) == 2
    # a long prompt starts chunked prefill alongside them
    assert srv.admit(Request(
        2, rng.integers(0, cfg.vocab, size=48).astype(np.int32), 4))
    long_slot = srv.prefill_order[0]
    steps_to_finish_prefill = 0
    while srv.prefilling[long_slot]:
        dec = list(srv.decoding)
        pos_before = srv.pos[dec].copy()
        pf_before = int(srv.prefill_pos[long_slot])
        srv.step()
        # every decoding slot advanced this step (no stop-the-world)
        assert (srv.pos[dec] == pos_before + 1).all()
        assert int(srv.prefill_pos[long_slot]) == pf_before + srv.chunk \
            or not srv.prefilling[long_slot]
        steps_to_finish_prefill += 1
    assert steps_to_finish_prefill == 6          # ceil(48 / 8)


def test_small_token_budget_defers_chunk_not_decode():
    """budget < decoding + chunk ⇒ the chunk waits, decode still runs;
    the queue still completes with the right outputs."""
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (4, 4, 20)]

    def queue():
        return [Request(i, p, m) for i, (p, m)
                in enumerate(zip(prompts, (6, 6, 3)))]

    want = SlotServer(model, params, n_slots=3, cache_len=40,
                      page_block=8).serve(queue())
    srv = SlotServer(model, params, n_slots=3, cache_len=40, page_block=8,
                     chunk=8, token_budget=9)
    # with 2 slots decoding, 2 + 8 > 9: the long prompt's chunks only run
    # once a decoder retires — but decode is never paused
    got = srv.serve(queue())
    assert got == want


# ---------------------------------------------------------------------------
# max_steps exhaustion: mid-prefill requests are dropped WITH position
# ---------------------------------------------------------------------------

def test_midprefill_request_reported_dropped_with_partial_position():
    """Regression: a request still chunk-prefilling at max_steps exhaustion
    was neither 'queued' nor decoding — it must be counted as dropped and
    report its partial prefill position."""
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.random.default_rng(13).integers(0, cfg.vocab, size=40) \
        .astype(np.int32)
    srv = SlotServer(model, params, n_slots=1, cache_len=64, page_block=8,
                     chunk=8)
    with pytest.raises(RuntimeError, match=r"prefill 16/40"):
        srv.serve([Request(7, prompt, max_new=4)], max_steps=2)


# ---------------------------------------------------------------------------
# Config fences
# ---------------------------------------------------------------------------

def test_chunked_requires_paged_for_attention_families():
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="paged pool"):
        SlotServer(model, params, n_slots=1, cache_len=16, chunk=4)


def test_chunked_rejects_misaligned_recurrent_chunk():
    cfg = get_smoke_config("zamba2_2_7b").reduced(vocab=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="chunkwise-scan"):
        SlotServer(model, params, n_slots=1, cache_len=32, page_block=8,
                   chunk=6)


def test_chunked_rejects_sliding_window():
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=64, sliding_window=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="sliding-window"):
        SlotServer(model, params, n_slots=1, cache_len=32, page_block=4,
                   chunk=4)


# ---------------------------------------------------------------------------
# Sharding: chunk-carry placement
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen3_8b", "whisper_small",
                                  "zamba2_2_7b"])
def test_chunk_carry_pspec_layout(arch):
    """A chunked-prefill carry is batch-extent-1 state: everything is
    replicated except full per-layer cross-attention KV rows, whose kv-head
    axis follows the model axis when divisible."""
    from jax.sharding import Mesh
    from repro.sharding.rules import chunk_carry_pspec_tree, logical_rules

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("pod", "data", "model"))
    rules = logical_rules(multi_pod=True, decentralized=True)
    cfg = get_smoke_config(arch).reduced(vocab=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b = make_requests(cfg, (6,), (2,))[0].batch()
    carry = model.init_chunk_carry(params, b, 32)
    specs = chunk_carry_pspec_tree(
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                     carry), rules, mesh)

    def check(ns, leaf):
        pspec = tuple(ns.spec) + (None,) * (len(leaf.shape) - len(ns.spec))
        if len(leaf.shape) == 5 and leaf.shape[-2] > 1 and \
                leaf.shape[-2] % mesh.shape["model"] == 0:
            assert pspec[-2] == rules["kv_cache_heads"], (leaf.shape, pspec)
            pspec = pspec[:-2] + (None,) + pspec[-1:]
        assert all(p is None for p in pspec), (leaf.shape, pspec)

    jax.tree.map(check, specs, carry)


# ---------------------------------------------------------------------------
# Stacked mixture core: chunked == monolithic (shared block table over K)
# ---------------------------------------------------------------------------

def test_chunked_mixture_matches_monolithic():
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=128)
    model = build_model(cfg)
    K, Df, B = 3, 16, 4
    experts = [model.init(jax.random.PRNGKey(k)) for k in range(K)]
    rng = np.random.default_rng(1)
    router = CentroidRouter(
        jnp.asarray(rng.normal(size=(K, Df)), jnp.float32),
        RouterConfig(top_k=2))
    toks = rng.integers(0, cfg.vocab, size=(B, 10)).astype(np.int32)
    feats = rng.normal(size=(B, Df)).astype(np.float32)

    def queue():
        return [Request(i, toks[i], 5, features=feats[i]) for i in range(B)]

    want = MixtureSlotServer(model, experts, router, n_slots=2,
                             cache_len=24, page_block=8).serve(queue())
    got = MixtureSlotServer(model, experts, router, n_slots=2, cache_len=24,
                            page_block=8, chunk=4).serve(queue())
    assert got == want
