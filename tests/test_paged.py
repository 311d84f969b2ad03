"""Paged KV cache correctness.

* The paged Pallas decode kernel must match the jnp paged oracle (which is
  itself defined as gather-then-contiguous-oracle).
* A paged ``SlotServer`` must produce greedy outputs identical to the
  contiguous-cache path for every attention family — and a request whose
  output exceeds its initial block reservation must complete un-truncated
  (impossible with fixed cache rows).
* The block allocator must recycle blocks across requests, block admission
  (not drop requests) when the pool is momentarily full, and fail loudly
  when a growing request exhausts it.
* Capacity retirement is exact (position cache_len - 1 decodable) and
  marks ``Request.truncated`` instead of masquerading as completion.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.core.router import CentroidRouter, RouterConfig
from repro.kernels import ref
from repro.kernels.decode_attention import paged_decode_attention
from repro.models import build_model
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import (BlockAllocator, MixtureSlotServer,
                                   Request, SlotServer)

from test_scheduler import engine_greedy, make_requests

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# Paged decode kernel vs jnp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,NB,block,H,KV,dh", [
    (2, 4, 32, 4, 4, 64),     # MHA
    (3, 8, 16, 8, 2, 64),     # GQA 4:1
    (1, 4, 64, 4, 1, 128),    # MQA, MXU-aligned head dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_kernel(B, NB, block, H, KV, dh, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    P = B * NB + 3                        # pool bigger than needed
    q = rand(ks[0], (B, H, dh), dtype)
    kp = rand(ks[1], (P, KV, block, dh), dtype)
    vp = rand(ks[2], (P, KV, block, dh), dtype)
    rng = np.random.default_rng(0)
    # distinct physical blocks per slot; block 0 reserved (scratch)
    bt = jnp.asarray(rng.permutation(np.arange(1, P))[:B * NB]
                     .reshape(B, NB), jnp.int32)
    pos = jax.random.randint(ks[3], (B,), 0, NB * block)
    out = paged_decode_attention(q, kp, vp, pos, bt, interpret=True)
    want = ref.paged_decode_attention_ref(q, kp, vp, pos, bt)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("pos_vals", [(3, 60), (64, 200), (63, 64)])
def test_paged_decode_kernel_ring(pos_vals):
    """window > 0: the slot's logical span NB·block is a ring buffer."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, NB, block, H, KV, dh = 2, 4, 16, 4, 2, 64
    P = B * NB + 1
    q = rand(ks[0], (B, H, dh), jnp.float32)
    kp = rand(ks[1], (P, KV, block, dh), jnp.float32)
    vp = rand(ks[2], (P, KV, block, dh), jnp.float32)
    rng = np.random.default_rng(1)
    bt = jnp.asarray(rng.permutation(np.arange(1, P)).reshape(B, NB),
                     jnp.int32)
    pos = jnp.asarray(pos_vals, jnp.int32)
    out = paged_decode_attention(q, kp, vp, pos, bt, window=NB * block,
                                 interpret=True)
    want = ref.paged_decode_attention_ref(q, kp, vp, pos, bt,
                                          window=NB * block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("block,NB", [(8, 6), (16, 4), (32, 3), (64, 2)])
@pytest.mark.parametrize("bps", [2, 3, 4])
def test_paged_decode_kernel_blocks_per_step(block, NB, bps):
    """Multi-block grid steps (wider KV tiles over the scalar-prefetched
    table) must be bit-identical to bps=1: sub-tiles accumulate in
    ascending logical order, past-the-horizon sub-tiles are skipped via
    the pos-derived ``live`` bound, and the padded tail when bps does not
    divide NB is killed by the ``ki < nb`` guard."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B, H, KV, dh = 3, 8, 4, 32
    P = B * NB + 2
    q = rand(ks[0], (B, H, dh), jnp.float32)
    kp = rand(ks[1], (P, KV, block, dh), jnp.float32)
    vp = rand(ks[2], (P, KV, block, dh), jnp.float32)
    rng = np.random.default_rng(3)
    bt = jnp.asarray(rng.permutation(np.arange(1, P))[:B * NB]
                     .reshape(B, NB), jnp.int32)
    # cover empty, mid-block, block-boundary and full horizons
    pos = jnp.asarray([0, block * (NB // 2), NB * block - 1][:B], jnp.int32)
    base = paged_decode_attention(q, kp, vp, pos, bt, interpret=True)
    want = ref.paged_decode_attention_ref(q, kp, vp, pos, bt)
    out = paged_decode_attention(q, kp, vp, pos, bt, blocks_per_step=bps,
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # ring-window variant keeps the whole span live once wrapped
    outw = paged_decode_attention(q, kp, vp, pos, bt, window=NB * block,
                                  blocks_per_step=bps, interpret=True)
    wantw = ref.paged_decode_attention_ref(q, kp, vp, pos, bt,
                                           window=NB * block)
    np.testing.assert_allclose(np.asarray(outw), np.asarray(wantw),
                               rtol=2e-5, atol=2e-5)


def test_paged_ref_equals_contiguous_gather():
    """The paged oracle over an identity block table IS the contiguous
    oracle — the indirection is pure layout."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    B, NB, block, H, KV, dh = 2, 4, 16, 4, 2, 32
    q = rand(ks[0], (B, H, dh), jnp.float32)
    k = rand(ks[1], (B, NB * block, KV, dh), jnp.float32)
    v = rand(ks[2], (B, NB * block, KV, dh), jnp.float32)
    pos = jax.random.randint(ks[3], (B,), 0, NB * block)
    kp = jnp.swapaxes(k.reshape(B * NB, block, KV, dh), 1, 2)
    vp = jnp.swapaxes(v.reshape(B * NB, block, KV, dh), 1, 2)
    bt = jnp.arange(B * NB, dtype=jnp.int32).reshape(B, NB)
    got = ref.paged_decode_attention_ref(q, kp, vp, pos, bt)
    want = ref.decode_attention_ref(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Block allocator
# ---------------------------------------------------------------------------

def test_block_allocator_recycles_and_reserves_scratch():
    alloc = BlockAllocator(6)             # blocks 1..5 allocatable
    a = alloc.alloc(3)
    assert a is not None and len(set(a)) == 3 and 0 not in a
    assert alloc.alloc(3) is None         # only 2 left: all-or-nothing
    assert alloc.n_free == 2              # the failed alloc took nothing
    b = alloc.alloc(2)
    assert alloc.n_free == 0
    alloc.free(a)
    c = alloc.alloc(3)
    assert sorted(c) == sorted(a)         # recycled
    assert 0 not in set(b) | set(c)
    with pytest.raises(ValueError):
        BlockAllocator(1)                 # scratch block alone is no pool


# ---------------------------------------------------------------------------
# Paged SlotServer == contiguous SlotServer (per family)
# ---------------------------------------------------------------------------

PAGED_FAMILY_ARCHS = [
    ("qwen3_8b", "dense"),
    ("deepseek_moe_16b", "moe"),
    ("internvl2_2b", "vlm"),
    ("whisper_small", "audio"),
    ("zamba2_2_7b", "hybrid"),
    ("xlstm_125m", "ssm"),      # no pageable leaves: must degrade cleanly
]


@pytest.mark.parametrize("arch,family", PAGED_FAMILY_ARCHS)
def test_paged_slot_server_matches_contiguous(arch, family):
    cfg = get_smoke_config(arch).reduced(vocab=256)
    assert cfg.family == family
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache_len = 40
    lens, budgets = (7, 11, 5), (4, 3, 5)

    ref_srv = SlotServer(model, params, n_slots=2, cache_len=cache_len)
    want = ref_srv.serve(make_requests(cfg, lens, budgets))

    paged_q = make_requests(cfg, lens, budgets)
    paged = SlotServer(model, params, n_slots=2, cache_len=cache_len,
                       page_block=8)
    got = paged.serve(paged_q)
    assert set(got) == set(want)
    for rid in want:
        assert got[rid] == want[rid], (arch, rid, got[rid], want[rid])
    assert paged.active == []
    assert not any(r.truncated for r in paged_q)
    if paged.paged:
        assert paged.allocator.n_free == paged.allocator.n_blocks - 1


def test_paged_slot_server_use_kernel_parity():
    """The Pallas paged decode kernel (interpret mode on CPU) must be
    reachable from continuous batching and agree with both jnp paths."""
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def queue():
        return make_requests(cfg, (8, 8), (3, 3), seed=7)

    want = SlotServer(model, params, n_slots=2, cache_len=16).serve(queue())
    jnp_paged = SlotServer(model, params, n_slots=2, cache_len=16,
                           page_block=8).serve(queue())
    ker_paged = SlotServer(model, params, n_slots=2, cache_len=16,
                           page_block=8, use_kernel=True).serve(queue())
    assert want == jnp_paged == ker_paged


def test_paged_sliding_window_ring_parity():
    """Windowed configs page the ring: the slot's bounded span is fully
    reserved at admission and wraps exactly like the contiguous ring."""
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=128, sliding_window=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def queue():
        return make_requests(cfg, (6, 4), (12, 14), seed=3)

    want = SlotServer(model, params, n_slots=2, cache_len=32).serve(queue())
    got = SlotServer(model, params, n_slots=2, cache_len=32,
                     page_block=4).serve(queue())
    assert want == got
    assert any(len(v) > 8 for v in got.values())   # decoded past the window


# ---------------------------------------------------------------------------
# The tentpole property: decode past the initial reservation
# ---------------------------------------------------------------------------

def test_paged_request_grows_past_initial_reservation():
    """A request whose output exceeds its admission-time block reservation
    completes un-truncated — the lazy allocator grows it block by block."""
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.random.default_rng(5).integers(0, cfg.vocab, size=4) \
        .astype(np.int32)
    req = Request(0, prompt, max_new=20)
    srv = SlotServer(model, params, n_slots=1, cache_len=32, page_block=8,
                     pool_blocks=5)
    assert srv.admit(req)
    assert int(srv.n_alloc[0]) == 1       # prompt fits one block
    peak = 1
    while srv.active:
        srv.step()
        peak = max(peak, int(srv.n_alloc[0]) or peak)
    assert peak == 3                      # grew to cover positions 4..23
    assert len(req.out) == 20 and not req.truncated
    want = SlotServer(model, params, n_slots=1, cache_len=32).serve(
        [Request(0, prompt, max_new=20)])
    assert req.out == want[0]


def test_paged_admission_waits_for_free_blocks():
    """A momentarily-full pool delays admission (continuous admission picks
    the request up when retirements free blocks) — it never drops it."""
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab, size=5).astype(np.int32)
               for _ in range(3)]

    def queue():
        return [Request(i, p, max_new=3) for i, p in enumerate(prompts)]

    want = SlotServer(model, params, n_slots=2, cache_len=16).serve(queue())
    # 1 usable block (pool=2 incl. scratch): strictly one request in flight
    srv = SlotServer(model, params, n_slots=2, cache_len=16, page_block=8,
                     pool_blocks=2)
    got = srv.serve(queue())
    assert got == want
    assert srv.allocator.n_free == 1


def test_paged_pool_exhaustion_raises():
    """Growth past what the pool can hold fails loudly (preemption is the
    roadmap answer), never silently truncates."""
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.random.default_rng(2).integers(0, cfg.vocab, size=5) \
        .astype(np.int32)
    srv = SlotServer(model, params, n_slots=1, cache_len=32, page_block=8,
                     pool_blocks=2)
    with pytest.raises(RuntimeError, match="pool exhausted"):
        srv.serve([Request(0, prompt, max_new=20)])


# ---------------------------------------------------------------------------
# Capacity-exact truncation semantics (contiguous AND paged)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("page_block", [0, 8])
def test_capacity_retirement_is_exact_and_flagged(page_block):
    """cache_len=12, prompt=8 → exactly 5 tokens fit (1 prefill + writes at
    positions 8..11). The seed's off-by-one stopped at 4; and a capacity
    retirement must be distinguishable from completion."""
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.random.default_rng(3).integers(0, cfg.vocab, size=8) \
        .astype(np.int32)
    cache_len = 12

    trunc = Request(0, prompt, max_new=10)
    srv = SlotServer(model, params, n_slots=1, cache_len=cache_len,
                     page_block=page_block)
    out = srv.serve([trunc])
    assert len(out[0]) == 5 and trunc.truncated

    # greedy reference: the truncated output is an exact prefix
    engine = ServeEngine(model, cache_len)
    want = engine_greedy(engine, params, Request(1, prompt, max_new=5))
    assert out[0] == want

    # a request that finishes exactly at capacity is NOT truncated
    exact = Request(2, prompt, max_new=5)
    out2 = SlotServer(model, params, n_slots=1, cache_len=cache_len,
                      page_block=page_block).serve([exact])
    assert out2[2] == want and not exact.truncated


@pytest.mark.parametrize("page_block", [0, 8])
def test_prompt_exceeding_context_rejected_before_prefill(page_block):
    """W > cache_len cannot even prefill into a cache row: admission must
    reject it with a clear error, not crash inside jnp.pad mid-queue."""
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.random.default_rng(6).integers(0, cfg.vocab, size=20) \
        .astype(np.int32)
    srv = SlotServer(model, params, n_slots=1, cache_len=16,
                     page_block=page_block)
    with pytest.raises(ValueError, match="serving context"):
        srv.serve([Request(0, prompt, max_new=4)])


def test_paged_degrades_to_direct_for_recurrent_family():
    """ssm has no pageable cache leaves: page_block must not spin up pool
    accounting that backs no memory (a tiny pool used to raise 'pool
    exhausted' here even though nothing was paged)."""
    cfg = get_smoke_config("xlstm_125m").reduced(vocab=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    srv = SlotServer(model, params, n_slots=2, cache_len=32, page_block=8,
                     pool_blocks=2)
    assert not srv.paged
    got = srv.serve(make_requests(cfg, (6, 9), (8, 5)))
    want = SlotServer(model, params, n_slots=2, cache_len=32).serve(
        make_requests(cfg, (6, 9), (8, 5)))
    assert got == want


@pytest.mark.parametrize("page_block", [0, 8])
def test_prompt_filling_context_retires_at_admission(page_block):
    """prompt_len == cache_len: the request keeps its single prefill token
    and retires truncated without ever occupying a slot."""
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.random.default_rng(4).integers(0, cfg.vocab, size=16) \
        .astype(np.int32)
    req = Request(0, prompt, max_new=4)
    srv = SlotServer(model, params, n_slots=1, cache_len=16,
                     page_block=page_block)
    out = srv.serve([req])
    assert len(out[0]) == 1 and req.truncated
    assert srv.active == []
    engine = ServeEngine(model, 16)
    assert out[0] == engine_greedy(engine, params,
                                   Request(1, prompt, max_new=1))


# ---------------------------------------------------------------------------
# Paged mixture core (stacked dexpert dim shares one block table per slot)
# ---------------------------------------------------------------------------

def test_paged_mixture_matches_contiguous_mixture():
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
                             cache_len=24).serve(queue())
    got = MixtureSlotServer(model, experts, router, n_slots=2, cache_len=24,
                            page_block=8).serve(queue())
    assert got == want


# ---------------------------------------------------------------------------
# Sharding: block-pool placement
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen3_8b", "zamba2_2_7b"])
def test_paged_pool_pspec_layout(arch):
    """Pool leaves shard the physical-block axis over the kv-cache batch
    axes and kv-heads over model; direct leaves keep their contiguous
    placement; the stacked variant carries ``dexpert`` (pod) at axis 1."""
    from jax.sharding import Mesh, PartitionSpec
    from repro.sharding.rules import (cache_pspec_tree, logical_rules,
                                      paged_pool_pspec_tree,
                                      stacked_cache_pspec_tree)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("pod", "data", "model"))
    rules = logical_rules(multi_pod=True, decentralized=True)
    model = build_model(get_smoke_config(arch))
    spec = model.cache_spec(8)
    shapes = model.paged_cache_shapes(4, 16, 8, 32)
    specs = paged_pool_pspec_tree(shapes, rules, mesh, spec.paged.seq_axes)
    plain = cache_pspec_tree(model.cache_shapes(4, 32), rules, mesh)

    def check(ns, leaf, s_ax, plain_ns):
        pspec = tuple(ns.spec) + (None,) * (len(leaf.shape) - len(ns.spec))
        if s_ax < 0:       # direct leaf: contiguous placement preserved
            want = tuple(plain_ns.spec)
            want += (None,) * (len(leaf.shape) - len(want))
            assert pspec == want, (leaf.shape, pspec, want)
        else:              # pool leaf (scan, P, KV, block, dh)
            # PartitionSpec normalizes a one-axis tuple to its axis name
            want_pool = PartitionSpec(rules["kv_cache_batch"])[0]
            assert pspec[s_ax - 1] == want_pool, (leaf.shape, pspec)
            assert pspec[-2] is None            # block interior never cut

    jax.tree.map(check, specs, shapes, spec.paged.seq_axes, plain)

    K = 2
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape[:1] + (K,) + s.shape[1:],
                                       s.dtype), shapes)
    sspecs = stacked_cache_pspec_tree(stacked, rules, mesh,
                                      spec.paged.seq_axes)
    jax.tree.map(
        lambda ns, leaf: np.testing.assert_equal(
            (tuple(ns.spec) + (None,) * len(leaf.shape))[1], "pod"),
        sspecs, stacked)
