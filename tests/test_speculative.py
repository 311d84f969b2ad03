"""Speculative decoding over the paged pool: draft + multi-token verify.

The hard invariant under test: speculation is a LATENCY lever only —
seeded sampled and greedy requests produce token-for-token identical
outputs (and identical finish reasons) with speculation on and off,
across every capable cache family, both draft sources, and every
scheduler interaction (chunked co-scheduling, pool pressure, stop tokens
landing at every offset of a span, the sanitizer's span-write plan).
Families that cannot roll a span back (ssm/hybrid — recurrent state has
no positional rollback) must degrade silently to vanilla decode.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.core.router import CentroidRouter, RouterConfig
from repro.kernels import ref
from repro.kernels.decode_attention import paged_verify_attention
from repro.models import build_model
from repro.serve.api import EngineConfig, SamplingParams
from repro.serve.fused import verify_epilogue
from repro.serve.scheduler import (DecentralizedSlotServer,
                                   MixtureSlotServer, Request, SlotServer)
from repro.serve.speculate import NGramProposer

FAMILY_ARCHS = [
    ("qwen3_8b", "dense"),
    ("deepseek_moe_16b", "moe"),
    ("internvl2_2b", "vlm"),
    ("whisper_small", "audio"),
    ("xlstm_125m", "ssm"),
    ("zamba2_2_7b", "hybrid"),
]

PROMPT_LENS = (7, 11, 5, 9)
SPEC_LEN = 4


def _extras(cfg, rng):
    extras = {}
    if cfg.family == "vlm":
        extras["patches"] = rng.normal(
            size=(cfg.n_patches, cfg.vision_dim)).astype(np.float32)
    if cfg.family == "audio":
        extras["frames"] = rng.normal(
            size=(cfg.n_audio_frames, cfg.audio_dim)).astype(np.float32)
    return extras


def _prompts(cfg, seed=42):
    """Period-4 repetitive prompts (the workload n-gram lookup targets)
    plus the per-family modality extras, rebuilt identically per call."""
    rng = np.random.default_rng(seed)
    ps = []
    for n in PROMPT_LENS:
        base = rng.integers(1, cfg.vocab, size=4)
        ps.append(np.tile(base, n // 4 + 2)[:n].astype(np.int32))
    ex = [_extras(cfg, rng) for _ in PROMPT_LENS]
    return ps, ex


def _queue(cfg, feats=None, stop_id=None, max_new=12):
    """Greedy + seeded-sampled requests in one queue (and, with a probed
    ``stop_id``, a mid-stream stop) — the parity comparison surface."""
    ps, ex = _prompts(cfg)
    f = (lambda i: feats[i]) if feats is not None else (lambda i: None)
    q = [Request(0, ps[0], max_new, extras=ex[0], features=f(0)),
         Request(1, ps[1], max_new, extras=ex[1], features=f(1),
                 params=SamplingParams(max_new=max_new, temperature=0.8,
                                       top_k=8, seed=123)),
         Request(2, ps[2], max_new, extras=ex[2], features=f(2),
                 params=SamplingParams(max_new=max_new, temperature=0.6,
                                       top_k=4, seed=7))]
    if stop_id is not None:
        q.append(Request(3, ps[3], max_new, extras=ex[3], features=f(3),
                         params=SamplingParams(
                             max_new=max_new, stop_token_ids=(stop_id,))))
    return q


def _dense_setup(vocab=256):
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=vocab)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _cfg(speculative=None, spec_len=SPEC_LEN, **kw):
    base = dict(n_slots=4, cache_len=64, paged=True, page_block=8,
                fused_step=True)
    base.update(kw)
    return EngineConfig(speculative=speculative, spec_len=spec_len, **base)


def _parity(cfg, model, mk_vanilla, mk_spec, feats=None, stop_id=None):
    """Drive identical queues through both servers; assert identical
    tokens AND identical finish reasons for every request."""
    qv = _queue(cfg, feats, stop_id)
    srv_v = mk_vanilla()
    got_v = srv_v.serve(qv)
    qs = _queue(cfg, feats, stop_id)
    srv_s = mk_spec()
    got_s = srv_s.serve(qs)
    assert got_v == got_s, (got_v, got_s)
    for rv, rs in zip(qv, qs):
        assert rv.finish_reason == rs.finish_reason, \
            (rv.rid, rv.finish_reason, rs.finish_reason)
    return srv_v, srv_s


# ---------------------------------------------------------------------
# Parity across the cache families (greedy AND seeded-sampled per queue)
# ---------------------------------------------------------------------

@pytest.mark.parametrize("arch,family", FAMILY_ARCHS)
def test_spec_family_parity(arch, family):
    cfg = get_smoke_config(arch).reduced(vocab=256)
    assert cfg.family == family
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache_len = 96 if family == "vlm" else 64   # room for the image prefix

    def mk(spec):
        return SlotServer(model, params, config=_cfg(
            speculative="ngram" if spec else None, cache_len=cache_len))

    _, srv_s = _parity(cfg, model, lambda: mk(False), lambda: mk(True))
    if model.speculative_capable:
        assert srv_s._can_spec and srv_s.stats()["spec_steps"] > 0
    else:
        # recurrent / sliding-window state can't roll a span back: the
        # server must degrade to vanilla decode, silently
        assert not srv_s._can_spec
        assert srv_s.stats().get("spec_steps") == 0


def test_spec_len_one_is_vanilla():
    """spec_len == 1 IS vanilla decode: no drafts, no verify dispatch."""
    cfg, model, params = _dense_setup()

    def mk(spec_len):
        return SlotServer(model, params,
                          config=_cfg("ngram", spec_len=spec_len))

    srv_v = SlotServer(model, params, config=_cfg(None))
    got_v = srv_v.serve(_queue(cfg))
    srv_1 = mk(1)
    assert not srv_1._can_spec
    assert srv_1.serve(_queue(cfg)) == got_v
    assert srv_1.stats()["spec_steps"] == 0


# ---------------------------------------------------------------------
# Accept rule: forward progress and the deterministic token match
# ---------------------------------------------------------------------

def test_all_reject_span_still_progresses():
    """Drafts that never match still emit >= 1 token per speculative
    step (the verify's position-0 score IS the vanilla next token), and
    the trajectory is untouched."""
    cfg, model, params = _dense_setup()
    srv_v = SlotServer(model, params, config=_cfg(None))
    got_v = srv_v.serve(_queue(cfg))

    srv = SlotServer(model, params, config=_cfg("ngram"))
    # worst-case proposer: every draft is a token the model can never
    # pick (ids are sampled from [0, vocab))
    srv._draft_tokens = lambda dec: jnp.full(
        (srv.n_slots, SPEC_LEN - 1), cfg.vocab - 1, jnp.int32)
    assert srv.serve(_queue(cfg)) == got_v
    st = srv.stats()
    assert st["spec_steps"] > 0
    assert st["spec_tokens"] >= st["spec_steps"]   # >= 1 token per step


def test_verify_epilogue_all_reject_and_full_accept():
    """Unit-level accept rule: a fully-matching draft row advances by the
    whole span; a fully-mismatching one advances by exactly 1 — and both
    emit the greedy-argmax (vanilla) tokens."""
    B, L, V = 2, 3, 16
    rng = np.random.default_rng(0)
    scores = jnp.asarray(rng.normal(size=(B, L, V)).astype(np.float32))
    true = np.asarray(jnp.argmax(scores, axis=-1))          # greedy rows
    drafts = np.stack([true[0, :L - 1],                     # full accept
                       (true[1, :L - 1] + 1) % V])          # full reject
    state = {"tok": jnp.zeros(B, jnp.int32),
             "pos": jnp.asarray([5, 5], jnp.int32),
             "active": jnp.ones(B, bool),
             "temps": jnp.zeros(B, jnp.float32),
             "top_ks": jnp.zeros(B, jnp.int32),
             "seeds": jnp.zeros(B, jnp.uint32),
             "counts": jnp.zeros(B, jnp.int32),
             "max_new": jnp.full(B, 100, jnp.int32),
             "stop_ids": jnp.full((B, 1), -1, jnp.int32)}
    new, toks, n_emit, done = verify_epilogue(
        scores, jnp.asarray(drafts), state, cache_len=1000)
    assert n_emit.tolist() == [L, 1]
    assert done.tolist() == [0, 0]
    assert np.array_equal(np.asarray(toks)[0], true[0])
    assert int(np.asarray(toks)[1, 0]) == int(true[1, 0])
    assert new["pos"].tolist() == [5 + L, 6]
    assert new["counts"].tolist() == [L, 1]


# ---------------------------------------------------------------------
# Stop tokens at every span offset: retire once, emit nothing past it
# ---------------------------------------------------------------------

@pytest.mark.parametrize("offset", range(SPEC_LEN))
def test_spec_stop_at_every_span_offset(offset):
    """A stop token accepted at span offset 0..L-1 must truncate the span
    on device: no tokens recorded past it, finish_reason == 'stop', and
    ``stats()['stopped']`` counts the request ONCE (the regression was a
    speculatively-finished request retiring twice)."""
    cfg, model, params = _dense_setup()
    ps, ex = _prompts(cfg)
    solo = SlotServer(model, params, config=_cfg(None))
    traj = solo.serve([Request(0, ps[0], 16)])[0]
    # token 0 comes from the prefill pick; the first decode span covers
    # traj[1..L], so traj[1 + offset] is span offset ``offset``
    stop_id = traj[1 + offset]
    first_hit = traj.index(stop_id)
    want = traj[:first_hit + 1]

    srv = SlotServer(model, params, config=_cfg("ngram"))
    # oracle drafts (the known greedy trajectory) force full-accept
    # spans, so the stop genuinely lands mid-span at the probed offset
    def oracle(dec):
        drafts = np.zeros((srv.n_slots, SPEC_LEN - 1), np.int32)
        for s in dec:
            done_n = len(srv.slot_req[s].out)
            fut = traj[done_n:done_n + SPEC_LEN - 1]
            drafts[s, :len(fut)] = fut
        return jnp.asarray(drafts)
    srv._draft_tokens = oracle
    q = [Request(0, ps[0], 16,
                 params=SamplingParams(max_new=16,
                                       stop_token_ids=(stop_id,)))]
    got = srv.serve(q)
    assert got[0] == want, (offset, got[0], want)
    assert q[0].finish_reason == "stop"
    st = srv.stats()
    assert st["stopped"] == 1          # retired exactly once
    if first_hit >= 1:     # hit at token 0 retires at admission instead
        assert st["spec_steps"] > 0


# ---------------------------------------------------------------------
# Scheduler interactions: chunked co-scheduling, pool pressure, sanitize
# ---------------------------------------------------------------------

def test_spec_parity_under_chunked_prefill():
    """Chunk co-scheduled steps fall back to vanilla decode that step;
    the trajectory must be unchanged and speculation must still engage on
    the pure-decode steps."""
    cfg, model, params = _dense_setup()

    def mk(spec):
        return SlotServer(model, params, config=_cfg(
            "ngram" if spec else None, chunked_prefill=True, chunk=8))

    _, srv_s = _parity(cfg, model, lambda: mk(False), lambda: mk(True))
    assert srv_s.stats()["spec_steps"] > 0


def test_spec_pool_pressure_falls_back_to_vanilla():
    """A pool too tight to reserve any span up front must degrade to
    vanilla steps (never deadlock, never raise) and keep parity; blocks
    freed by retirements let later spans speculate."""
    cfg, model, params = _dense_setup()
    # nb_slot = ceil(64/8) = 8; 4 slots want 32 blocks at full depth —
    # 18 usable blocks forces span-reservation failures mid-flight
    def mk(spec):
        return SlotServer(model, params, config=_cfg(
            "ngram" if spec else None, pool_blocks=19))

    _parity(cfg, model, lambda: mk(False), lambda: mk(True))


def test_spec_pool_conservation_with_sanitizer():
    """The PoolSanitizer's span-aware write plan passes every step, and
    the pool conserves: all blocks return to the free list at drain."""
    cfg, model, params = _dense_setup()
    srv = SlotServer(model, params, config=_cfg("ngram", sanitize=True))
    srv.serve(_queue(cfg))
    st = srv.stats()
    assert st["spec_steps"] > 0
    assert st["sanitize_violations"] == 0
    assert st["sanitize_checked_steps"] > 0
    assert st["pool_free_blocks"] == st["pool_blocks"] - 1  # scratch stays


# ---------------------------------------------------------------------
# Mixture core: expert-0 drafting and the decentralized deployment
# ---------------------------------------------------------------------

def _mixture_setup():
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=128)
    model = build_model(cfg)
    K, Df = 3, 16
    experts = [model.init(jax.random.PRNGKey(k)) for k in range(K)]
    rng = np.random.default_rng(1)
    router = CentroidRouter(
        jnp.asarray(rng.normal(size=(K, Df)), jnp.float32),
        RouterConfig(top_k=2))
    feats = rng.normal(size=(len(PROMPT_LENS), Df)).astype(np.float32)
    return cfg, model, experts, router, feats


@pytest.mark.parametrize("mode", ["ngram", "expert"])
def test_spec_mixture_parity(mode):
    cfg, model, experts, router, feats = _mixture_setup()

    def mk(spec):
        return MixtureSlotServer(model, experts, router, config=_cfg(
            mode if spec else None, cache_len=48, strategy="mixture"))

    _, srv_s = _parity(cfg, model, lambda: mk(False), lambda: mk(True),
                       feats=feats)
    assert srv_s.stats()["spec_steps"] > 0


def test_spec_decentralized_top1_parity():
    cfg, model, experts, router, feats = _mixture_setup()

    def mk(spec):
        return DecentralizedSlotServer(model, experts, router, config=_cfg(
            "ngram" if spec else None, cache_len=48, strategy="top1"))

    qv = _queue(cfg, feats)
    got_v = mk(False).serve(qv)
    srv_s = mk(True)
    assert srv_s.serve(_queue(cfg, feats)) == got_v
    assert sum(p["spec_steps"] for p in srv_s.occupancy()
               if "spec_steps" in p) > 0


# ---------------------------------------------------------------------
# The Pallas verify kernel
# ---------------------------------------------------------------------

@pytest.mark.parametrize("B,NB,block,H,KV,dh,L", [
    (2, 4, 16, 4, 4, 64, 3),     # MHA
    (3, 8, 16, 8, 2, 64, 4),     # GQA 4:1
])
@pytest.mark.parametrize("bps", [1, 2, 4])
@pytest.mark.parametrize("layer", [0, 2])
def test_paged_verify_kernel_matches_decode_ref(B, NB, block, H, KV, dh,
                                                L, bps, layer):
    """Verify row j IS decode attention at position pos + j (the per-row
    causal fence), so the existing paged-decode oracle checks every row
    of the one-launch span kernel — reading ``layer`` of a 3-layer pool,
    the first and the last."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    P = B * NB + 3
    dt = jnp.float32
    q = jax.random.normal(ks[0], (B, L, H, dh), dt)
    kp = jax.random.normal(ks[1], (3, P, KV, block, dh), dt)
    vp = jax.random.normal(ks[2], (3, P, KV, block, dh), dt)
    rng = np.random.default_rng(0)
    bt = jnp.asarray(rng.permutation(np.arange(1, P))[:B * NB]
                     .reshape(B, NB), jnp.int32)
    # span must fit the logical horizon: pos + L - 1 < NB * block
    pos = jax.random.randint(ks[3], (B,), 0, NB * block - L + 1)
    out = paged_verify_attention(q, kp, vp, jnp.int32(layer), pos, bt,
                                 blocks_per_step=bps, interpret=True)
    assert out.shape == (B, L, H, dh)
    for j in range(L):
        want = ref.paged_decode_attention_ref(q[:, j], kp, vp, layer,
                                              pos + j, bt)
        np.testing.assert_allclose(np.asarray(out[:, j], np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-5, atol=2e-5)


def test_spec_use_kernel_parity():
    """The whole speculative stack through the Pallas kernels matches the
    jnp path token-for-token."""
    cfg, model, params = _dense_setup()

    def mk(uk):
        return SlotServer(model, params,
                          config=_cfg("ngram", use_kernel=uk))

    got_jnp = mk(False).serve(_queue(cfg, max_new=8))
    srv_k = mk(True)
    assert srv_k.serve(_queue(cfg, max_new=8)) == got_jnp
    assert srv_k.stats()["spec_steps"] > 0


# ---------------------------------------------------------------------
# Config validation and the proposer
# ---------------------------------------------------------------------

def test_spec_config_validation():
    with pytest.raises(ValueError, match="ngram"):
        EngineConfig(paged=True, speculative="bogus").validate()
    with pytest.raises(ValueError, match="paged"):
        EngineConfig(paged=False, speculative="ngram").validate()
    with pytest.raises(ValueError, match="fused"):
        EngineConfig(paged=True, fused_step=False,
                     speculative="ngram").validate()
    with pytest.raises(ValueError, match="mixture"):
        EngineConfig(paged=True, strategy="top1",
                     speculative="expert").validate()
    with pytest.raises(ValueError, match="spec_len"):
        EngineConfig(paged=True, speculative="ngram",
                     spec_len=0).validate()
    # legal combinations
    EngineConfig(paged=True, speculative="ngram").validate()
    EngineConfig(paged=True, strategy="mixture",
                 speculative="expert").validate()


def test_ngram_proposer():
    p = NGramProposer(spec_len=4, n=2)
    # the continuation of the most recent earlier (7, 8) occurrence
    hist = [1, 2, 3, 7, 8, 9, 4, 5, 7, 8]
    assert p.propose(hist).tolist() == [9, 4, 5]
    # no earlier occurrence: pad with the last token
    assert p.propose([1, 2, 3, 4]).tolist() == [4, 4, 4]
    # short history pads too
    assert p.propose([6]).tolist() == [6, 6, 6]
    assert p.propose([]).tolist() == [0, 0, 0]
    # continuation shorter than the span right-pads with its last token
    assert p.propose([5, 1, 2, 5, 1, 2]).tolist()[:2] == [5, 1]
    batch = p.propose_batch([hist, [1, 2, 3, 4]])
    assert batch.shape == (2, 3) and batch.dtype == np.int32
    with pytest.raises(ValueError):
        NGramProposer(spec_len=1)
