"""The paged pool is updated in place: every step program consumes (donates)
its cache, and nothing reads a cache after it was passed on.

* After each ``step()`` that ran a program, every array of the cache the
  step started from is deleted — a later read of a donated buffer raises,
  so the runs below would fail on any path that kept one: preemption by
  swap (``swap_out`` / ``swap_in``) and by recompute, the prefix cache,
  the PoolSanitizer, the unfused step functions, and expert-0 drafting in
  the speculative mixture.
* ``serve_pool_inplace_total{outcome="copied"}`` stays 0 and
  ``{outcome="kept"}`` counts every step-program call on the pool.
* Outputs are the same as without pool pressure, token for token.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.core.router import CentroidRouter, RouterConfig
from repro.models import build_model
from repro.serve.api import EngineConfig, SamplingParams
from repro.serve.scheduler import MixtureSlotServer, Request, SlotServer


@pytest.fixture(scope="module")
def small_model():
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=64)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _queue(cfg, n=4, seed=3):
    """Low-priority requests that fill the slots, then high-priority ones
    that must preempt them; every prompt shares an 8-token prefix (the
    prefix cache's hits) and odd ids sample."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab, size=8).astype(np.int32)
    reqs = []
    for i in range(n):
        tail = rng.integers(0, cfg.vocab, size=4).astype(np.int32)
        sp = SamplingParams(max_new=6, temperature=0.0 if i % 2 == 0
                            else 0.8, seed=100 + i,
                            priority=0 if i < 2 else 2)
        reqs.append(Request(i, np.concatenate([shared, tail]), 6,
                            params=sp))
    return reqs


def _serve_checking_donation(srv, queue):
    """``srv.serve(queue)`` one ``step()`` at a time, asserting after each
    step that ran a program that the cache it started from was consumed."""
    for r in queue:
        srv.add_request(r)
    out, steps = {}, 0
    while srv.has_unfinished():
        before = jax.tree.leaves(srv.cache)
        for o in srv.step():
            if o.finished:
                out[o.rid] = o.token_ids
        after = jax.tree.leaves(srv.cache)
        if any(a is not b for a, b in zip(before, after)):
            assert all(a.is_deleted() for a in before)
            steps += 1
        assert steps < 500
    return out


def _counts(srv):
    return srv.obs.pool_kept.value, srv.obs.pool_copied.value


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "unfused"])
@pytest.mark.parametrize("mode", ["swap", "recompute"])
def test_preempting_prefix_cached_run_consumes_every_cache(small_model, mode,
                                                           fused):
    cfg, model, params = small_model
    knobs = dict(n_slots=2, cache_len=32, paged=True, page_block=4,
                 chunked_prefill=True, chunk=8, prefix_cache=True,
                 fused_step=fused)
    want = SlotServer(model, params, config=EngineConfig(**knobs)).serve(
        _queue(cfg))
    srv = SlotServer(model, params, config=EngineConfig(
        pool_blocks=7, preemption=mode, sanitize=True, **knobs))
    queue = _queue(cfg)
    got = _serve_checking_donation(srv, queue)
    assert sum(r.preemptions for r in queue) > 0, \
        "no preemption: the swap/recompute paths were not driven"
    assert srv.stats()["prefix_skipped_tokens"] > 0, "no prefix-cache hit"
    assert srv.sanitizer.violations == 0
    assert got == want
    kept, copied = _counts(srv)
    assert copied == 0 and kept > 0


def test_speculative_expert_mixture_consumes_every_cache(small_model):
    """Expert-0 drafts on a copy made inside the verify program, so the
    caches the program was given can be consumed."""
    cfg, model, _ = small_model
    K, Df = 2, 8
    experts = [model.init(jax.random.PRNGKey(k)) for k in range(K)]
    rng = np.random.default_rng(5)
    router = CentroidRouter(
        jnp.asarray(rng.normal(size=(K, Df)), jnp.float32),
        RouterConfig(top_k=2))
    knobs = dict(n_slots=2, cache_len=32, paged=True, page_block=4,
                 strategy="mixture")

    def queue():
        reqs = _queue(cfg)
        for i, r in enumerate(reqs):
            r.features = np.linspace(-1.0, 1.0, Df).astype(np.float32) \
                * (i + 1)
        return reqs

    want = MixtureSlotServer(model, experts, router, config=EngineConfig(
        **knobs)).serve(queue())
    srv = MixtureSlotServer(model, experts, router, config=EngineConfig(
        speculative="expert", spec_len=3, **knobs))
    got = _serve_checking_donation(srv, queue())
    assert got == want
    assert srv.stats()["spec_steps"] > 0
    kept, copied = _counts(srv)
    assert copied == 0 and kept > 0
