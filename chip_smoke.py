#!/usr/bin/env python3
"""Smoke run of the decentralized serving path on one TPU chip.

Two InternVL2-2B experts at full published width (24 layers, d_model
2048, 16 heads / 8 KV heads, vocab 92553; random bf16 weights made on the
device from ``--seed``) sit behind the Eq. 28 centroid router, and eight
vision-language requests (256 image patches of width 1024, 64 text tokens
each) are served through ``make_engine`` → ``add_request`` / ``step``:

* phase A — top-1 pods: paged KV cache, chunked prefill, fused decode
  step, Pallas kernels compiled for the chip;
* compare — phase A's kernels against the jnp attention path on the same
  inputs (one paged decode step's logits, and the greedy first tokens;
  the tolerances are stated at ``DECODE_SLACK``);
* phase B — the Eq. 27 mixture core (``strategy="mixture"``) on the same
  requests.

Each phase must serve every request to ``finish_reason == "length"`` with
exactly ``--new-tokens`` tokens. The lines before the last are smoke
output (compile seconds, wall seconds, tokens, peak device bytes), not
benchmark metrics. The last line is one JSON object naming the device.
Any failure exits non-zero, and so does any platform other than a TPU;
the one exception is ``--cpu-rehearsal``, which runs the smoke-size
config on the CPU with the kernels interpreted.

    python chip_smoke.py                                  # on a TPU host
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# Kernel path against the jnp path (see compare_paths). The reference is
# the jnp path in float32 on the same bf16 weights. With random weights
# the top logits of a row sit close together, and bf16 rounding alone
# reorders them, so no check asks for equal tokens:
# * one paged decode step's logits: the kernel path's relative L2 error to
#   the reference may be at most DECODE_SLACK[0] times the bf16 jnp
#   path's error plus DECODE_SLACK[1];
# * the served greedy first tokens are judged by value: the reference
#   logit of each must lie within 2·noise of the reference row's maximum,
#   where noise is the largest absolute error of the bf16 jnp prefill's
#   last-position logits on the same requests. A path whose logits are
#   within noise of the reference can pick no token further down than
#   that (its pick beats the reference maximum in its own logits), so a
#   flip between near-tied logits passes and a wrong prefill does not.
DECODE_SLACK = (2.0, 1e-2)
N_EXPERTS = 2
N_REQUESTS = 8
PAGE = 16
CHUNK = 64


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit counts
    its retrieval time) and counts cache hits and misses."""

    def __init__(self):
        import jax
        self.secs, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return self.secs, self.hits, self.misses

    def since(self, m) -> str:
        return (f"compile {self.secs - m[0]:.3f}s, cache hits "
                f"{self.hits - m[1]}, misses {self.misses - m[2]}")


def device_bytes(dev) -> str:
    """The process's peak so far (it never resets), what is held now, and
    the device's limit."""
    stats = dev.memory_stats() or {}
    return ", ".join(f"{k} {stats.get(k, 'not reported')}" for k in
                     ("peak_bytes_in_use", "bytes_in_use", "bytes_limit"))


def make_inputs(cfg, text_len: int, seed: int):
    """Router centroids and the requests' patches, tokens and routing
    features — request i lies near centroid i % K so both pods serve."""
    import numpy as np
    rng = np.random.default_rng(seed)
    feat_dim = 64
    centroids = rng.normal(size=(N_EXPERTS, feat_dim)).astype(np.float32)
    reqs = []
    for i in range(N_REQUESTS):
        feats = centroids[i % N_EXPERTS] + 0.3 * rng.normal(size=feat_dim)
        reqs.append({
            "tokens": rng.integers(0, cfg.vocab, text_len).astype(np.int32),
            "patches": rng.normal(size=(cfg.n_patches, cfg.vision_dim))
            .astype(np.float32),
            "features": feats.astype(np.float32)})
    return centroids, reqs


def serve(engine, reqs, new_tokens: int):
    """Drive the incremental API until nothing is unfinished; return each
    request's output ids in submission order."""
    from repro.serve.api import SamplingParams
    rids = [engine.add_request(r["tokens"], SamplingParams(max_new=new_tokens),
                               {"patches": r["patches"]},
                               features=r["features"]) for r in reqs]
    done = {}
    steps = 0
    while engine.has_unfinished():
        for out in engine.step():
            if out.finished:
                done[out.rid] = out
        steps += 1
        check(steps < 100_000, "engine did not drain")
    outs = []
    for rid in rids:
        out = done.get(rid)
        check(out is not None, f"request {rid} never finished")
        check(out.finish_reason == "length",
              f"request {rid} finished with {out.finish_reason!r}")
        check(len(out.token_ids) == new_tokens,
              f"request {rid}: {len(out.token_ids)} tokens, "
              f"want {new_tokens}")
        outs.append(out.token_ids)
    return outs, steps


def run_phase(name, model, experts, router, config, reqs, new_tokens,
              clock, dev):
    """Build the engine over ``experts`` and serve the requests through
    it."""
    from repro.serve.scheduler import make_engine
    m = clock.mark()
    t0 = time.perf_counter()
    engine = make_engine(model, experts=experts, router=router,
                         config=config)
    outs, steps = serve(engine, reqs, new_tokens)
    wall = time.perf_counter() - t0
    log(f"{name}: {len(outs)} requests to finish_reason=length, "
        f"{sum(map(len, outs))} tokens in {steps} engine steps, wall "
        f"{wall:.3f}s ({clock.since(m)}); device {device_bytes(dev)}")
    return engine, outs


def compare_paths(model, experts, router, reqs, first_tokens, cache_len,
                  clock, on_tpu: bool):
    """Phase A's kernels against the jnp path on the same inputs.

    The reference is the jnp path (``use_kernel=False``) computing in
    float32 on the same bf16 weights. Its monolithic prefill of all
    requests on their top-1 expert gives the reference last-position
    logits and a KV cache, which is stored in bf16 as a paged pool (one
    run of blocks per request); the same prefill in bf16 gives the jnp
    path's logits and greedy first tokens. One decode step then runs on
    that pool three ways: the kernel path and the jnp path in bf16, and
    the reference. See ``DECODE_SLACK`` for the checks."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import build_model

    m = clock.mark()
    ref_model = build_model(model.cfg.reduced(compute_dtype="float32"))
    feats = jnp.asarray(np.stack([r["features"] for r in reqs]))
    k_of = np.asarray(router.top1(feats))
    batch = {"tokens": jnp.asarray(np.stack([r["tokens"] for r in reqs])),
             "patches": jnp.asarray(np.stack([r["patches"] for r in reqs]))}
    B = len(reqs)
    width = batch["tokens"].shape[1] + model.cfg.n_patches
    nb = cache_len // PAGE
    tables = jnp.asarray(1 + np.arange(B * nb).reshape(B, nb), jnp.int32)
    pos = jnp.full((B,), width, jnp.int32)

    @jax.jit
    def prefill(p, batch):
        logits, cache = ref_model.prefill(p, batch, cache_len)
        # (L, B, S, KV, dh) rows → head-major bf16 pool, scratch block 0
        pool = jax.tree.map(
            lambda c: jnp.pad(
                jnp.swapaxes(c.reshape(c.shape[0], B * nb, PAGE,
                                       *c.shape[3:]), 2, 3),
                [(0, 0), (1, 0), (0, 0), (0, 0), (0, 0)]
            ).astype(jnp.bfloat16), cache)
        return logits[:, -1].astype(jnp.float32), pool

    @jax.jit
    def prefill_bf16(p, batch):
        logits, _ = model.prefill(p, batch, cache_len)
        return logits[:, -1].astype(jnp.float32)

    def decode_fn(mdl, use_kernel):
        def f(p, pool, tok):
            logits, _ = mdl.decode_step_paged(p, pool, tok, pos, tables,
                                              use_kernel=use_kernel)
            return logits.astype(jnp.float32)
        return jax.jit(f)

    steps = {"kernel": decode_fn(model, True),
             "jnp": decode_fn(model, False),
             "ref": decode_fn(ref_model, False)}
    ref_first = np.zeros((B, model.cfg.padded_vocab), np.float32)
    jnp_first = np.zeros_like(ref_first)
    dec = {name: np.zeros_like(ref_first) for name in steps}
    tok = jnp.asarray(np.asarray(first_tokens, np.int32))
    for k, p in enumerate(experts):
        rows = np.nonzero(k_of == k)[0]
        if not len(rows):
            continue
        last, pool = prefill(p, batch)
        if not isinstance(steps["kernel"], jax.stages.Compiled):
            steps["kernel"] = steps["kernel"].lower(p, pool, tok).compile()
            if on_tpu:
                check("tpu_custom_call" in steps["kernel"].as_text(),
                      "kernel decode step holds no Mosaic kernel call")
                log("kernel decode step compiled with Mosaic kernel calls")
        ref_first[rows] = np.asarray(last)[rows]
        jnp_first[rows] = np.asarray(prefill_bf16(p, batch))[rows]
        for name, fn in steps.items():
            dec[name][rows] = np.asarray(fn(p, pool, tok))[rows]
    check(all(np.isfinite(a).all() for a in (ref_first, jnp_first,
                                             *dec.values())),
          "logits not finite")

    def rel(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    err_ker = rel(dec["kernel"], dec["ref"])
    err_jnp = rel(dec["jnp"], dec["ref"])
    limit = DECODE_SLACK[0] * err_jnp + DECODE_SLACK[1]
    first = np.asarray(first_tokens)
    noise = float(np.abs(jnp_first - ref_first).max())
    gap = ref_first.max(-1) - ref_first[np.arange(B), first]
    log(f"compare: decode-step logits rel L2 to the f32 reference: kernel "
        f"{err_ker:.4e}, jnp bf16 {err_jnp:.4e} (kernel limit {limit:.4e}); "
        f"served first tokens equal to the jnp bf16 greedy pick "
        f"{int(np.sum(first == np.argmax(jnp_first, -1)))}/{B} and to the "
        f"f32 reference's {int(np.sum(first == np.argmax(ref_first, -1)))}"
        f"/{B}; worst reference-logit shortfall {gap.max():.4e} (limit "
        f"2*noise {2 * noise:.4e}; prefill logit rms "
        f"{float(np.sqrt(np.mean(ref_first ** 2))):.4e}, jnp bf16 rel L2 "
        f"{rel(jnp_first, ref_first):.4e}) ({clock.since(m)})")
    check(err_ker <= limit, f"kernel decode logits rel L2 {err_ker:.4e} > "
                            f"{limit:.4e}")
    check(bool((gap <= 2 * noise).all()),
          "a served first token is further below the reference maximum "
          "than bf16 noise explains")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="smoke-size config on the CPU, kernels "
                         "interpreted (a rehearsal, not a chip run)")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no src/repro next to {__file__}; run it from "
              f"a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    dev = jax.devices()[0]
    want = "cpu" if args.cpu_rehearsal else "tpu"
    if dev.platform != want:
        print(f"chip_smoke: needs platform {want!r}, JAX found "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    clock = CompileClock()
    import jax.numpy as jnp
    from repro.configs.base import get_config, get_smoke_config
    from repro.core.router import CentroidRouter, RouterConfig
    from repro.kernels import ops as kops
    from repro.models import build_model
    from repro.serve.api import EngineConfig

    if args.cpu_rehearsal:
        cfg = get_smoke_config("internvl2_2b").reduced(
            param_dtype="bfloat16", compute_dtype="bfloat16")
        text_len = 16
    else:
        cfg = get_config("internvl2_2b")
        text_len = 64
    on_tpu = dev.platform == "tpu"
    check(kops._interpret() != on_tpu, "kernel mode does not match platform")
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {cache_dir}; kernels "
        f"{'compiled' if on_tpu else 'interpreted'}")

    model = build_model(cfg)
    t0 = time.perf_counter()
    experts = [model.init(jax.random.PRNGKey(args.seed + k), jnp.bfloat16)
               for k in range(N_EXPERTS)]
    jax.block_until_ready(experts)
    n_params = sum(x.size for x in jax.tree.leaves(experts[0]))
    log(f"{cfg.arch_id}: {N_EXPERTS} experts x {n_params} params (bf16) "
        f"made in {time.perf_counter() - t0:.3f}s; device {device_bytes(dev)}")
    centroids, reqs = make_inputs(cfg, text_len, args.seed)
    router = CentroidRouter(jnp.asarray(centroids),
                            RouterConfig(temperature=10.0, top_k=2))
    width = text_len + cfg.n_patches
    cache_len = -(-(width + args.new_tokens) // PAGE) * PAGE
    base = dict(n_slots=N_REQUESTS, cache_len=cache_len, paged=True,
                page_block=PAGE, chunked_prefill=True, chunk=CHUNK,
                fused_step=True, use_kernel=True)

    engine, outs_a = run_phase(
        "phase A top-1 pods", model, experts, router,
        EngineConfig(strategy="top1", **base), reqs, args.new_tokens,
        clock, dev)
    del engine
    gc.collect()        # the pods' KV pools, should a cycle hold them
    compare_paths(model, experts, router, reqs, [o[0] for o in outs_a],
                  cache_len, clock, on_tpu)
    # The mixture core stacks the experts into one device copy of its own.
    # Beside the unstacked experts that copy does not fit one 16 GB chip
    # (the first chip run ran out of memory there), so the core takes them
    # from host memory and the stacking uploads one leaf at a time.
    experts = jax.device_get(experts)
    log(f"experts moved to host memory; device {device_bytes(dev)}")
    engine, outs_b = run_phase(
        "phase B mixture core", model, experts, router,
        EngineConfig(strategy="mixture", **base), reqs, args.new_tokens,
        clock, dev)
    del engine
    log(f"total compile {clock.secs:.3f}s, cache hits {clock.hits}, "
        f"misses {clock.misses}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
