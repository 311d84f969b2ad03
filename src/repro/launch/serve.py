"""Serving launcher — decentralized continuous batching (paper §5.2).

Loads the per-expert checkpoints + the centroid router written by
launch/train.py and serves a stream of synthetic multimodal requests through
the ``DecentralizedSlotServer``: the Eq. 28 router runs at the front end on
each request's frozen-encoder features and either dispatches it to its
top-1 expert pod (grouped, compute-matched) or admits it into the stacked-
expert mixture core (one vmapped decode step over all K experts, Eq. 27
mixing fused in). Slots turn over continuously, so short requests never
wait for long ones. Reports routing fidelity and throughput.

    PYTHONPATH=src python -m repro.launch.serve --run /tmp/repro_run \
        --arch qwen3_8b --requests 16 --new-tokens 24 --slots 8

Every serving flag lands in ONE ``EngineConfig`` (validated up front —
bad flag combinations raise a single actionable error) and the engine is
built by ``make_engine``. The drive loop speaks the incremental
``add_request``/``step`` API; ``--stream`` prints each request's token
deltas as they decode, ``--stop-token`` retires requests early with
``finish_reason="stop"``.

``--engine batch`` falls back to the whole-batch ``DecentralizedServer``
(lockstep generation, supports temperature sampling).
"""
from __future__ import annotations

import argparse
import json
import time

import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.configs.base import ARCH_IDS, get_smoke_config
from repro.core.router import CentroidRouter, RouterConfig
from repro.data.synthetic import SyntheticConfig, SyntheticMultimodal
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serve.api import EngineConfig, QoSConfig, SamplingParams
from repro.serve.ensemble_engine import DecentralizedServer
from repro.serve.scheduler import make_engine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--run", required=True, help="launch.train output dir")
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3_8b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--top-k", type=int, default=1)
    ap.add_argument("--temperature", type=float, default=1.0,
                    help="batch engine only; the slot engine is greedy")
    ap.add_argument("--strategy", choices=["top1", "mixture"],
                    default="top1")
    ap.add_argument("--engine", choices=["slots", "batch"], default="slots")
    ap.add_argument("--slots", type=int, default=8,
                    help="cache slots per pod (slot engine)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: slots hold block tables into a "
                         "shared pool instead of fixed-length cache rows")
    ap.add_argument("--page-block", type=int, default=16,
                    help="positions per KV block (with --paged)")
    ap.add_argument("--pool-blocks", type=int, default=0,
                    help="physical blocks in the pool per pod "
                         "(0 → full capacity: slots × blocks-per-slot + 1)")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="consume prompts in fixed-size chunks written "
                         "through the paged pool, co-scheduled with decode "
                         "steps (no stop-the-world prefill; needs --paged)")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="prompt positions per prefill chunk "
                         "(with --chunked-prefill)")
    ap.add_argument("--token-budget", type=int, default=0,
                    help="per-step token budget: decoding slots count 1 "
                         "each, the chunk counts --prefill-chunk "
                         "(0 → slots + chunk, co-scheduling always fits)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache over the paged pool: "
                         "admissions sharing a cached prompt prefix map "
                         "the shared KV blocks read-only and start "
                         "chunked prefill at the first uncached position "
                         "(needs --paged and --chunked-prefill; ssm/"
                         "hybrid fall back to the uncached path)")
    ap.add_argument("--slot-temperature", type=float, default=0.0,
                    help="per-request sampling temperature for the slot "
                         "engine (0 → greedy; sampling is seeded per "
                         "request, deterministic given --seed)")
    ap.add_argument("--slot-top-k", type=int, default=0,
                    help="sample from the k highest-scoring tokens "
                         "(slot engine, 0 → full vocabulary)")
    ap.add_argument("--stop-token", type=int, action="append", default=None,
                    help="stop/eos token id (repeatable): a request retires "
                         "with finish_reason='stop' as soon as it GENERATES "
                         "one (slot engine)")
    ap.add_argument("--stream", action="store_true",
                    help="drive the incremental add_request/step API and "
                         "print per-token deltas as they decode "
                         "(slot engine)")
    ap.add_argument("--preemption", choices=["off", "recompute", "swap"],
                    default="off",
                    help="paged-block preemption: under pool pressure a "
                         "lower-priority decoding request is evicted — "
                         "'recompute' drops its private blocks and replays "
                         "its tokens through chunked prefill at resume "
                         "(needs --chunked-prefill), 'swap' parks their "
                         "contents host-side and scatters them back (needs "
                         "--paged). Resumed output is token-for-token "
                         "identical either way")
    ap.add_argument("--tenant-weight", action="append", default=None,
                    metavar="NAME=W",
                    help="QoS fair-share weight for a tenant (repeatable): "
                         "admission and prefill-chunk bandwidth are split "
                         "across tenants by deficit round robin in "
                         "proportion to these weights (unlisted tenants "
                         "weigh 1.0); FCFS order is kept within a tenant")
    ap.add_argument("--qos-quantum", type=int, default=0,
                    help="DRR credit per round in prompt tokens "
                         "(0 → the prefill chunk size)")
    ap.add_argument("--admit-lookahead", type=int, default=0,
                    help="bounded admission skip-ahead window past an "
                         "unservable queue head (0 → default 8)")
    ap.add_argument("--max-predicted-ttft", type=float, default=0.0,
                    help="SLO admission control: reject a submission "
                         "(finish_reason='rejected') when its predicted "
                         "TTFT from the live token backlog exceeds this "
                         "many seconds (0 → disabled; needs "
                         "--chunked-prefill)")
    ap.add_argument("--max-waiting", type=int, default=0,
                    help="reject submissions once the waiting queue is "
                         "this deep (0 → unbounded)")
    ap.add_argument("--tenants", type=int, default=1,
                    help="assign synthetic requests round-robin to this "
                         "many tenants (tenant-0, tenant-1, …) to exercise "
                         "the QoS fair-share path")
    ap.add_argument("--priorities", type=int, action="append", default=None,
                    help="request priority cycle (repeatable): request i "
                         "gets the i-th value mod the list length — higher "
                         "preempts lower under pool pressure")
    ap.add_argument("--sanitize", action="store_true",
                    help="debug mode: run the PoolSanitizer — a per-step "
                         "ownership scan over the paged block pool "
                         "(aliasing, refcount drift, leaks, use-after-"
                         "free raise immediately; needs --paged)")
    ap.add_argument("--no-fused-step", action="store_true",
                    help="run the legacy host epilogue instead of the fused "
                         "single-dispatch decode step (parity escape hatch; "
                         "slot engine)")
    ap.add_argument("--speculative", choices=["ngram", "expert"],
                    default=None,
                    help="speculative decoding: draft spec_len-1 tokens "
                         "(host n-gram prompt lookup, or the mixture's "
                         "expert 0 on device) and verify the span in one "
                         "dispatch — outputs stay token-for-token "
                         "identical to vanilla decode (needs --paged; "
                         "'expert' needs --strategy mixture)")
    ap.add_argument("--spec-len", type=int, default=4,
                    help="speculative span length L: one committed token "
                         "+ L-1 drafts verified per step (1 = vanilla)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace of the run to PATH "
                         "(slot engine; enables span tracing — load the "
                         "file in ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a JSON metrics snapshot of every pod's "
                         "registry to PATH at exit (slot engine)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="route attention through the Pallas decode kernel")
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    centroids, tau, _ = ckpt.load_router(args.run)
    router = CentroidRouter(jnp.asarray(centroids, jnp.float32),
                            RouterConfig(temperature=tau, top_k=args.top_k))
    cfg = get_smoke_config(args.arch).reduced(vocab=args.vocab)
    model = build_model(cfg)

    experts = []
    k = 0
    while True:
        state, step = ckpt.restore_expert(args.run, k)
        if state is None:
            break
        experts.append(state["params"])
        k += 1
    assert experts, f"no expert checkpoints under {args.run}"
    print(f"loaded {len(experts)} experts (router τ={tau})")

    corpus = SyntheticMultimodal(SyntheticConfig(
        vocab=args.vocab, seq_len=args.prompt_len, seed=args.seed + 7))
    batch_np = corpus.sample_batch(args.requests, step=123)
    cache_len = args.prompt_len + args.new_tokens + 1
    routed = np.asarray(router.top1(jnp.asarray(batch_np["features"])))

    t0 = time.time()
    if args.engine == "slots":
        # every flag lands in ONE validated config — bad combinations
        # raise a single actionable ValueError before any compilation
        qos = None
        if (args.tenant_weight or args.qos_quantum or args.admit_lookahead
                or args.max_predicted_ttft or args.max_waiting):
            weights = tuple(
                (name, float(w)) for name, _, w in
                (s.partition("=") for s in (args.tenant_weight or ())))
            qos = QoSConfig(
                tenant_weights=weights, quantum=args.qos_quantum,
                admit_lookahead=args.admit_lookahead or 8,
                max_predicted_ttft_s=args.max_predicted_ttft,
                max_waiting=args.max_waiting)
        ecfg = EngineConfig(
            n_slots=args.slots, cache_len=cache_len, paged=args.paged,
            page_block=args.page_block, pool_blocks=args.pool_blocks,
            chunked_prefill=args.chunked_prefill, chunk=args.prefill_chunk,
            token_budget=args.token_budget, prefix_cache=args.prefix_cache,
            fused_step=not args.no_fused_step, sanitize=args.sanitize,
            qos=qos, preemption=args.preemption,
            use_kernel=args.use_kernel, strategy=args.strategy,
            speculative=args.speculative, spec_len=args.spec_len,
            trace=args.trace_out is not None,
            metrics=args.metrics_out is not None)
        ecfg.validate(model)
        server = make_engine(model, experts=experts, router=router,
                             config=ecfg)

        def sp(i: int) -> SamplingParams:
            prios = args.priorities or (0,)
            return SamplingParams(
                max_new=args.new_tokens, temperature=args.slot_temperature,
                top_k=args.slot_top_k, seed=args.seed + i,
                stop_token_ids=tuple(args.stop_token or ()),
                priority=prios[i % len(prios)],
                tenant=f"tenant-{i % max(args.tenants, 1)}"
                if args.tenants > 1 else "default")

        for i in range(args.requests):
            server.add_request(batch_np["tokens"][i], sp(i), rid=i,
                               features=batch_np["features"][i])
        finished = {}
        while server.has_unfinished():
            for o in server.step():
                if args.stream and o.deltas:
                    tail = f"  [{o.finish_reason}]" if o.finished else ""
                    print(f"rid={o.rid:3d} +"
                          f"{[d.token for d in o.deltas]}{tail}")
                if o.finished:
                    finished[o.rid] = o.token_ids
        out = {i: finished[i] for i in range(args.requests)}
        n_tok = sum(len(v) for v in out.values())
        if args.trace_out:
            server.export_trace(args.trace_out)
            print(f"trace written to {args.trace_out} "
                  "(load in ui.perfetto.dev)")
        if args.metrics_out:
            server.export_metrics(args.metrics_out)
            print(f"metrics snapshot written to {args.metrics_out}")
    else:
        batch = {
            "tokens": jnp.asarray(batch_np["tokens"]),
            "labels": jnp.asarray(batch_np["labels"]),
            "features": jnp.asarray(batch_np["features"]),
        }
        server = DecentralizedServer(model, experts, router,
                                     cache_len=cache_len,
                                     use_kernel=args.use_kernel)
        gen = (server.generate_top1 if args.strategy == "top1"
               else server.generate_mixture)
        arr = np.asarray(gen(batch, SamplingParams(
            max_new=args.new_tokens, temperature=args.temperature,
            seed=args.seed)))
        out = {i: arr[i].tolist() for i in range(args.requests)}
        n_tok = args.requests * args.new_tokens
    dt = time.time() - t0

    per_expert = np.bincount(routed, minlength=len(experts))
    # routing/latent alignment up to cluster-id permutation (Hungarian)
    from scipy.optimize import linear_sum_assignment
    K, Kl = len(experts), int(batch_np["cluster"].max()) + 1
    conf = np.zeros((K, max(K, Kl)))
    for r, c in zip(routed, batch_np["cluster"]):
        conf[r, c] += 1
    rows, cols = linear_sum_assignment(-conf)
    aligned = conf[rows, cols].sum() / len(routed)
    print(json.dumps({
        "requests": args.requests,
        "new_tokens": args.new_tokens,
        "engine": args.engine,
        "strategy": args.strategy,
        "slots": args.slots if args.engine == "slots" else None,
        "paged": args.paged if args.engine == "slots" else None,
        "chunked_prefill": (args.chunked_prefill
                            if args.engine == "slots" else None),
        "prefix_cache": (args.prefix_cache
                         if args.engine == "slots" else None),
        "pods": server.occupancy() if args.engine == "slots" else None,
        "fused_step": (not args.no_fused_step
                       if args.engine == "slots" else None),
        "use_kernel": args.use_kernel,
        "stream": args.stream if args.engine == "slots" else None,
        "wall_s": round(dt, 2),
        "tok_per_s": round(n_tok / dt, 1),
        "requests_per_expert": per_expert.tolist(),
        "router_latent_alignment": float(aligned),
    }, indent=1))
    for i in range(min(4, args.requests)):
        print(f"req {i} → expert {routed[i]}: "
              f"prompt={batch_np['tokens'][i, :8].tolist()}… "
              f"gen={list(out[i])[:12]}…")


if __name__ == "__main__":
    main()
