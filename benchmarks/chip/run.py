#!/usr/bin/env python3
"""Chip benchmark of the decentralized serving path, one cell per run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``configs/<name>.json``: the model's sizes, the deployment of K experts
behind the Eq. 28 router, the engine settings) and a traffic mix
(``traffic/<name>.json``, whose ``generator`` names
``generators/<kind>.py``). Everything is found by name; adding a cell adds
files and one entry, and edits nothing here.

One run, all in this process and on one chip:

1. build the model, make the K experts on the device from ``--seed`` (one
   jitted call each, bf16) and the router's centroids; the mixture takes
   its experts from host memory, as its stacked copy and the originals do
   not fit one chip together;
2. ``make_engine(model, experts=..., router=..., config=...)``;
3. warm up every program the window can reach, through the engine's own
   ``add_request``/``step``: each prompt-width bucket on every pod, then a
   ladder that decodes one request across every block-table width, alone
   and beside a prefill chunk;
4. drive ``add_request``/``step`` from one host loop: the mix's pre-roll,
   then the measured window of ``--seconds``;
5. read the device's peak bytes, free the engine, and check what the
   window served against the plain reference (``check.py``);
6. print the checks on standard error and one JSON result line last on
   standard output: the end-to-end metrics with ``--trace 0``, the
   per-layer metrics (``layer_metrics/<name>.py``) with ``--trace 1``,
   which also profiles the last few seconds of the window.

It needs a TPU: any other platform, or fewer chips than the cell asks
for, exits non-zero with no result. ``--rehearsal`` (tests only) runs the
whole path on the CPU at a tiny size with the kernels interpreted.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SRC = os.path.join(ROOT, "src")
# JAX's persistent compilation cache: a fixed directory inside the
# checkout, so only a checkout's first run of a cell compiles
CACHE_DIR = os.path.join(HERE, "_jax_cache")
TRACE_SECONDS = 3.0
WARM_IDX = 10 ** 12     # warm-up requests' indices, apart from the mix's

# The rehearsal's sizes: the same path on the CPU, small enough to
# interpret the kernels (the model's from its architecture file). Never
# used for a measurement.
REHEARSAL_ENGINE = {"n_slots": 3, "cache_len": 96, "page_block": 16,
                    "chunk": 16}
REHEARSAL_SHRINK = 16


class Refused(RuntimeError):
    """The run cannot measure here (no checkout, wrong platform)."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def process_age() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_START


def device_bytes(dev) -> str:
    """What the device holds now, and the process's peak so far."""
    st = dev.memory_stats() or {}
    return (f"in use {st.get('bytes_in_use', 'not reported')}, peak "
            f"{st.get('peak_bytes_in_use', 'not reported')}")


def load_cell(name: str, root: str = ROOT):
    """(benchmark, cell, configuration entry, configuration, mix)."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload named {name!r}; have {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    import traffic
    return bench, cell, entry, config, traffic.load(cell["traffic"])


def rehearse(config: dict, mix: dict, arch) -> None:
    """Shrink a configuration and mix to the rehearsal's sizes in place."""
    config["model"].update(arch.REHEARSAL)
    config["engine"].update(REHEARSAL_ENGINE)
    config["deployment"]["router"]["feature_dim"] = 16
    for key in ("prompt_tokens", "output_tokens"):
        d = mix[key]
        for k in ("min", "max", "median"):
            if k in d:
                d[k] = max(1, d[k] // REHEARSAL_SHRINK)
    if "min" in mix["output_tokens"]:
        mix["output_tokens"]["min"] = max(2, mix["output_tokens"]["min"])
    if "arrivals" in mix:
        mix["arrivals"]["rate_per_s"] = 3.0
    mix["preroll_s"] = min(mix["preroll_s"], 1.0)


class HostLoop:
    """The host loop around the engine: submits what the generator has
    due, steps, and stamps every streamed token with the return of the
    ``step()`` that carried it (harness clock, ``perf_counter``)."""

    def __init__(self, engine, mix, n_patches: int):
        import jax
        self.jax, self.engine, self.mix = jax, engine, mix
        self.n_patches = n_patches
        self.reqs = {}           # rid -> request record
        self.steps = []          # (t_begin, t_end, tokens streamed)
        self.on_finish = None
        self.slowest_submit = (0.0, 0.0)   # (seconds, when)

    def submit(self, r, due: float, share=None, features=None):
        """Hand request ``r`` (a ``traffic.Request``) to the engine."""
        from repro.serve.api import SamplingParams
        tokens, patches, feats = self.mix.content(r)
        if features is not None:
            feats = features
        max_new = r.max_new if share is None else \
            max(1, math.ceil(share * r.max_new))
        t0 = time.perf_counter()
        with self.jax.profiler.TraceAnnotation("engine.add_request"):
            rid = self.engine.add_request(
                tokens, SamplingParams(max_new=max_new),
                {"patches": patches}, features=feats)
        took = time.perf_counter() - t0
        if took > self.slowest_submit[0]:
            self.slowest_submit = (took, t0)
        self.reqs[rid] = {"idx": r.idx, "due": due,
                          "sub": time.perf_counter(), "text_len": r.text_len,
                          "width": self.n_patches + r.text_len,
                          "max_new": max_new, "features": feats,
                          "toks": [], "t": [], "s": [], "t_admit": 0.0,
                          "reason": None, "t_done": None}
        return rid

    def step(self) -> int:
        n = len(self.steps)
        t0 = time.perf_counter()
        with self.jax.profiler.TraceAnnotation("engine.step", step_index=n):
            outs = self.engine.step()
        t1 = time.perf_counter()
        streamed = 0
        for o in outs:
            q = self.reqs[o.rid]
            for d in o.deltas:
                q["toks"].append(d.token)
                q["t"].append(t1)
                q["s"].append(n)
            streamed += len(o.deltas)
            q["t_admit"] = o.t_admit or q["t_admit"]
            if o.finished:
                q["reason"], q["t_done"] = o.finish_reason, t1
                if self.on_finish is not None:
                    self.on_finish(o.rid, t1)
        self.steps.append((t0, t1, streamed))
        return streamed

    def drain(self, limit: int = 100_000) -> None:
        for _ in range(limit):
            if not self.engine.has_unfinished():
                return
            self.step()
        raise RuntimeError("the engine did not drain")


def warm_up(loop, mix, config, K: int, clock) -> dict:
    """Run every program the window can reach, through the engine's own
    API, so nothing compiles inside the window:

    * on every pod, one request per prompt-width bucket (prompts are
      padded to whole chunks, and each padded width is its own embedding
      program); the first runs its chunks alone, the rest beside decodes;
    * on the first pod, a ladder: one request with the shortest prompt
      decodes up to the mix's largest position, so the decode step runs at
      every block-table width the window can reach; a one-token request
      arrives at each new width, so the decode step with a prefill chunk
      runs at every width too.

    Every top-1 pod shares the jitted step functions; the per-pod splice
    programs are warmed by the first item."""
    import traffic as tr
    e, m = config["engine"], config["model"]
    Np, C, B = m["num_image_token"], e["chunk"], e["page_block"]
    (lo, hi), (_, out_hi) = mix.bounds()
    buckets = {}
    for L in range(lo, hi + 1):
        buckets[-(-(Np + L) // C)] = L          # the longest prompt of each
    maxpos = Np + hi + out_hi - 2
    if maxpos + 1 >= e["cache_len"]:
        raise ValueError(f"the mix reaches position {maxpos}; cache_len "
                         f"{e['cache_len']} is too short")
    strategy = config["engine"]["strategy"]
    pods = range(K) if strategy == "top1" else range(1)
    before = clock.snapshot()
    t0 = time.perf_counter()
    j = WARM_IDX
    for k in pods:
        for L in sorted(buckets.values()):
            loop.submit(tr.Request(j, L, 2, k), 0.0,
                       features=mix.centroids[k].astype("float32"))
            j += 1
    loop.drain()
    width = Np + lo
    rid = loop.submit(tr.Request(j, lo, maxpos - width + 2, 0), 0.0,
                     features=mix.centroids[0].astype("float32"))
    seen = set()
    while loop.engine.has_unfinished():
        q = loop.reqs[rid]
        if q["toks"] and q["reason"] is None:
            w = (width + len(q["toks"]) - 1) // B + 1
            if w not in seen:
                seen.add(w)
                j += 1
                loop.submit(tr.Request(j, lo, 1, 0), 0.0,
                           features=mix.centroids[0].astype("float32"))
        loop.step()
    after = clock.snapshot()
    return {"warmup_s": time.perf_counter() - t0,
            "warmup_compiles": after["compiles"] - before["compiles"],
            "widths": sorted(seen), "buckets": len(buckets)}


def run_window(loop, gen, preroll: float, seconds: float, trace_dir, mark,
               trace_s: float = TRACE_SECONDS):
    """Pre-roll, then the measured window. Returns (t0, t1, trace span)
    on the harness clock; ``mark(which)`` is called once as the window
    opens and once as it closes. ``trace_dir`` set profiles the last
    ``trace_s`` seconds of the window, whole steps only; the profiler is
    stopped, which takes seconds, only once the window has closed."""
    jax = loop.jax
    loop.on_finish = lambda rid, t: gen.finished(
        loop.reqs[rid]["idx"], t - origin)
    origin = time.perf_counter()
    t0 = origin + preroll
    t1 = t0 + seconds
    tr_lo = t1 - min(trace_s, seconds)
    tracing, traced, opened = False, None, False
    while True:
        now = time.perf_counter()
        if not opened and now >= t0:
            mark("open")
            opened = True
        if now >= t1:
            break
        if trace_dir is not None and traced is None and now >= tr_lo:
            jax.profiler.start_trace(trace_dir)
            tracing, traced = True, [time.perf_counter(), None]
        for idx, due, share, expert in gen.pop_due(now - origin):
            loop.submit(loop.mix.request(idx, expert), origin + due, share)
        if loop.engine.has_unfinished():
            loop.step()
            continue
        nxt = gen.next_due()
        wait = t1 - now if nxt is None else min(t1, origin + nxt) - now
        if wait > 0:
            with jax.profiler.TraceAnnotation("generator.wait"):
                time.sleep(wait)
    mark("close")
    if tracing:
        traced[1] = time.perf_counter()
        jax.profiler.stop_trace()
    return t0, t1, traced


def collect_and_freeze():
    """A full collection once warm-up is done, timed, then everything that
    survives it is moved out of the collector's reach (``gc.freeze``), as
    a long-lived server does after start-up: the objects set-up leaves
    (traced and lowered programs among them) are never garbage, and a full
    collection in the window would walk them all while every request
    waits. Returns (seconds, objects walked)."""
    t = time.perf_counter()
    gc.collect()
    took = time.perf_counter() - t
    n = len(gc.get_objects())
    gc.freeze()
    return took, n


class GcPauses:
    """The collector's pauses while ``on``, by generation."""

    def __init__(self):
        self.on, self.t, self.seen = False, 0.0, {}
        gc.callbacks.append(self)

    def __call__(self, phase, info):
        if phase == "start":
            self.t = time.perf_counter()
        elif self.on:
            n, worst = self.seen.get(info["generation"], (0, 0.0))
            self.seen[info["generation"]] = (
                n + 1, max(worst, time.perf_counter() - self.t))

    def summary(self) -> str:
        return ", ".join(f"gen {g}: {n}, longest {w:.6f}s"
                         for g, (n, w) in sorted(self.seen.items())) or "none"


def slowest_bucket(snap0: dict, snap1: dict, name: str) -> str:
    """Upper edge of the highest bucket that histogram ``name`` filled
    between two of the program's snapshots, summed over its pods: says
    whether a stalled step waited on the host (dispatch) or the device
    (``device_get``)."""
    def counts(snap):
        out = {}
        for s in snap["metrics"]:
            if s["name"] == name and "buckets" in s:
                for i, c in enumerate(s["buckets"]):
                    out[i] = out.get(i, 0) + c
                bounds = list(s["bounds"]) + [float("inf")]
                out["bounds"] = bounds
        return out
    a, b = counts(snap0), counts(snap1)
    if "bounds" not in b:
        return "not reported"
    top = max((i for i in b if i != "bounds" and b[i] > a.get(i, 0)),
              default=None)
    return "none" if top is None else f"<= {b['bounds'][top]:.4g}s"


def percentile(xs, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(xs, float), q)) if xs else \
        float("nan")


def e2e_metrics(rec: dict) -> dict:
    """The end-to-end numbers of the window [t0, t1] (harness clock)."""
    t0, t1 = rec["t0"], rec["t1"]
    ttft, itl, tokens = [], [], 0
    for q in rec["reqs"].values():
        if t0 <= q["due"] < t1:
            first = q["t"][0] if q["t"] and q["t"][0] <= t1 else t1
            ttft.append(first - q["due"])
        ts = [t for t in q["t"] if t0 <= t <= t1]
        tokens += len(ts)
        itl += [b - a for a, b in zip(ts, ts[1:])]
    return {"ttft_p95_s": percentile(ttft, 95),
            "ttft_p50_s": percentile(ttft, 50),
            "itl_p95_ms": 1e3 * percentile(itl, 95),
            "itl_p50_ms": 1e3 * percentile(itl, 50),
            "output_tokens_per_s": tokens / (t1 - t0),
            "n_ttft": len(ttft), "n_itl": len(itl)}


def layer_readers(bench: dict, cell: dict):
    """{name: (spec, module)} of the per-layer metrics whose cells include
    this one, each read by its own ``layer_metrics/<name>.py``."""
    from by_name import load_module
    return {spec["name"]: (spec, load_module("layer_metrics", spec["name"]))
            for spec in bench["per_layer"]
            if cell["name"] in spec.get("workloads", [cell["name"]])}


def layer_metrics(readers: dict, ctx: dict) -> dict:
    """Each reader's number; one that finds nothing to read returns None
    and its metric is left out of the line."""
    out = {}
    for name, (spec, mod) in readers.items():
        v = mod.read(ctx)
        if v is not None:
            out[name] = {"value": v, "unit": spec["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tests only: tiny sizes on the CPU, kernels "
                         "interpreted; never a measurement")
    ap.add_argument("--keep-trace", default=None,
                    help="also copy the raw profiler trace to this "
                         "directory")
    ap.add_argument("--trace-seconds", type=float, default=TRACE_SECONDS,
                    help="length of the profiled stretch at the window's "
                         "end")
    args = ap.parse_args(argv)
    try:
        result, checks = run(args)
    except Refused as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    for line in checks:
        print(line, file=sys.stderr)
    print(json.dumps(result))
    return 0


def run(args, control: bool = False, since: float | None = None):
    """One run: (result line, check lines). ``control`` (calibration
    only) also reads the float8 control's gap. Set-up is timed from the
    process's start, or from ``since`` (``perf_counter``) for a later run
    in the same process."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise Refused(f"no src/repro beside {HERE}: run from a checkout of "
                      f"the repository")
    sys.path[:0] = [p for p in (SRC, HERE) if p not in sys.path]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    # libtpu would otherwise log to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.makedirs(CACHE_DIR, exist_ok=True)
    seed = args.seed % 2 ** 63
    bench, cell, entry, config, mix_spec = load_cell(args.workload)
    from by_name import load_module
    arch = load_module("models", config["architecture"])
    readers = layer_readers(bench, cell) if args.trace else {}
    if args.rehearsal:
        rehearse(config, mix_spec, arch)
    if getattr(args, "rate", None) is not None:     # the knee sweep only
        mix_spec["arrivals"]["rate_per_s"] = args.rate

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: the cache is the checkout's own, and eviction's access
    # stamps would make every entry written without them unreadable
    jax.config.update("jax_compilation_cache_max_size", -1)
    devs = jax.devices()
    want = "cpu" if args.rehearsal else "tpu"
    if devs[0].platform != want:
        raise Refused(f"needs platform {want!r}; JAX found "
                      f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < cell["chips"]:
        raise Refused(f"the cell asks for {cell['chips']} chips; JAX found "
                      f"{len(devs)}")
    from compile_clock import CompileClock
    clock = CompileClock()

    import jax.numpy as jnp
    import check
    import traffic
    import weights
    from repro.core.router import CentroidRouter, RouterConfig
    from repro.models import build_model
    from repro.serve.api import EngineConfig
    from repro.serve.scheduler import make_engine

    m, dep, eng = config["model"], config["deployment"], config["engine"]
    K, strategy = dep["experts"], eng["strategy"]
    model = build_model(arch.model_config(m, entry["name"]))
    layout = arch.layout(m)
    weights.check_layout(model, layout)
    make = weights.make_expert_fn(layout, m["dtype"])
    experts = [make(key) for key in weights.expert_keys(seed, K)]
    jax.block_until_ready(experts)
    log(f"{K} experts x {weights.n_params(layout)} parameters made on "
        f"{devs[0].device_kind}; {device_bytes(devs[0])}")

    slots_total = eng["n_slots"] * (K if strategy == "top1" else 1)
    horizon = mix_spec["preroll_s"] + args.seconds
    gen = load_module("generators", mix_spec["generator"]).Generator(
        mix_spec, slots_total, horizon, seed, K)
    mix = traffic.Mix(mix_spec, seed, gen.n, K,
                      dep["router"]["feature_dim"], m["vocab_size"],
                      m["num_image_token"], m["projector_input_size"],
                      gen.order_seed)
    router = CentroidRouter(
        jnp.asarray(mix.centroids, jnp.float32),
        RouterConfig(temperature=dep["router"]["temperature"],
                     top_k=dep["router"]["top_k"]))
    served = experts
    if strategy == "mixture":
        # the stacked copy beside device-resident originals does not fit
        # one chip; from host memory the stacking uploads leaf by leaf
        served = jax.device_get(experts)
        experts = None
    engine = make_engine(model, experts=served, router=router,
                         config=EngineConfig(**eng, trace=bool(args.trace)))
    del served
    loop = HostLoop(engine, mix, m["num_image_token"])
    warm = warm_up(loop, mix, config, K, clock)
    log(f"warm-up {warm}; {device_bytes(devs[0])}")
    loop.reqs.clear()
    loop.steps.clear()
    loop.slowest_submit = (0.0, 0.0)
    full_gc = collect_and_freeze()
    log(f"full collection after warm-up {full_gc[0]:.6f}s over "
        f"{full_gc[1]} objects, then frozen")
    pauses = GcPauses()
    marks = {}

    def mark(which):
        if which == "open":         # set-up ends where the window opens
            marks["setup_s"] = process_age() if since is None else \
                time.perf_counter() - since
        marks[which] = (engine.export_metrics(), clock.snapshot())
        pauses.on = which == "open"

    tmp = tempfile.TemporaryDirectory() if args.trace else None
    t0, t1, traced = run_window(loop, gen, mix_spec["preroll_s"],
                                args.seconds,
                                tmp.name if tmp is not None else None, mark,
                                args.trace_seconds)
    (snap0, c0), (snap1, c1) = marks["open"], marks["close"]
    setup_s = marks["setup_s"]
    stats = devs[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    spans = engine.export_trace() if args.trace else None
    lateness = [q["sub"] - q["due"] for q in loop.reqs.values()
                if t0 <= q["due"] < t1]
    slowest_submit = loop.slowest_submit
    rec = {"t0": t0, "t1": t1, "reqs": loop.reqs, "steps": loop.steps,
           "metrics0": snap0, "metrics1": snap1, "traced": traced,
           "spans": spans, "compiles_in_window": c1["compiles"] -
           c0["compiles"]}
    del engine, loop
    gc.callbacks.remove(pauses)
    gc.unfreeze()               # the engine's cycles can go now
    gc.collect()

    red = None
    if tmp is not None:
        import glob
        import trace_reduce
        found = sorted(glob.glob(os.path.join(tmp.name, "**", "*.xplane.pb"),
                                 recursive=True))
        if found:
            if args.keep_trace:
                import shutil
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(found[-1], args.keep_trace)
            kernels = {mod.KERNEL: mod.PATTERN
                       for _, mod in readers.values()
                       if hasattr(mod, "PATTERN")}
            red = trace_reduce.reduce(trace_reduce.load(found[-1]), kernels)
        tmp.cleanup()

    keys = weights.expert_keys(seed, K)
    verdict = check.compare(
        rec, config, mix, arch,
        (lambda k: experts[k]) if experts else (lambda k: make(keys[k])),
        seed, K, strategy, cell["name"], control=control)
    attempted, failed = check.outcome(rec)
    e2e = e2e_metrics(rec)
    slow = max(((b - a, a - t0) for a, b, _ in rec["steps"]),
               default=(0.0, 0.0))
    log(f"slowest since the pre-roll: step {slow[0]:.6f}s at {slow[1]:.3f}s, "
        f"add_request {slowest_submit[0]:.6f}s at "
        f"{slowest_submit[1] - t0:.3f}s; garbage collections in the window "
        f"{pauses.summary()}; slowest dispatch "
        f"{slowest_bucket(snap0, snap1, 'serve_step_dispatch_seconds')}, "
        f"slowest device_get "
        f"{slowest_bucket(snap0, snap1, 'serve_step_device_get_seconds')}")
    log(f"device memory at the close: {stats}")
    log(f"window {t1 - t0:.3f}s: ttft p50 {e2e['ttft_p50_s']:.6f}s p95 "
        f"{e2e['ttft_p95_s']:.6f}s over {e2e['n_ttft']}; itl p50 "
        f"{e2e['itl_p50_ms']:.4f}ms p95 {e2e['itl_p95_ms']:.4f}ms over "
        f"{e2e['n_itl']}; {e2e['output_tokens_per_s']:.3f} tokens/s; "
        f"generator lateness p50 {percentile(lateness, 50):.6f}s max "
        f"{max(lateness, default=float('nan')):.6f}s; compiles in window "
        f"{rec['compiles_in_window']} (traces {c1['traces'] - c0['traces']}, "
        f"lowerings {c1['lowerings'] - c0['lowerings']}, "
        f"{c1['lower_s'] - c0['lower_s']:.3f}s); peak bytes {peak}; setup "
        f"{setup_s:.3f}s; compile clock {clock.snapshot()}")

    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if args.trace:
        ctx = {"rec": rec, "trace": red, "config": config, "arch": arch,
               "K": K, "strategy": strategy,
               "device_kind": devs[0].device_kind, "centroids": mix.centroids}
        metrics = layer_metrics(readers, ctx)
        if red is not None:
            dev["busy_s"], dev["window_s"] = red["busy_s"], red["window_s"]
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for spec in bench["end_to_end"]:
            name = spec["name"]
            if name != "setup_s" and cell["name"] in spec.get(
                    "workloads", [cell["name"]]):
                metrics[name] = {"value": e2e[name], "unit": spec["unit"]}
    result = {"correct": verdict["correct"], "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": dev}
    if red is not None:
        import trace_reduce
        result["breakdown"] = trace_reduce.breakdown(red)
    if control:
        result["control_gap"] = verdict["control_gap"]
        result["control_correct"] = verdict["control_correct"]
    result["checks"] = verdict["checks"]
    return result, verdict["lines"]


if __name__ == "__main__":
    sys.exit(main())
