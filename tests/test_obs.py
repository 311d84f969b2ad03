"""Engine telemetry layer: metrics registry, span tracing, Perfetto export.

Three strata:

* **Registry units** — counter/gauge/histogram semantics, get-or-create
  identity, base-label merging, the documented ``reset()`` contract, and
  the Prometheus text exposition format.
* **Recorder units** — ring-buffer bounding (metadata must survive
  wrap), event shapes for every Chrome ``ph`` kind, and the no-op
  recorder's zero-cost contract.
* **Engine integration** — a traced smoke server's exported trace must
  be schema-valid Perfetto JSON whose per-request spans tile the
  request's end-to-end latency EXACTLY (phases share boundary stamps);
  tracing must be observation-only (token parity with tracing off, zero
  events by default); TTFT/queue-delay must be measured from
  *submission* on a deliberately pool-starved queue; repeated
  ``serve()`` calls must not accumulate stale ``aborted``/``stopped``;
  the decentralized server's merged export must keep one ``pid`` per
  pod; speculative serving must populate the draft-source and
  accept-length diagnostics.
* **Spans on the profiler's clock** — ``EngineObs.span`` off reads no
  clock and makes no annotation; on, the annotations nest exactly like
  the ring's spans; the phase spans tile each pod step; every routing
  call is timed and traced.
"""
import json
import math

import jax
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.models import build_model
from repro.obs.engine import EngineObs
from repro.obs.metrics import (MetricsRegistry, log_buckets, prometheus,
                               snapshot)
from repro.obs.trace import (ADMIT_TID, SLOT_TID0, STEP_TID, NullRecorder,
                             TraceRecorder, merge_chrome, us)
from repro.serve.api import EngineConfig, SamplingParams
from repro.serve.scheduler import (DecentralizedSlotServer, Request,
                                   SlotServer)

CACHE_LEN = 48


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_smoke_config("qwen3_8b").reduced(vocab=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def prompts_of(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
            for n in lens]


def chunked_config(**kw):
    base = dict(n_slots=2, cache_len=CACHE_LEN, paged=True, page_block=8,
                chunked_prefill=True, chunk=8)
    base.update(kw)
    return EngineConfig(**base)


# ---------------------------------------------------------------------------
# Metrics registry units
# ---------------------------------------------------------------------------

def test_counter_gauge_semantics():
    r = MetricsRegistry()
    c = r.counter("c_total", "help")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("g")
    g.set(7)
    g.inc(-2)
    assert g.value == 5.0


def test_histogram_buckets_and_mean():
    r = MetricsRegistry()
    h = r.histogram("h_seconds", bounds=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 100.0):
        h.observe(v)
    assert h.counts == (1, 1, 1, 1)          # last = overflow (+Inf)
    assert h.count == 4 and h.sum == 105.0
    assert h.value == pytest.approx(105.0 / 4)
    with pytest.raises(ValueError):
        r.histogram("h_bad", bounds=(2.0, 1.0))
    # empty histogram's scalar summary is NaN, not a crash
    assert math.isnan(r.histogram("h_empty").value)


def test_log_buckets_span_and_monotonicity():
    b = log_buckets()
    assert b[0] == pytest.approx(1e-5) and b[-1] >= 10.0
    assert list(b) == sorted(b) and len(set(b)) == len(b)
    with pytest.raises(ValueError):
        log_buckets(lo=0)


def test_registry_get_or_create_and_type_conflict():
    r = MetricsRegistry(base_labels={"pod": "3"})
    c1 = r.counter("x_total", "first help")
    c2 = r.counter("x_total")
    assert c1 is c2 and c1.label_dict == {"pod": "3"}
    # same name, different labels → a distinct series of the same type
    c3 = r.counter("x_total", labels={"reason": "stop"})
    assert c3 is not c1
    assert c3.label_dict == {"pod": "3", "reason": "stop"}
    with pytest.raises(ValueError):
        r.gauge("x_total")
    assert r.get("x_total") is c1
    assert r.get("x_total", {"reason": "stop"}) is c3
    assert r.get("nope") is None


def test_registry_reset_keeps_handles_valid():
    r = MetricsRegistry()
    c = r.counter("c_total")
    h = r.histogram("h_seconds")
    c.inc(5)
    h.observe(1.0)
    r.reset()
    assert c.value == 0.0 and h.count == 0
    c.inc()                                   # the old handle still works
    assert r.get("c_total").value == 1.0


def test_prometheus_exposition_format():
    r = MetricsRegistry(base_labels={"pod": "0"})
    r.counter("req_total", "requests").inc(3)
    h = r.histogram("lat_seconds", "latency", bounds=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = r.to_prometheus()
    lines = text.splitlines()
    assert "# TYPE req_total counter" in lines
    assert 'req_total{pod="0"} 3.0' in lines
    # cumulative le buckets + the +Inf bucket + _sum/_count expansion
    assert 'lat_seconds_bucket{pod="0",le="0.1"} 1' in lines
    assert 'lat_seconds_bucket{pod="0",le="1.0"} 2' in lines
    assert 'lat_seconds_bucket{pod="0",le="+Inf"} 3' in lines
    assert 'lat_seconds_count{pod="0"} 3' in lines
    # TYPE once per name even across registries (one series per pod)
    r2 = MetricsRegistry(base_labels={"pod": "1"})
    r2.counter("req_total", "requests").inc(1)
    merged = prometheus([r, r2])
    assert merged.count("# TYPE req_total counter") == 1
    assert 'req_total{pod="1"} 1.0' in merged


def test_snapshot_merges_registries():
    r0 = MetricsRegistry(base_labels={"pod": "0"})
    r1 = MetricsRegistry(base_labels={"pod": "1"})
    r0.counter("c_total").inc()
    r1.counter("c_total").inc(2)
    snap = snapshot([r0, r1])
    vals = {m["labels"]["pod"]: m["value"] for m in snap["metrics"]}
    assert vals == {"0": 1.0, "1": 2.0}


# ---------------------------------------------------------------------------
# Trace recorder units
# ---------------------------------------------------------------------------

def test_null_recorder_is_inert():
    tr = NullRecorder(pid=0)
    assert tr.enabled is False
    tr.complete("x", 0.0, 1.0, 0)
    tr.instant("i", 0.5, 0)
    assert tr.events() == []
    assert tr.to_chrome()["traceEvents"] == []


def test_recorder_event_shapes():
    tr = TraceRecorder(capacity=64, pid=5)
    assert tr.enabled is True
    tr.set_process_name("pod 5")
    tr.set_thread_name(STEP_TID, "engine steps")
    tr.complete("span", 1.0, 1.25, SLOT_TID0, args={"rid": 7})
    tr.async_begin("queued", 1.0, 7)
    tr.async_end("queued", 2.0, 7)
    tr.instant("retire", 2.0, SLOT_TID0)
    tr.counter("engine", 2.0, {"active": 1})
    evs = tr.events()
    by_ph = {e["ph"]: e for e in evs}
    x = by_ph["X"]
    assert x["ts"] == us(1.0) and x["dur"] == us(1.25) - us(1.0)
    assert x["pid"] == 5 and x["tid"] == SLOT_TID0
    assert x["args"]["rid"] == 7
    assert by_ph["b"]["id"] == 7 and by_ph["e"]["id"] == 7
    assert by_ph["b"]["tid"] == ADMIT_TID
    assert by_ph["i"]["name"] == "retire"
    assert by_ph["C"]["args"] == {"active": 1}
    assert by_ph["M"]["ph"] == "M"
    # negative duration is clamped, never emitted
    tr.complete("clamped", 3.0, 2.0, 0)
    assert [e for e in tr.events() if e["name"] == "clamped"][0]["dur"] == 0


def test_ring_bounds_and_metadata_survive_wrap():
    tr = TraceRecorder(capacity=8, pid=0)
    tr.set_process_name("pod 0")
    tr.set_thread_name(0, "steps")
    for i in range(100):
        tr.instant(f"e{i}", float(i), 0)
    evs = tr.events()
    metas = [e for e in evs if e["ph"] == "M"]
    others = [e for e in evs if e["ph"] != "M"]
    assert len(metas) == 2                    # names survive the wrap
    assert len(others) == 8                   # ring holds the newest 8
    assert others[0]["name"] == "e92" and others[-1]["name"] == "e99"
    assert tr.dropped == 92
    with pytest.raises(ValueError):
        TraceRecorder(capacity=0)


def test_merge_chrome_concatenates_pods():
    a, b = TraceRecorder(capacity=8, pid=0), TraceRecorder(capacity=8, pid=1)
    a.instant("x", 1.0, 0)
    b.instant("y", 2.0, 0)
    doc = merge_chrome([a, b])
    assert {e["pid"] for e in doc["traceEvents"]} == {0, 1}


# ---------------------------------------------------------------------------
# Engine integration: schema, span sums, parity, TTFT, hygiene
# ---------------------------------------------------------------------------

REQ_KEYS = {"X": {"name", "ph", "ts", "dur", "pid", "tid"},
            "b": {"name", "ph", "ts", "pid", "tid", "id"},
            "e": {"name", "ph", "ts", "pid", "tid", "id"},
            "i": {"name", "ph", "ts", "pid", "tid"},
            "C": {"name", "ph", "ts", "pid", "args"},
            "M": {"name", "ph", "pid", "args"}}


def validate_chrome(doc, n_slots, pids):
    """Schema-validate a Chrome/Perfetto trace_event document."""
    assert set(doc) >= {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    for e in evs:
        assert e["ph"] in REQ_KEYS, e
        missing = REQ_KEYS[e["ph"]] - set(e)
        assert not missing, (e, missing)
        if e["ph"] in ("X", "b", "e", "i"):
            assert isinstance(e["ts"], int) and e["ts"] >= 0, e
        if e["ph"] == "X":
            assert isinstance(e["dur"], int) and e["dur"] >= 0, e
    # X spans must nest properly per (pid, tid) track: sort by (start,
    # -dur) and check the enclosing-interval stack property
    tracks = {}
    for e in evs:
        if e["ph"] == "X":
            tracks.setdefault((e["pid"], e["tid"]), []).append(e)
    for track, spans in tracks.items():
        spans.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for e in spans:
            while stack and e["ts"] >= stack[-1]:
                stack.pop()
            if stack:
                assert e["ts"] + e["dur"] <= stack[-1], \
                    (track, e, "overlaps an enclosing span")
            stack.append(e["ts"] + e["dur"])
    # track naming: one process_name per pod, one thread_name per slot
    # track plus the step + admission tracks
    for pid in pids:
        pmeta = [e for e in evs if e["ph"] == "M" and e["pid"] == pid]
        names = {e["name"]: e for e in pmeta}
        assert "process_name" in names, pid
        tids = {e["tid"] for e in pmeta if e["name"] == "thread_name"}
        assert tids >= {STEP_TID, ADMIT_TID} | \
            {SLOT_TID0 + s for s in range(n_slots)}, (pid, tids)
    return evs


def serve_traced(model, params, prompts, max_new=6, **cfg_kw):
    srv = SlotServer(model, params,
                     config=chunked_config(trace=True, prefix_cache=True,
                                           **cfg_kw))
    reqs = [Request(i, p, max_new) for i, p in enumerate(prompts)]
    out = srv.serve(reqs)
    return srv, reqs, out


def test_trace_schema_and_span_taxonomy(dense_setup):
    cfg, model, params = dense_setup
    srv, reqs, out = serve_traced(model, params,
                                  prompts_of(cfg, (12, 9, 14, 7)))
    doc = srv.export_trace()
    evs = validate_chrome(doc, n_slots=2, pids=[0])
    names = {e["name"] for e in evs if e["ph"] == "X"}
    # the documented span taxonomy (docs/observability.md)
    assert {"admission", "prefix_match", "prefill", "decode",
            "dispatch", "device_get"} <= names
    assert any(n.startswith("prefill_chunk[") for n in names)
    assert any(n.startswith("step:") for n in names)
    # every request retires exactly once, with its finish reason
    retires = [e for e in evs if e["ph"] == "i" and e["name"] == "retire"]
    assert len(retires) == len(reqs)
    assert all(e["args"]["finish_reason"] == "length" for e in retires)
    # queued async spans pair up b/e per rid
    for kind in ("b", "e"):
        assert {e["id"] for e in evs
                if e["ph"] == kind and e["name"] == "queued"} \
            == {r.rid for r in reqs}


def test_spans_tile_end_to_end_latency_exactly(dense_setup):
    """Phases share boundary stamps, so in integer µs each request's
    queued + admission + prefill(+chunks are nested) + decode spans
    telescope to exactly ``us(t_done) - us(t_submit)`` — the acceptance
    criterion's 'spans sum to end-to-end latency within stamp
    granularity', with zero slack because the boundaries are the SAME
    perf_counter values, not re-stamped."""
    cfg, model, params = dense_setup
    srv, reqs, _ = serve_traced(model, params, prompts_of(cfg, (12, 9, 15)))
    evs = srv.export_trace()["traceEvents"]
    for req in reqs:
        rid = req.rid
        phase = [e for e in evs if e["ph"] == "X"
                 and e["name"] in ("admission", "prefill", "decode")
                 and e["args"].get("rid") == rid]
        q_b = next(e for e in evs if e["ph"] == "b" and e["id"] == rid)
        q_e = next(e for e in evs if e["ph"] == "e" and e["id"] == rid)
        total = (q_e["ts"] - q_b["ts"]) + sum(e["dur"] for e in phase)
        assert total == us(req.t_done) - us(req.t_submit), \
            (rid, total, us(req.t_done) - us(req.t_submit))
        # and the phases are contiguous: each span starts where the
        # previous one ended
        phase.sort(key=lambda e: e["ts"])
        assert phase[0]["ts"] == q_e["ts"]
        for a, b in zip(phase, phase[1:]):
            assert a["ts"] + a["dur"] == b["ts"], (rid, a, b)


# ---------------------------------------------------------------------------
# Spans on the profiler's clock: EngineObs.span and its annotation factory
# ---------------------------------------------------------------------------

# the benchmark harness's own annotations, which no program span may reuse
HARNESS_NAMES = {"engine.step", "engine.add_request", "generator.wait"}
# the spans that reach the profiler (request-lifetime spans do not)
PROFILER_SPANS = {"step", "admit", "schedule", "dispatch", "device_get",
                  "advance", "outputs", "admission", "prefix_match",
                  "route", "preempt"}
PHASE_ORDER = ("admit", "schedule", "dispatch", "device_get", "advance",
               "outputs")


class FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each enter and
    exit with the name and args the annotation carried."""

    def __init__(self, log, name, **args):
        self.log, self.name, self.args = log, name, dict(args)

    def __enter__(self):
        self.log.append(("enter", self.name, self.args))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, self.args))

    def set_metadata(self, **args):
        self.args.update(args)


def annotation_factory(log):
    return lambda name, **args: FakeAnnotation(log, name, **args)


def test_span_off_reads_no_clock_and_makes_no_annotation(monkeypatch):
    import repro.obs.engine as engine_mod
    from repro.obs.engine import NULL_SPAN
    calls = []
    monkeypatch.setattr(engine_mod.time, "perf_counter",
                        lambda: calls.append(1) or 0.0)
    log = []
    obs = EngineObs(trace=False, annotate=annotation_factory(log))
    for _ in range(100):
        with obs.span("dispatch") as span:
            span.note(rid=1)
            span.kind("decode")
            span.stamp(0.0, 1.0, SLOT_TID0)
            span.drop()
        assert span is NULL_SPAN
    assert obs.span("admission", ADMIT_TID, rid=3) is NULL_SPAN
    assert calls == [] and log == []
    assert obs.trace.events() == []


def test_span_on_writes_the_ring_and_the_annotation():
    log = []
    obs = EngineObs(pod=3, trace=True, annotate=annotation_factory(log))
    with obs.span("step") as step:
        with obs.span("prefix_match", SLOT_TID0, rid=7) as span:
            span.note(hit_blocks=2)
        with obs.span("admission", ADMIT_TID) as span:
            span.drop()
        step.kind("decode")
    assert [e[:2] for e in log] == [
        ("enter", "step"), ("enter", "prefix_match"),
        ("exit", "prefix_match"), ("enter", "admission"),
        ("exit", "admission"), ("exit", "step")]
    assert log[-1][2] == {"pod": 3, "kind": "decode"}
    assert log[2][2] == {"pod": 3, "rid": 7, "hit_blocks": 2}
    xs = {e["name"]: e for e in obs.trace.events() if e["ph"] == "X"}
    assert set(xs) == {"step:decode", "prefix_match"}   # dropped: no event
    assert xs["prefix_match"]["args"] == {"rid": 7, "hit_blocks": 2}
    assert xs["prefix_match"]["tid"] == SLOT_TID0 and \
        xs["prefix_match"]["pid"] == 3
    assert "args" not in xs["step:decode"]
    # a span without an injected factory still writes the ring
    plain = EngineObs(trace=True)
    with plain.span("outputs"):
        pass
    assert [e["name"] for e in plain.trace.events()] == ["outputs"]


def _ring_tree(evs, pid):
    """[(name, parent)] of a pod's profiler-bound X spans in start order,
    nesting by timestamps across the pod's tracks (one host thread)."""
    spans = [e for e in evs if e["ph"] == "X" and e["pid"] == pid and
             (e["name"].split(":")[0] in PROFILER_SPANS or
              e["name"].startswith("prefill_chunk["))]
    spans.sort(key=lambda e: (e["ts"], -e["dur"]))
    out, stack = [], []
    for e in spans:
        while stack and e["ts"] >= stack[-1]["ts"] + stack[-1]["dur"]:
            stack.pop()
        out.append((e["name"], stack[-1]["name"] if stack else None))
        stack.append(e)
    return out


def _annotation_tree(log):
    """[(name, parent)] in enter order, ``step`` named ``step:<kind>`` as
    the ring names it."""
    def label(name, args):
        return f"{name}:{args['kind']}" if name == "step" else name
    out, stack = [], []
    for ev in log:
        if ev[0] == "enter":
            out.append([ev, stack[-1] if stack else None])
            stack.append(ev)
        elif ev[0] == "exit":
            stack.pop()
    return [(label(e[1], e[2]), label(p[1], p[2]) if p else None)
            for e, p in out]


def test_profiler_spans_nest_like_the_ring(dense_setup, monkeypatch):
    """Each span the program hands the profiler is entered and left like
    the ring's X spans nest; on a strictly increasing clock the two trees
    are the same, and no program span takes a harness annotation's name."""
    import time as time_mod
    clock = iter(range(1, 10 ** 9))
    monkeypatch.setattr(time_mod, "perf_counter",
                        lambda: next(clock) * 1e-3)
    cfg, model, params = dense_setup
    srv = SlotServer(model, params,
                     config=chunked_config(trace=True, prefix_cache=True))
    log = []
    srv.obs.annotate = annotation_factory(log)
    ps = prompts_of(cfg, (12, 9, 14, 7))
    srv.serve([Request(i, p, 5) for i, p in enumerate(ps)])
    ann = _annotation_tree(log)
    ring = _ring_tree(srv.export_trace()["traceEvents"], 0)
    assert ann == ring
    names = {n for n, _ in ann}
    assert not names & HARNESS_NAMES
    assert {"admission", "prefix_match", "dispatch", "device_get"} <= names
    assert any(n.startswith("prefill_chunk[") for n in names)
    assert all(e[2]["pod"] == 0 for e in log if e[0] == "enter")


def test_phase_spans_tile_each_step(dense_setup):
    """Each ``step:<kind>`` span on the engine-steps track holds its phases
    in order, disjoint, and covering it but for the few stamps between
    them; the always-on histograms count one dispatch per ``dispatch`` span
    and one readback per ``device_get`` span (an intermediate chunk reads
    nothing back)."""
    cfg, model, params = dense_setup
    srv, reqs, _ = serve_traced(model, params,
                                prompts_of(cfg, (12, 9, 30, 7)))
    evs = [e for e in srv.export_trace()["traceEvents"]
           if e["ph"] == "X" and e["tid"] == STEP_TID]
    steps = [e for e in evs if e["name"].startswith("step:")]
    phases = [e for e in evs if e["name"] in PHASE_ORDER]
    kinds = {s["name"] for s in steps}
    assert {"step:chunk", "step:decode+chunk", "step:decode"} <= kinds
    covered = total = 0
    for s in steps:
        lo, hi = s["ts"], s["ts"] + s["dur"]
        inner = sorted((p for p in phases if lo <= p["ts"] < hi),
                       key=lambda p: p["ts"])
        seq = [p["name"] for p in inner]
        assert seq[0] == "admit" and seq[-1] == "outputs", seq
        assert [PHASE_ORDER.index(n) for n in seq] == sorted(
            PHASE_ORDER.index(n) for n in seq), seq
        if s["name"] == "step:none":
            assert seq == ["admit", "outputs"]
        else:
            assert "dispatch" in seq and "advance" in seq, seq
        for a, b in zip(inner, inner[1:]):
            assert a["ts"] + a["dur"] <= b["ts"], (a, b)
        assert inner[-1]["ts"] + inner[-1]["dur"] <= hi
        covered += sum(p["dur"] for p in inner)
        total += s["dur"]
    assert covered >= 0.95 * total, (covered, total)
    n = {k: sum(1 for p in phases if p["name"] == k)
         for k in ("dispatch", "device_get")}
    assert srv.obs.dispatch_s.count == n["dispatch"]
    assert srv.obs.readback_s.count == n["device_get"] < n["dispatch"]


def test_router_seconds_time_every_routing_call(dense_setup):
    """``serve_router_seconds`` observes each front-end routing call on
    the pod the request went to (top-1), and each admission's routing on
    the mixture core; traced, each call is a ``route`` span."""
    cfg, model, params = dense_setup
    from repro.core.router import CentroidRouter, RouterConfig
    K = 2
    rng = np.random.default_rng(1)
    experts = [model.init(jax.random.PRNGKey(k)) for k in range(K)]
    router = CentroidRouter(
        jax.numpy.asarray(rng.normal(size=(K, 8)), jax.numpy.float32),
        RouterConfig(top_k=2))
    ps = prompts_of(cfg, (10, 9, 11, 8, 12))
    feats = rng.normal(size=(len(ps), 8)).astype(np.float32)
    for strategy in ("top1", "mixture"):
        srv = DecentralizedSlotServer(
            model, experts, router,
            config=chunked_config(trace=True, strategy=strategy))
        srv.serve([Request(i, p, 3, features=feats[i])
                   for i, p in enumerate(ps)])
        engines = srv._engines()
        counts = [e.obs.router_s.count for e in engines]
        assert sum(counts) == len(ps), (strategy, counts)
        if strategy == "top1":
            assert counts == [e.obs.submitted.value for e in engines]
        assert all(e.obs.router_s.sum > 0 for e in engines
                   if e.obs.router_s.count)
        routes = [e for e in srv.export_trace()["traceEvents"]
                  if e["ph"] == "X" and e["name"] == "route"]
        assert len(routes) == len(ps)


def test_tracing_is_observation_only(dense_setup):
    """Token-exact parity with tracing off — and the default (no-op
    recorder) path records nothing at all."""
    cfg, model, params = dense_setup
    ps = prompts_of(cfg, (12, 9, 14, 7))
    srv_off = SlotServer(model, params, config=chunked_config())
    out_off = srv_off.serve([Request(i, p, 6) for i, p in enumerate(ps)])
    _, _, out_on = serve_traced(model, params, ps)
    assert out_on == out_off
    assert srv_off.obs.trace.enabled is False
    assert srv_off.export_trace()["traceEvents"] == []
    # metrics are always on regardless of tracing
    assert srv_off.obs.steps.value > 0
    assert srv_off.obs.e2e_s.count == len(ps)


def test_ttft_measured_from_submission_under_pool_starvation(dense_setup):
    """The TTFT satellite: a pool-starved queue (every block in use until
    retirements free them) must report its wait in BOTH ``queued_s`` and
    ``ttft_s`` — TTFT from submission, never from admission."""
    cfg, model, params = dense_setup
    ps = prompts_of(cfg, (16, 16, 16, 16, 16, 16))
    # 2 slots, and a pool of just enough blocks for ~2 live requests:
    # later requests stay queued until a retirement frees blocks
    srv = SlotServer(model, params, config=chunked_config(pool_blocks=7))
    outs = {}
    for i, p in enumerate(ps):
        srv.add_request(p, SamplingParams(max_new=6), rid=i)
    while srv.has_unfinished():
        for o in srv.step():
            if o.finished:
                outs[o.rid] = o
    assert len(outs) == len(ps)
    for o in outs.values():
        assert o.t_admit >= o.t_submit > 0
        assert o.queued_s >= 0 and not math.isnan(o.queued_s)
        # TTFT includes the queue delay: first token can only follow
        # admission
        assert o.ttft_s >= o.queued_s
        assert o.ttft == o.ttft_s            # the explicit-unit alias
    # the starved tail waited on retirements — real, visible queue delay
    tail = sorted(outs.values(), key=lambda o: o.t_admit)[-1]
    head = sorted(outs.values(), key=lambda o: o.t_admit)[0]
    assert tail.queued_s > head.queued_s
    assert tail.queued_s > 1e-4
    # the registry saw every request's latency triple
    assert srv.obs.queued_s.count == len(ps)
    assert srv.obs.ttft_s.count == len(ps)
    assert srv.obs.e2e_s.count == len(ps)


def test_repeated_serve_does_not_accumulate_stats(dense_setup):
    """The stats-hygiene satellite: ``aborted``/``stopped`` in
    ``stats()`` are per-``serve()``-run, not process-lifetime."""
    cfg, model, params = dense_setup
    ps = prompts_of(cfg, (10, 10))
    srv = SlotServer(model, params, config=chunked_config())
    # run 1: force one stop and one abort
    first = srv.serve([Request(0, ps[0], 8)])[0][0]
    srv.add_request(ps[0], SamplingParams(max_new=8,
                                          stop_token_ids=(first,)), rid=10)
    srv.add_request(ps[1], SamplingParams(max_new=8), rid=11)
    srv.abort(11)
    while srv.has_unfinished():
        srv.step()
    st = srv.stats()
    assert st["stopped"] == 1 and st["aborted"] == 1
    # run 2 (plain): a fresh serve() must start the counters at zero
    out = srv.serve([Request(20, ps[1], 4)])
    assert len(out) == 1
    st2 = srv.stats()
    assert st2["stopped"] == 0 and st2["aborted"] == 0
    # ...while cumulative registry series keep counting across runs
    assert srv.obs.admitted.value >= 3
    # full registry reset is the documented wider hammer
    srv.metrics.reset()
    assert srv.obs.admitted.value == 0


def test_decentralized_trace_keeps_one_pid_per_pod(dense_setup):
    cfg, model, params = dense_setup
    K = 2
    from repro.core.router import CentroidRouter, RouterConfig
    rng = np.random.default_rng(0)
    experts = [model.init(jax.random.PRNGKey(k)) for k in range(K)]
    router = CentroidRouter(
        jax.numpy.asarray(rng.normal(size=(K, 8)), jax.numpy.float32),
        RouterConfig())
    srv = DecentralizedSlotServer(
        model, experts, router, config=chunked_config(trace=True))
    ps = prompts_of(cfg, (10, 9, 11, 8))
    feats = rng.normal(size=(len(ps), 8)).astype(np.float32)
    out = srv.serve([Request(i, p, 4, features=feats[i])
                     for i, p in enumerate(ps)])
    assert len(out) == len(ps)
    doc = srv.export_trace()
    pids = {e["pid"] for e in doc["traceEvents"]}
    assert pids == {0, 1}
    validate_chrome(doc, n_slots=2, pids=[0, 1])
    # per-pod labels distinguish the merged metrics series
    snap = srv.export_metrics()
    pods = {m["labels"].get("pod") for m in snap["metrics"]}
    assert pods == {"0", "1"}
    text = srv.prometheus_metrics()
    assert 'pod="0"' in text and 'pod="1"' in text
    # run-scoped reset works across pods too
    srv.reset_stats()
    assert all(p.stats()["stopped"] == 0 for p in srv.pods)


def test_speculative_diagnostics_populate(dense_setup):
    """Accept-length + draft-source diagnostics: a repetitive greedy
    workload through the ngram-speculative server must fill the
    accept-length histogram, the per-request accept-rate histogram, and
    the per-source draft counters — the registry view that makes an
    aggregate accept rate per-workload explainable."""
    cfg, model, params = dense_setup
    rng = np.random.default_rng(0)
    ps = []
    for n in (9, 13, 11):
        base = rng.integers(1, cfg.vocab, size=4)
        ps.append(np.tile(base, n // 4 + 2)[:n].astype(np.int32))
    ecfg = EngineConfig(n_slots=2, cache_len=CACHE_LEN, paged=True,
                        page_block=8, speculative="ngram", spec_len=4)
    srv = SlotServer(model, params, config=ecfg)
    out = srv.serve([Request(i, p, 16) for i, p in enumerate(ps)])
    assert len(out) == len(ps)
    obs = srv.obs
    assert obs.n_spec_steps > 0
    assert obs.accept_len.count == obs.n_spec_steps
    assert obs.accept_len.sum == obs.n_spec_tokens
    assert obs.req_accept_rate.count == len(ps)
    assert 0.0 <= obs.req_accept_rate.value <= 1.0
    proposed = obs.drafts("ngram", "proposed").value
    accepted = obs.drafts("ngram", "accepted").value
    assert proposed == obs.n_spec_steps * (ecfg.spec_len - 1)
    assert accepted == obs.n_spec_tokens - obs.n_spec_steps
    assert 0 <= accepted <= proposed


def test_engine_config_validates_trace_ring():
    with pytest.raises(ValueError):
        EngineConfig(trace=True, trace_ring=0).validate(None)


def test_aborted_from_queue_closes_queued_span(dense_setup):
    """A request aborted while still waiting (never admitted) must still
    appear in the trace — its queued span closes at the abort, keeping
    the trace an honest record of every request the engine saw."""
    cfg, model, params = dense_setup
    srv = SlotServer(model, params,
                     config=chunked_config(n_slots=1, trace=True))
    ps = prompts_of(cfg, (10, 10))
    srv.add_request(ps[0], SamplingParams(max_new=40), rid=0)
    srv.step()                       # rid 0 occupies the only slot
    srv.add_request(ps[1], SamplingParams(max_new=4), rid=1)
    out = srv.abort(1)
    assert out is not None and out.finish_reason == "aborted"
    while srv.has_unfinished():
        srv.step()
    evs = srv.export_trace()["traceEvents"]
    q = [e for e in evs if e["ph"] in ("b", "e") and e["id"] == 1]
    assert {e["ph"] for e in q} == {"b", "e"}
    aborts = [e for e in evs if e["ph"] == "i" and e["name"] == "abort"]
    assert len(aborts) == 1 and aborts[0]["args"]["rid"] == 1
    assert srv.obs.n_aborted == 1
