"""Per-engine telemetry bundle: registry handles + trace recorder + spans.

One :class:`EngineObs` per ``_SlotTable`` (per pod on the decentralized
server). It owns the engine's private :class:`MetricsRegistry` (labelled
``pod=<k>``), caches every hot-path instrument handle at construction so
the step loop does dict-free attribute loads, and holds either a real
:class:`TraceRecorder` or the :class:`NullRecorder` off-switch.

The metrics side is **always on** — plain-Python counter bumps and a few
``perf_counter`` stamps per engine step, orders of magnitude below the
device dispatch they time (the ``serve_obs`` bench gates the full
trace+metrics overhead at ≤ 1.05×). The trace side is off by default.

Spans (:meth:`EngineObs.span`) are the one way host code marks a stretch
of its own work. Tracing on, a span stamps the Chrome ring and also
enters a host annotation on the profiler's trace, so the program's phases
lie on the same clock as the device's operations. The annotation factory
is injected by whoever builds the bundle (the scheduler passes
``jax.profiler.TraceAnnotation``): this package imports no jax. Tracing
off, every span is one shared no-op object — no clock read, no
annotation.

Metric catalog lives in docs/observability.md; names are stable surface.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional

from repro.obs import metrics as _m
from repro.obs.trace import (ADMIT_TID, SLOT_TID0, STEP_TID, NullRecorder,
                             TraceRecorder)

__all__ = ["EngineObs", "NULL_SPAN"]

# Accept-length histogram: speculative spans commit 1..spec_len tokens
# per verify step; unit-width buckets make the histogram an exact
# distribution over commit lengths for any spec_len <= 16.
ACCEPT_LEN_BUCKETS = tuple(float(i) for i in range(1, 17))
# Per-request accept-rate in [0, 1], tenth-width buckets.
RATE_BUCKETS = tuple(round(0.1 * i, 1) for i in range(0, 11))


class _NullSpan:
    """The span of an engine that is not tracing: every method is a
    no-op, and one instance serves every call site."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def note(self, **args) -> None:
        pass

    def stamp(self, t0: float, t1: float, tid: int) -> None:
        pass

    def kind(self, kind: str) -> None:
        pass

    def drop(self) -> None:
        pass


NULL_SPAN = _NullSpan()


class _NoAnnotation:
    """Stands in for the profiler's annotation when none was injected."""

    def __init__(self, name: str, **args) -> None:
        pass

    def __enter__(self) -> "_NoAnnotation":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set_metadata(self, **args) -> None:
        pass


class _Span:
    """One traced stretch of host work: a profiler annotation entered for
    its whole extent, and a Chrome ``X`` event written to the ring when
    it closes. The ring event keeps the name, track and args the site
    gives, and the span's own ``perf_counter`` stamps unless the site
    supplies boundary stamps that other spans share (:meth:`stamp`)."""

    __slots__ = ("_obs", "_ann", "name", "tid", "args", "t0", "t1",
                 "_keep")

    def __init__(self, obs: "EngineObs", name: str, tid: int,
                 args: dict) -> None:
        self._obs, self.name, self.tid, self.args = obs, name, tid, args
        self.t0 = self.t1 = None
        self._keep = True

    def __enter__(self) -> "_Span":
        self._ann = self._obs.annotate(self.name, pod=self._obs.pod,
                                       **self.args)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter() if self.t1 is None else self.t1
        self._ann.__exit__(*exc)
        if self._keep:
            self._obs.trace.complete(self.name, self.t0, t1, self.tid,
                                     args=self.args or None)

    def note(self, **args) -> None:
        """Add args learnt inside the span (ring args and annotation
        metadata alike)."""
        self.args.update(args)
        self._ann.set_metadata(**args)

    def stamp(self, t0: float, t1: float, tid: int) -> None:
        """Write the ring event at these stamps, on this track: for spans
        whose boundaries other spans share exactly (a request's phases
        tile its latency), and whose track is known only inside."""
        self.t0, self.t1, self.tid = t0, t1, tid

    def kind(self, kind: str) -> None:
        """Name a step by what it dispatched, known only once scheduled:
        the ring event becomes ``<name>:<kind>``; the annotation, named
        when it was entered, carries ``kind`` as an arg."""
        self.name = f"{self.name}:{kind}"
        self._ann.set_metadata(kind=kind)

    def drop(self) -> None:
        """Write no ring event (an admission attempt that failed)."""
        self._keep = False


class EngineObs:
    """Telemetry handles for one engine/pod.

    Parameters
    ----------
    pod: pod index — becomes the trace ``pid`` and the registry's
        ``pod`` label.
    trace: attach a real ring-buffer recorder (else the no-op recorder).
    trace_ring: ring capacity when tracing.
    publish: attach this registry to the process-global exposition set
        (``EngineConfig(metrics=True)``).
    annotate: the profiler's host-annotation factory, called as
        ``annotate(name, **args)`` and entered for each span's extent
        while tracing (``jax.profiler.TraceAnnotation``); None writes the
        Chrome ring only.
    """

    def __init__(self, *, pod: int = 0, trace: bool = False,
                 trace_ring: int = 65536, publish: bool = False,
                 annotate: Optional[Callable[..., object]] = None) -> None:
        self.pod = pod
        self.annotate = annotate if annotate is not None else _NoAnnotation
        self.registry = _m.MetricsRegistry(base_labels={"pod": str(pod)})
        self.trace: NullRecorder = (
            TraceRecorder(capacity=trace_ring, pid=pod) if trace
            else NullRecorder(pid=pod))
        if publish:
            _m.attach(self.registry)
        r = self.registry
        # -- request lifecycle (counters) --------------------------------
        self.submitted = r.counter(
            "serve_requests_submitted_total",
            "requests handed to add_request")
        self.admitted = r.counter(
            "serve_admissions_total",
            "requests that won a slot (or retired at admission)")
        self.aborted = r.counter(
            "serve_aborts_total", "requests cancelled via abort()")
        self._retired: Dict[str, _m.Counter] = {}
        # -- step loop ----------------------------------------------------
        self.steps = r.counter("serve_engine_steps_total",
                               "engine step() iterations")
        self.dispatch_s = r.histogram(
            "serve_step_dispatch_seconds",
            "host time to build + launch the fused step dispatch")
        self.readback_s = r.histogram(
            "serve_step_device_get_seconds",
            "host time blocked in the one per-step jax.device_get")
        self.router_s = r.histogram(
            "serve_router_seconds",
            "host time of one front-end routing call, readback included")
        self.active_g = r.gauge("serve_active_slots",
                                "slots holding a live request")
        self.waiting_g = r.gauge("serve_waiting_requests",
                                 "requests queued for admission")
        self.pool_free_g = r.gauge("serve_pool_free_blocks",
                                   "free physical KV blocks in the pool")
        self.pool_total_g = r.gauge("serve_pool_blocks",
                                    "physical KV blocks in the pool")
        pool_help = ("step-program calls on a paged pool, by whether the "
                     "donated pool was consumed (kept: updated in place) "
                     "or survived the call (copied)")
        self.pool_kept = r.counter("serve_pool_inplace_total", pool_help,
                                   labels={"outcome": "kept"})
        self.pool_copied = r.counter("serve_pool_inplace_total", pool_help,
                                     labels={"outcome": "copied"})
        # -- request latency (histograms) --------------------------------
        self.queued_s = r.histogram(
            "serve_request_queued_seconds",
            "submission to admission (queue delay)")
        self.ttft_s = r.histogram(
            "serve_request_ttft_seconds",
            "submission to first emitted token")
        self.e2e_s = r.histogram(
            "serve_request_e2e_seconds", "submission to retirement")
        # -- speculative decoding ----------------------------------------
        self.spec_steps = r.counter(
            "serve_spec_steps_total", "speculative verify dispatches")
        self.spec_tokens = r.counter(
            "serve_spec_tokens_total",
            "tokens committed by speculative verify steps")
        self.accept_len = r.histogram(
            "serve_spec_accept_length",
            "tokens committed per verify step (1 = all drafts rejected)",
            bounds=ACCEPT_LEN_BUCKETS)
        self.req_accept_rate = r.histogram(
            "serve_spec_request_accept_rate",
            "per-request draft acceptance rate at retirement",
            bounds=RATE_BUCKETS)
        self._drafts: Dict[str, Dict[str, _m.Counter]] = {}
        # -- multi-tenant QoS (lazily-resolved tenant-labelled counters) --
        self._tenant_tokens: Dict[str, _m.Counter] = {}
        self._preempted: Dict[str, _m.Counter] = {}
        self._resumed: Dict[str, _m.Counter] = {}
        self._rejected: Dict[str, _m.Counter] = {}

    # -- labelled lazily-resolved counters --------------------------------
    def retired(self, reason: str) -> _m.Counter:
        """`serve_retirements_total{reason=...}` — one per finish reason."""
        c = self._retired.get(reason)
        if c is None:
            c = self.registry.counter(
                "serve_retirements_total",
                "requests retired from a slot, by finish_reason",
                labels={"reason": reason})
            self._retired[reason] = c
        return c

    def drafts(self, source: str, kind: str) -> _m.Counter:
        """`serve_spec_drafts_{proposed,accepted}_total{source=...}`."""
        by_kind = self._drafts.setdefault(source, {})
        c = by_kind.get(kind)
        if c is None:
            c = self.registry.counter(
                f"serve_spec_drafts_{kind}_total",
                f"draft tokens {kind}, by draft source",
                labels={"source": source})
            by_kind[kind] = c
        return c

    def tenant_tokens(self, tenant: str) -> _m.Counter:
        """`serve_tenant_tokens_total{tenant=...}` — tokens emitted for
        the tenant's retired requests (live requests are added by
        ``stats()`` on top of this cumulative base)."""
        c = self._tenant_tokens.get(tenant)
        if c is None:
            c = self.registry.counter(
                "serve_tenant_tokens_total",
                "tokens emitted, by tenant (counted at retirement)",
                labels={"tenant": tenant})
            self._tenant_tokens[tenant] = c
        return c

    def preempted(self, tenant: str, mode: str) -> _m.Counter:
        """`serve_preemptions_total{tenant=,mode=}` — requests parked
        (swap/recompute) or bounced back mid-prefill (requeue)."""
        key = f"{tenant}\x00{mode}"
        c = self._preempted.get(key)
        if c is None:
            c = self.registry.counter(
                "serve_preemptions_total",
                "decoding/prefilling requests preempted, by tenant + mode",
                labels={"tenant": tenant, "mode": mode})
            self._preempted[key] = c
        return c

    def resumed(self, tenant: str) -> _m.Counter:
        """`serve_resumes_total{tenant=...}` — preempted requests
        re-admitted into a slot."""
        c = self._resumed.get(tenant)
        if c is None:
            c = self.registry.counter(
                "serve_resumes_total",
                "preempted requests re-admitted, by tenant",
                labels={"tenant": tenant})
            self._resumed[tenant] = c
        return c

    def rejected(self, tenant: str) -> _m.Counter:
        """`serve_rejections_total{tenant=...}` — submissions refused by
        admission control (finish_reason="rejected")."""
        c = self._rejected.get(tenant)
        if c is None:
            c = self.registry.counter(
                "serve_rejections_total",
                "submissions refused by admission control, by tenant",
                labels={"tenant": tenant})
            self._rejected[tenant] = c
        return c

    # -- aggregate views used by stats() ----------------------------------
    @property
    def n_aborted(self) -> int:
        return int(self.aborted.value)

    @property
    def n_stopped(self) -> int:
        c = self._retired.get("stop")
        return int(c.value) if c is not None else 0

    @property
    def n_spec_steps(self) -> int:
        return int(self.spec_steps.value)

    @property
    def n_spec_tokens(self) -> int:
        return int(self.spec_tokens.value)

    def reset_run_counters(self) -> None:
        """Per-run hygiene: zero the request-lifecycle counters.

        Called at the top of ``serve()`` so back-to-back drain loops on
        one engine report that run's ``aborted``/``stopped`` alone.
        Cumulative series (spec totals, prefix cache, latency
        histograms) are left to the full ``registry.reset()``.
        """
        self.aborted.reset()
        for c in self._retired.values():
            c.reset()

    # -- trace conveniences ------------------------------------------------
    def name_tracks(self, n_slots: int, label: str) -> None:
        """Emit the "M" metadata naming this pod + its fixed tracks."""
        tr = self.trace
        if not tr.enabled:
            return
        tr.set_process_name(label)
        tr.set_thread_name(STEP_TID, "engine steps")
        tr.set_thread_name(ADMIT_TID, "queue / admission-retired")
        for s in range(n_slots):
            tr.set_thread_name(SLOT_TID0 + s, f"slot {s}")

    @staticmethod
    def slot_tid(slot: int) -> int:
        return SLOT_TID0 + slot

    def span(self, name: str, tid: int = STEP_TID, **args):
        """A context manager marking host work ``name`` on track ``tid``
        (``args`` go to the ring event and the annotation, which also
        carries ``pod``). Tracing off it is :data:`NULL_SPAN`."""
        if not self.trace.enabled:
            return NULL_SPAN
        return _Span(self, name, tid, args)
