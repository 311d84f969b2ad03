"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

* busy: the union of the intervals in which an operation ran on a device,
  inside the window, averaged over the devices; idle is the rest;
* per-kernel device time: the summed durations of the device operations
  whose own name (the HLO instruction's, without the instruction text
  after it) matches a kernel's pattern;
* time by operation: each operation's self time (less the operations
  nested inside it, as a layer loop holds its body), by name without the
  instance number;
* idle gaps by host annotation: each stretch with no device operation is
  put down to the host ``TraceAnnotation`` under way at its middle (the
  benchmark annotates its own calls: ``engine.step``,
  ``engine.add_request``, ``generator.wait``), or to ``(no annotation)``.

The window is that of the host ``engine.step`` annotations the trace holds
whole, so the steps the harness counts and the device time it divides are
the same ones.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

STEP = "engine.step"
HOST_NAMES = ("engine.step", "engine.add_request", "generator.wait")
NO_ANNOTATION = "(no annotation)"


def _stats(e) -> dict:
    return {k: v for k, v in e.stats}


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def op_name(event_name: str) -> str:
    """``%paged_decode_attention.9 = bf16[...] custom-call(...)`` →
    ``paged_decode_attention.9``: the instruction's own name, never the
    operands it reads."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def device_ops(pd):
    """{device plane name: [(start_ns, end_ns, op name)]} of the
    operations on each accelerator."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        ops = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                ops.append((e.start_ns, e.start_ns + e.duration_ns,
                            op_name(e.name)))
        if ops:
            out[plane.name] = sorted(ops)
    return out


def _self_times(ops, lo, hi):
    """[(name, self ns)] inside [lo, hi): each op's clipped duration less
    that of the ops nested in it."""
    out, stack = [], []           # stack of [end, name, self]
    for a, b, name in ops:
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        while stack and stack[-1][0] <= a:
            out.append(stack.pop()[1:])
        if stack:
            stack[-1][2] -= b - a
        stack.append([b, name, b - a])
    return out + [s[1:] for s in stack]


def host_spans(pd):
    """[(start_ns, end_ns, name, stats)] of the benchmark's annotations."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in HOST_NAMES:
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name, _stats(e)))
    return sorted(out)


def _union(intervals, lo, hi):
    """Merged [a, b) intervals clipped to [lo, hi)."""
    merged = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _label(name: str) -> str:
    """An operation's name without its instance number (``fusion.12`` →
    ``fusion``), so repeats of one operation add up."""
    return re.sub(r"[.\d]+$", "", name) or name


def reduce(pd, kernels: dict | None = None) -> dict:
    """The numbers of one trace. ``kernels`` maps a kernel's name to a
    regular expression searched in each device operation's own name.
    Returns None when the trace holds no whole step or no device."""
    steps = [s for s in host_spans(pd) if s[2] == STEP]
    devs = device_ops(pd)
    if not steps or not devs:
        return None
    lo, hi = steps[0][0], steps[-1][1]
    busy, by_op, gaps = [], defaultdict(float), []
    kern = {k: [0.0, 0] for k in (kernels or {})}
    pats = {k: re.compile(p) for k, p in (kernels or {}).items()}
    for ops in devs.values():
        merged = _union([(a, b) for a, b, _ in ops], lo, hi)
        busy.append(sum(b - a for a, b in merged))
        for name, d in _self_times(ops, lo, hi):
            by_op[_label(name)] += d / len(devs)
        for a, b, name in ops:
            d = min(b, hi) - max(a, lo)
            if d <= 0:
                continue
            for k, p in pats.items():
                if p.search(name):
                    kern[k][0] += d / len(devs)
                    kern[k][1] += 1
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    # the benchmark's annotations come from one thread, one after another
    spans = host_spans(pd)
    starts = [s[0] for s in spans]
    idle = defaultdict(float)
    for a, b in gaps:
        mid = (a + b) / 2
        i = bisect.bisect_right(starts, mid) - 1
        name = spans[i][2] if i >= 0 and mid < spans[i][1] \
            else NO_ANNOTATION
        idle[name] += (b - a) / len(devs)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "n_devices": len(devs),
        "steps": [int(s[3].get("step_index", -1)) for s in steps],
        "ops": sorted(((k, v * 1e-9) for k, v in by_op.items()),
                      key=lambda kv: -kv[1]),
        "kernels": {k: {"seconds": v[0] * 1e-9, "calls": v[1]}
                    for k, v in kern.items()},
        "idle": sorted(((k, v * 1e-9) for k, v in idle.items()),
                       key=lambda kv: -kv[1]),
    }


def breakdown(red: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took
    most time and the idle time by what the host was doing."""
    return {"device_ops": [[k, v] for k, v in red["ops"][:top]],
            "idle_gaps": [[k, v] for k, v in red["idle"][:top]]}
