"""What the per-layer readers share: the window's steps and requests, the
work each traced step did, and the program's counters over the window.

A reader (``layer_metrics/<name>.py``) gets one ``ctx`` dict:

* ``rec``: the run's record — ``t0``/``t1`` (the window, harness clock),
  ``reqs`` (rid -> due, width, features, tokens with their step indices
  and times, ``t_admit``), ``steps`` ((begin, end, tokens) per engine
  step), ``metrics0``/``metrics1`` (the program's registries at the
  window's ends), ``spans`` (the program's own spans, traced runs only),
  ``compiles_in_window``;
* ``trace``: ``trace_reduce.reduce`` of the profiled stretch, or None;
* ``config``, ``arch`` (the architecture's module), ``K``, ``strategy``,
  ``device_kind``, ``centroids``.
"""
from __future__ import annotations

import re

import numpy as np

import flops
import reference


def counter_by_pod(snap: dict, name: str, field: str = "value") -> dict:
    out = {}
    for s in snap["metrics"]:
        if s["name"] == name:
            pod = s["labels"].get("pod", "0")
            out[pod] = out.get(pod, 0.0) + float(s[field])
    return out


def delta_by_pod(ctx, name: str, field: str = "value") -> dict:
    a = counter_by_pod(ctx["rec"]["metrics0"], name, field)
    b = counter_by_pod(ctx["rec"]["metrics1"], name, field)
    return {p: b[p] - a.get(p, 0.0) for p in b}


def window_steps(ctx) -> int:
    rec = ctx["rec"]
    return sum(1 for a, b, _ in rec["steps"] if a >= rec["t0"] and
               b <= rec["t1"])


def due_in_window(ctx):
    rec = ctx["rec"]
    return [q for q in rec["reqs"].values()
            if rec["t0"] <= q["due"] < rec["t1"]]


def pod_of(ctx, q) -> int:
    """The expert a request is served by (top-1), by the reference's own
    Eq. 28 routing; the mixture serves every request on one core."""
    if ctx["strategy"] != "top1":
        return 0
    dep = ctx["config"]["deployment"]["router"]
    w = reference.route(np.asarray(q["features"], float)[None],
                        ctx["centroids"], dep["temperature"], dep["top_k"],
                        "top1")
    return int(np.argmax(w[0]))


CHUNK_SPAN = re.compile(r"prefill_chunk\[\d+\]")


def traced_work(ctx):
    """The work of the traced steps: ``decode`` {(step, pod): [positions
    fed]} and ``chunks`` [(step, pod, start, length, final)], from the
    harness's token stamps and the program's ``prefill_chunk`` spans."""
    red = ctx["trace"]
    if red is None:
        return None
    rec = ctx["rec"]
    C = ctx["config"]["engine"]["chunk"]
    steps = set(red["steps"])
    decode, chunks = {}, []
    by_rid = rec["reqs"]
    for q in by_rid.values():
        pod = None
        for k, s in enumerate(q["s"]):
            if k >= 1 and s in steps:
                pod = pod_of(ctx, q) if pod is None else pod
                decode.setdefault((s, pod), []).append(q["width"] + k - 1)
    bounds = {i: rec["steps"][i][:2] for i in steps}
    for ev in (rec["spans"] or {}).get("traceEvents", []):
        if ev.get("ph") != "X" or not CHUNK_SPAN.fullmatch(ev["name"]):
            continue
        t = ev["ts"] * 1e-6
        step = next((i for i, (a, b) in bounds.items() if a <= t <= b), None)
        q = by_rid.get(ev["args"]["rid"])
        if step is None or q is None:
            continue
        start = int(ev["args"]["start"])
        length = min(C, q["width"] - start)
        chunks.append((step, pod_of(ctx, q), start, length,
                       start + length >= q["width"]))
    return {"decode": decode, "chunks": chunks}


def expert_factor(ctx) -> int:
    """How many experts compute each token: K in the mixture, 1 top-1."""
    return ctx["K"] if ctx["strategy"] == "mixture" else 1


def step_flops(ctx) -> float | None:
    """Model operations of the traced steps' tokens, once per expert that
    computes them."""
    work = traced_work(ctx)
    if work is None:
        return None
    m = ctx["config"]["model"]
    arch = ctx["arch"]
    total = sum(arch.decode_flops(m, p)
                for ps in work["decode"].values() for p in ps)
    total += sum(arch.chunk_flops(m, start, n, final)
                 for _, _, start, n, final in work["chunks"])
    return float(total * expert_factor(ctx))


def mfu(ctx) -> float | None:
    red, total = ctx["trace"], step_flops(ctx)
    if red is None or total is None or red["window_s"] <= 0:
        return None
    peak = flops.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * total / (red["window_s"] * peak)


def kernel_roofline(ctx, kernel: str, calls) -> float | None:
    """Percent of the roofline: the least time the chip needs for the
    kernel's traced calls over the device time they took. ``calls`` is a
    list of (flops, bytes) per call."""
    red = ctx["trace"]
    if red is None:
        return None
    k = red["kernels"].get(kernel)
    if not k or k["calls"] == 0 or k["seconds"] <= 0 or not calls:
        return None
    peak = flops.peaks(ctx["device_kind"])
    need = sum(flops.roofline_seconds(f, b, peak) for f, b in calls)
    return 100.0 * need / k["seconds"]
