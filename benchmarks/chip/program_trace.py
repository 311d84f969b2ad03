#!/usr/bin/env python3
"""Put a chip trace's idle time and device time down to the program's own
phases and layers.

The program names what it does on both sides of the profiler's clock
(docs/observability.md):

* host: traced, each pod step is a ``step`` annotation tiled by its phases
  (``admit``, ``schedule``, ``dispatch``, ``device_get``, ``advance``,
  ``outputs``), with ``admission`` ⊃ ``prefix_match``, ``route``,
  ``preempt`` and ``prefill_chunk[i]`` nested where they run;
* device: each step program has a name of its own (``jit_top1_fused_decode``
  and so on) and its operations carry named scopes (``embed``, ``layers``,
  ``attn.qkv``, ...) in their HLO metadata.

A trace's operation events carry the instruction's text but not its
metadata, so the scopes come from the optimized HLO of the executables the
run compiled, read back from JAX's persistent compilation cache after the
run, on the machine with the chip (:func:`scope_map`).

:func:`reduce` gives, over the stretch ``trace_reduce.reduce`` measures (the
harness's ``engine.step`` annotations the trace holds whole), averaged over
the devices:

* ``idle_by_span``/``idle_by_phase``: every device-idle interval inside an
  ``engine.step``, cut exactly where program spans begin and end, each piece
  put down to the innermost program span open over it and to the step
  phase holding that, or to ``(no program span)``;
* ``device_by_scope``: the step programs' device self time by innermost
  named scope; an operation with no scope of its own (a copy XLA put in a
  layer loop) takes that of the operation it runs inside, else
  ``(no scope)``;
* ``metrics``: ``sched.host_idle_ms_per_step``, ``step.kv_pool_ms_per_step``
  and ``step.sampler_ms_per_step``, each per engine step traced (None where
  the trace holds no program span, or no scopes were given).

    python3 benchmarks/chip/program_trace.py scopes <compile cache dir> <out.json>
    python3 benchmarks/chip/program_trace.py reduce <trace.xplane.pb> [<scopes.json>]
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
from collections import defaultdict

import trace_reduce

# the phases that tile a pod step, and the host's own work among them
# (in ``device_get`` the host waits on the device)
PHASES = ("admit", "schedule", "dispatch", "device_get", "advance",
          "outputs")
HOST_PHASES = ("admit", "schedule", "dispatch", "advance", "outputs")
SPANS = frozenset(PHASES + ("step", "admission", "prefix_match", "route",
                            "preempt", "prefill_chunk"))
CHUNK_SPAN = re.compile(r"prefill_chunk\[\d+\]")
SCOPES = frozenset(("embed", "layers", "attn.qkv", "attn.kv_write",
                    "attn.kernel", "attn.out", "mlp", "lm_head", "mix",
                    "sample", "epilogue"))
# the layer loop's own slicing and writing back of the stacked cache (and
# weights), and each layer's write of the new token into the pool
KV_POOL = ("layers", "attn.kv_write")
STEP_PROGRAM = re.compile(
    r"jit_(top1|mixture)_(fused_decode|fused_decode_chunk|chunk_only|"
    r"fused_verify\w*)$")
NO_SPAN = "(no program span)"
NO_SCOPE = "(no scope)"
_INSTR = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) [a-z][\w\-]*\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE_PART = re.compile(r"(?:\w+\()*([\w.\-]+)\)*")


def span_name(name: str) -> str | None:
    """A host annotation's program span (``prefill_chunk[i]`` as
    ``prefill_chunk``), or None for anything else on the thread."""
    if CHUNK_SPAN.fullmatch(name):
        return "prefill_chunk"
    return name if name in SPANS else None


def scope_of(op_name: str) -> str | None:
    """The innermost named scope on an HLO ``op_name`` path; ``vmap(layers)``
    counts as ``layers``."""
    for part in reversed(op_name.split("/")):
        m = _SCOPE_PART.fullmatch(part)
        if m and m.group(1) in SCOPES:
            return m.group(1)
    return None


def instr_key(text: str) -> str | None:
    """``name = shape`` of an HLO instruction, from a line of HLO text or
    from a trace's operation event (which prints the operands otherwise)."""
    m = _INSTR.match(text)
    return f"{m.group(1)} = {m.group(2)}" if m else None


def hlo_scopes(text: str) -> dict:
    """{instruction key: scope} of every instruction of an HLO module's text
    whose metadata names one of :data:`SCOPES`."""
    out = {}
    for line in text.splitlines():
        m = _OP_NAME.search(line)
        if m is None:
            continue
        scope, key = scope_of(m.group(1)), instr_key(line)
        if scope is not None and key is not None:
            out[key] = scope
    return out


def scope_map(cache_dir: str, programs=STEP_PROGRAM) -> dict:
    """{module name: [{instruction key: scope}, one per compiled variant]}
    of the step programs in a JAX persistent compilation cache, read back
    by the backend that compiled them (run it on the machine with the
    chip, once the run has let the chip go). A trace names a module's
    variant by an id the executable does not expose, so a lookup tries
    every variant of the name."""
    import jax
    from jax._src import compilation_cache as cc
    devices = jax.devices()[:1]
    client = devices[0].client
    out = defaultdict(list)
    for path in sorted(glob.glob(os.path.join(cache_dir, "*-cache"))):
        name = os.path.basename(path).split("-", 1)[0]
        if not programs.match(name):
            continue
        with open(path, "rb") as f:
            blob, _ = cc.extract_executable_and_time(
                cc.decompress_executable(f.read()))
        exe = client.deserialize_executable(blob, devices)
        ops = {}
        for module in exe.hlo_modules():
            ops.update(hlo_scopes(module.to_string()))
        out[name].append(ops)
    return dict(out)


def _lookup(scopes: dict, module: str, key: str | None) -> str | None:
    """The scope of instruction ``key`` of the trace's module ``module``
    (``jit_name(id)``), found in whichever compiled variant of that name
    holds the key."""
    if key is None:
        return None
    for variant in scopes.get(module.split("(", 1)[0], ()):
        scope = variant.get(key)
        if scope is not None:
            return scope
    return None


def program_spans(pd):
    """[(start_ns, end_ns, span)] of the program's host annotations, an
    enclosing span before the spans it holds."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = span_name(e.name)
                if name is not None:
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                name))
    return sorted(out, key=lambda s: (s[0], -s[1]))


def partition(spans):
    """[(a, b, open spans outermost first)]: the stretches between the
    points where a program span begins or ends, where any is open. Spans
    of one thread nest; an inner one is clipped to its parent."""
    out, stack, t = [], [], None

    def to(x):
        nonlocal t
        if t is not None and x > t and stack:
            out.append((t, x, tuple(n for _, n in stack)))
        if t is None or x > t:
            t = x

    for a, b, name in spans:
        while stack and stack[-1][0] <= a:
            to(stack[-1][0])
            stack.pop()
        to(a)
        stack.append((min(b, stack[-1][0]) if stack else b, name))
    while stack:
        to(stack[-1][0])
        stack.pop()
    return out


def _intersect(xs, ys):
    """Pieces where two sorted lists of disjoint [a, b) intervals meet:
    [(a, b, index in xs, index in ys)]."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b, i, j))
        if xs[i][1] <= ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def _phase(stack) -> str:
    return next((n for n in stack if n in PHASES), stack[0])


def device_events(pd):
    """{device plane: ([(start, end, module)], [(start, end, op text)])}."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        mods, ops = [], []
        for line in plane.lines:
            into = mods if line.name == "XLA Modules" else \
                ops if line.name == "XLA Ops" else None
            if into is not None:
                into += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events]
        if ops:
            out[plane.name] = (sorted(mods),
                               sorted(ops, key=lambda o: (o[0], -o[1])))
    return out


def _module_at(mods, starts, t) -> str | None:
    i = bisect.bisect_right(starts, t) - 1
    return mods[i][2] if i >= 0 and t < mods[i][1] else None


def _scoped_self_times(ops, mods, scopes, lo, hi):
    """[(module, scope, self ns)] of the operations inside [lo, hi): each
    one's clipped duration less that of the operations nested in it, under
    its own scope or, lacking one, that of the operation holding it."""
    starts = [m[0] for m in mods]
    out, stack = [], []          # stack of [end, module, scope, self]
    for a, b, text in ops:
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        while stack and stack[-1][0] <= a:
            out.append(tuple(stack.pop()[1:]))
        module = _module_at(mods, starts, a) or "(no module)"
        scope = _lookup(scopes, module, instr_key(text))
        if scope is None:
            scope = stack[-1][2] if stack else NO_SCOPE
        if stack:
            stack[-1][3] -= b - a
        stack.append([b, module, scope, b - a])
    return out + [tuple(s[1:]) for s in stack]


def reduce(pd, scopes: dict | None = None) -> dict | None:
    """The program's phases and layers over the traced stretch; None when
    the trace holds no whole engine step or no device."""
    steps = [s for s in trace_reduce.host_spans(pd)
             if s[2] == trace_reduce.STEP]
    devs = device_events(pd)
    if not steps or not devs:
        return None
    lo, hi = steps[0][0], steps[-1][1]
    in_steps = [(a, b) for a, b, _, _ in steps]
    spans = program_spans(pd)
    cuts = partition(s for s in spans if s[1] > lo and s[0] < hi)
    by_span, by_phase = defaultdict(float), defaultdict(float)
    by_program, by_scope = defaultdict(float), defaultdict(float)
    n = len(devs)
    for mods, ops in devs.values():
        merged = trace_reduce._union([(a, b) for a, b, _ in ops], lo, hi)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        idle = [(a, b) for a, b, _, _ in _intersect(gaps, in_steps)]
        covered = 0
        for a, b, _, j in _intersect(idle, cuts):
            stack = cuts[j][2]
            by_span[stack[-1]] += (b - a) / n
            by_phase[_phase(stack)] += (b - a) / n
            covered += b - a
        by_phase[NO_SPAN] += (sum(b - a for a, b in idle) - covered) / n
        for module, scope, d in _scoped_self_times(ops, mods, scopes or {},
                                                   lo, hi):
            base = module.split("(", 1)[0]
            by_program[base] += d / n
            if STEP_PROGRAM.match(base):
                by_scope[scope] += d / n
    idle_s = sum(by_phase.values()) * 1e-9
    step_s = sum(by_scope.values()) * 1e-9
    per_step = 1e3 / len(steps)
    host = sum(by_phase[p] for p in HOST_PHASES) * 1e-9
    has_spans = bool(cuts)
    has_scopes = step_s > 0 and by_scope.get(NO_SCOPE, 0.0) * 1e-9 < step_s

    def secs(d):
        return {k: v * 1e-9 for k, v in sorted(d.items(), key=lambda kv:
                                                -kv[1]) if v > 0}
    return {
        "window_s": (hi - lo) * 1e-9,
        "steps": len(steps),
        "idle_in_steps_s": idle_s,
        "idle_by_phase": secs(by_phase),
        "idle_by_span": secs(by_span),
        "program_span_share": 100.0 * (1 - by_phase[NO_SPAN] * 1e-9 / idle_s)
        if idle_s > 0 and has_spans else None,
        "device_by_program": secs(by_program),
        "device_step_programs_s": step_s,
        "device_by_scope": secs(by_scope),
        "scope_share": 100.0 * (1 - by_scope.get(NO_SCOPE, 0.0) * 1e-9 /
                                step_s) if has_scopes else None,
        "metrics": {
            "sched.host_idle_ms_per_step": host * per_step
            if has_spans else None,
            "step.kv_pool_ms_per_step":
                sum(by_scope.get(s, 0.0) for s in KV_POOL) * 1e-9 * per_step
                if has_scopes else None,
            "step.sampler_ms_per_step":
                by_scope.get("sample", 0.0) * 1e-9 * per_step
                if has_scopes else None,
        },
    }


def main(argv) -> int:
    if len(argv) == 3 and argv[0] == "scopes":
        with open(argv[2], "w") as f:
            json.dump(scope_map(argv[1]), f)
        return 0
    if len(argv) in (2, 3) and argv[0] == "reduce":
        scopes = None
        if len(argv) == 3:
            with open(argv[2]) as f:
                scopes = json.load(f)
        print(json.dumps(reduce(trace_reduce.load(argv[1]), scopes),
                         indent=1))
        return 0
    print(__doc__.rsplit("\n\n", 1)[-1], file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
