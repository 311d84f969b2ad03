"""The reduction that puts a chip trace's idle and device time down to the
program's own phases and layers (``benchmarks/chip/program_trace.py``):
on a hand-built trace whose every number is known, and on a trace
recorded on a TPU v5e."""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path[:0] = [p for p in (os.path.join(ROOT, "src"), BENCH)
                if p not in sys.path]

import program_trace as pt  # noqa: E402
import trace_reduce  # noqa: E402


class Event:
    def __init__(self, name, start, end, stats=()):
        self.name, self.start_ns = name, start
        self.duration_ns, self.stats = end - start, list(stats)


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class Trace:
    def __init__(self, planes):
        self.planes = planes


MODULE = "jit_top1_fused_decode"
OPS = {                       # trace event text -> scope in the HLO
    "%while.1 = (s32[]) while(s32[] %p)": "layers",
    "%fusion.7 = bf16[65,8,128,128]{3,2,1,0} fusion(bf16[8] %a)":
        "attn.kv_write",
    "%copy.3 = bf16[65,8,128,128]{3,2,1,0} copy(bf16[8] %b)": None,
    "%sort.2 = (f32[8,92553]) sort(f32[8,92553] %c)": "sample",
    "%copy-start.1 = (s32[8]) copy-start(s32[8] %d)": None,
}


def hand_built():
    """One engine step [0, 100) ns: the program's phases tile it, an
    admission runs inside ``admit``; the device runs one step program
    over [35, 85): a layer loop (a pool write and an unscoped copy inside
    it), the sampler's sort, and an unscoped copy."""
    host = [Event("engine.step", 0, 100, [("step_index", 4)]),
            Event("step", 0, 100, [("pod", 0), ("kind", "decode")]),
            Event("admit", 0, 20), Event("admission", 5, 15),
            Event("$scheduler.py:700 _admit_one", 5, 15),
            Event("schedule", 20, 30), Event("dispatch", 30, 40),
            Event("prefill_chunk[2]", 31, 39),
            Event("device_get", 40, 80), Event("advance", 80, 95),
            Event("outputs", 95, 100)]
    text = list(OPS)
    ops = [Event(text[0], 35, 70), Event(text[1], 40, 60),
           Event(text[2], 60, 65), Event(text[3], 70, 80),
           Event(text[4], 80, 85)]
    device = Plane("/device:TPU:0", [
        Line("XLA Modules", [Event(f"{MODULE}(123)", 35, 85)]),
        Line("XLA Ops", ops)])
    return Trace([Plane("/host:CPU", [Line("python3", host)]), device])


SCOPES = {MODULE: [{pt.instr_key(t): s for t, s in OPS.items()
                    if s is not None}]}


def test_scope_of_reads_the_innermost_named_scope():
    assert pt.scope_of("jit(top1_fused_decode)/layers/while/body/"
                       "closed_call/attn.kernel/jit(paged_decode_attention)/"
                       "while/body/dynamic_slice") == "attn.kernel"
    assert pt.scope_of("jit(mixture_fused_decode)/vmap(layers)/while/body/"
                       "dynamic_update_slice") == "layers"
    assert pt.scope_of("jit(top1_fused_decode)/sample/sample/vmap()/"
                       "bsd,dhk->bshk/dot_general") == "sample"
    assert pt.scope_of("jit(_lambda)/while/body/add") is None


def test_instruction_keys_match_between_hlo_and_trace():
    hlo = ('  ROOT %fusion.187 = s32[8]{0:T(128)S(1)} fusion(%copy-done.9), '
           'kind=kLoop, calls=%fused_computation.278, metadata={op_name='
           '"jit(top1_fused_decode)/epilogue/add" source_file="x.py"}')
    event = ('%fusion.187 = s32[8]{0:T(128)S(1)} fusion(s32[8]{0:T(128)S(1)}'
             ' %copy-done.9), kind=kLoop, calls=%fused_computation.278')
    assert pt.instr_key(hlo) == pt.instr_key(event) == \
        "fusion.187 = s32[8]{0:T(128)S(1)}"
    assert pt.hlo_scopes(hlo) == {"fusion.187 = s32[8]{0:T(128)S(1)}":
                                  "epilogue"}
    assert pt.span_name("prefill_chunk[12]") == "prefill_chunk"
    assert pt.span_name("engine.step") is None
    assert pt.span_name("$scheduler.py:1 step") is None


def test_partition_cuts_at_every_span_edge():
    spans = [(0, 100, "step"), (0, 20, "admit"), (5, 15, "admission"),
             (20, 30, "schedule"), (95, 100, "outputs")]
    assert pt.partition(spans) == [
        (0, 5, ("step", "admit")), (5, 15, ("step", "admit", "admission")),
        (15, 20, ("step", "admit")), (20, 30, ("step", "schedule")),
        (30, 95, ("step",)), (95, 100, ("step", "outputs"))]


def test_hand_built_trace_reduces_exactly():
    red = pt.reduce(hand_built(), SCOPES)
    ns = 1e-9
    assert red["steps"] == 1 and red["window_s"] == pytest.approx(100 * ns)
    # idle [0, 35) and [85, 100), cut at every program span's edges
    assert red["idle_in_steps_s"] == pytest.approx(50 * ns)
    assert red["idle_by_span"] == pytest.approx({
        "admit": 10 * ns, "admission": 10 * ns, "schedule": 10 * ns,
        "dispatch": 1 * ns, "prefill_chunk": 4 * ns, "advance": 10 * ns,
        "outputs": 5 * ns})
    assert red["idle_by_phase"] == pytest.approx({
        "admit": 20 * ns, "schedule": 10 * ns, "dispatch": 5 * ns,
        "advance": 10 * ns, "outputs": 5 * ns})
    assert red["program_span_share"] == pytest.approx(100.0)
    # device self time: the loop's own 10 ns, the pool write 20, the copy
    # inside the loop 5 (takes the loop's scope), the sort 10, and the
    # copy outside anything 5
    assert red["device_step_programs_s"] == pytest.approx(50 * ns)
    assert red["device_by_scope"] == pytest.approx({
        "layers": 15 * ns, "attn.kv_write": 20 * ns, "sample": 10 * ns,
        pt.NO_SCOPE: 5 * ns})
    assert red["scope_share"] == pytest.approx(90.0)
    ms = 1e3 * ns
    assert red["metrics"] == pytest.approx({
        "sched.host_idle_ms_per_step": 50 * ms,
        "step.kv_pool_ms_per_step": 35 * ms,
        "step.sampler_ms_per_step": 10 * ms})
    # the harness's own reduction of the same trace is untouched by it
    old = trace_reduce.reduce(hand_built())
    assert old["busy_s"] == pytest.approx(50 * ns)
    assert dict(old["idle"]) == pytest.approx({"engine.step": 50 * ns})


def test_a_trace_without_program_spans_or_scopes_reads_nothing():
    tr = hand_built()
    host = tr.planes[0].lines[0]
    host.events = [e for e in host.events if e.name == "engine.step"]
    red = pt.reduce(tr, None)
    assert red["metrics"] == {"sched.host_idle_ms_per_step": None,
                              "step.kv_pool_ms_per_step": None,
                              "step.sampler_ms_per_step": None}
    assert red["idle_by_phase"] == pytest.approx({pt.NO_SPAN: 50e-9})
    assert red["program_span_share"] is None and red["scope_share"] is None


RECORDED = os.path.join(BENCH, "testdata", "top1-vqa.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_a_chip_trace_of_a_program_without_spans_reads_no_metric():
    """The trace recorded on a TPU v5e before the program had spans,
    named programs or scopes: every idle piece is unattributed, the
    device time is put down to the programs by name, and no metric
    reads; the window and idle time agree with ``trace_reduce``'s."""
    pd = trace_reduce.load(RECORDED)
    red = pt.reduce(pd, None)
    old = trace_reduce.reduce(pd)
    assert red["window_s"] == pytest.approx(old["window_s"], rel=1e-12)
    assert red["idle_in_steps_s"] == pytest.approx(
        dict(old["idle"])["engine.step"], rel=1e-9)
    assert red["idle_by_phase"] == pytest.approx(
        {pt.NO_SPAN: red["idle_in_steps_s"]})
    assert set(red["device_by_program"]) == {"jit_step_chunk",
                                             "jit__lambda"}
    assert sum(red["device_by_program"].values()) == pytest.approx(
        old["busy_s"], rel=1e-9)
    assert red["device_step_programs_s"] == 0.0
    assert set(red["metrics"].values()) == {None}


CAPTION = os.path.join(BENCH, "testdata", "top1-caption")


@pytest.mark.skipif(not os.path.exists(CAPTION + ".xplane.pb"),
                    reason="no recorded trace")
def test_a_recorded_chip_trace_reduces_to_its_expected_numbers():
    """A stretch of ``top1-caption`` traced on a TPU v5e with the program's
    spans, step-program names and scopes, and the scopes read back from
    that run's compile cache: the reduction reproduces every number kept
    beside it, the program's spans and scopes cover nearly all of the idle
    and device time, and the step programs go by their own names."""
    with open(CAPTION + ".expect.json") as f:
        want = json.load(f)
    with open(CAPTION + ".scopes.json") as f:
        scopes = json.load(f)
    red = pt.reduce(trace_reduce.load(CAPTION + ".xplane.pb"), scopes)
    want.pop("recorded")
    assert set(red) == set(want)
    for key, value in want.items():
        assert red[key] == pytest.approx(value, rel=1e-9), key
    assert red["program_span_share"] >= 90 and red["scope_share"] >= 90
    assert all(name.startswith("jit_top1_")
               for name in red["device_by_program"])
    assert all(v is not None for v in red["metrics"].values())
