"""Tests of the chip benchmark (``benchmarks/chip/``) that run on the CPU.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 -m pytest -q tests/bench_chip

The harness runs in its rehearsal mode (tiny sizes, kernels interpreted):
the result line's keys, determinism of the traffic, discovery of files by
name, refusal off a TPU or outside a checkout, and faults planted under
the timed path that the comparison must catch. The trace reduction is
checked on a trace recorded on a TPU v5e, the FLOP arithmetic against the
program's own parameter count, and the float8 control through the
comparison that decides ``correct`` at a size the CPU holds.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path[:0] = [p for p in (os.path.join(ROOT, "src"), BENCH)
                if p not in sys.path]

import flops  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402
from by_name import load_module  # noqa: E402

ARCH = load_module("models", "internvl2")

RUN = os.path.join(BENCH, "run.py")
CELLS = ("top1-vqa", "mixture-caption", "top1-caption")
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def spec(workload, seed=20260001, seconds=3.0, trace=0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace, rehearsal=True, keep_trace=None,
                              trace_seconds=run.TRACE_SECONDS)


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def subprocess_run(args, cwd=ROOT, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run([sys.executable] + args, cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=600)


# --- the result line ---------------------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_result_line_has_the_contract_keys(cell):
    p = subprocess_run([RUN, "--workload", cell, "--seed", "3000000019",
                        "--seconds", "3", "--trace", "0", "--rehearsal"])
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line) == KEYS
    assert line["correct"] is True
    e2e = {m["name"] for m in bench()["end_to_end"]
           if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == e2e
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert all(set(c) == {"value", "limit", "rule"}
               for c in line["checks"].values())
    assert p.stderr.strip().splitlines()[-1].startswith("check ")


def test_refuses_a_platform_other_than_a_tpu():
    p = subprocess_run([RUN, "--workload", "top1-vqa", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs platform 'tpu'" in p.stderr


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("_jax_cache", "__pycache__"))
    p = subprocess_run([str(tmp_path / "benchmarks" / "chip" / "run.py"),
                        "--workload", "top1-vqa", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# --- traffic -----------------------------------------------------------------

def make_traffic(name, seed, clients=8, horizon=20.0):
    mix = traffic.load(name)
    gen = load_module("generators", mix["generator"]).Generator(
        mix, clients, horizon, seed)
    return mix, gen, traffic.Mix(mix, seed, gen.n, 2, 16, 512, 4, 8,
                                 gen.order_seed)


@pytest.mark.parametrize("name", ["vqa", "caption"])
def test_one_seed_gives_the_same_traffic_twice(name):
    seed = 2 ** 31 + 12345
    runs = []
    for _ in range(2):
        _, gen, mix = make_traffic(name, seed)
        due = gen.pop_due(1e9)
        reqs = [mix.request(i, e) for i, _, _, e in due]
        content = [mix.content(r) for r in reqs[:5]]
        runs.append((due, reqs, content))
    (d1, r1, c1), (d2, r2, c2) = runs
    assert d1 == d2 and r1 == r2
    for a, b in zip(c1, c2):
        assert all(np.array_equal(x, y) for x, y in zip(a, b))


@pytest.mark.parametrize("name", ["vqa", "caption"])
def test_seeds_differ_in_content_not_in_work(name):
    _, ga, a = make_traffic(name, 1)
    _, gb, b = make_traffic(name, 2)
    assert np.array_equal(np.sort(a.text), np.sort(b.text))
    assert np.array_equal(np.sort(a.new), np.sort(b.new))
    ra, rb = a.request(3), b.request(3)
    assert not np.array_equal(a.content(ra)[0], b.content(rb)[0])
    if name == "vqa":      # the open loop replays one sample path
        assert np.array_equal(a.text, b.text)
        assert ga.pop_due(1e9) == gb.pop_due(1e9)
    else:
        assert not np.array_equal(a.text, b.text)


def test_open_loop_rate_and_bounds():
    mix, gen, m = make_traffic("vqa", 7, horizon=100.0)
    due = [d for _, d, _, _ in gen.pop_due(1e9)]
    rate = mix["arrivals"]["rate_per_s"]
    assert abs(len(due) / due[-1] - rate) / rate < 0.05
    (lo, hi), (olo, ohi) = m.bounds()
    assert lo <= m.text.min() and m.text.max() <= hi
    assert olo <= m.new.min() and m.new.max() <= ohi
    dist = mix["output_tokens"]
    assert dist["dist"] == "categorical"
    share = [np.mean(m.new == v) for v in dist["values"]]
    want = np.asarray(dist["weights"]) / np.sum(dist["weights"])
    assert np.abs(np.asarray(share) - want).max() <= 1.0 / len(m.new)


def test_lognormal_lengths_keep_their_median():
    d = {"dist": "lognormal", "median": 218, "sigma": 0.4, "min": 64,
         "max": 512}
    q = traffic.quantiles(d, 201)
    assert q[100] == 218 and q.min() >= 64 and q.max() <= 512
    assert np.all(np.diff(q) >= 0)


def test_closed_loop_keeps_one_request_per_client():
    mix = traffic.load("caption")
    gen = load_module("generators", "closed").Generator(
        mix, 4, 20.0, 3, k=2)
    first = gen.pop_due(0.0)
    assert len(first) == 4 and gen.pop_due(10.0) == []
    assert all(share is not None for _, _, share, _ in first)
    assert [e for _, _, _, e in first] == [0, 1, 0, 1]
    gen.finished(first[1][0], 2.5)
    (idx, due, share, expert), = gen.pop_due(3.0)
    assert due == 2.5 and share is None and expert == 1


# --- discovery by name ---------------------------------------------------------

def test_files_dropped_into_a_copy_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("_jax_cache", "__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    b = bench()
    chip = root / "benchmarks" / "chip"
    cfg = json.loads((chip / "configs" / "internvl2_2b-k2-top1.json")
                     .read_text())
    cfg["name"] = "dropped-config"
    (chip / "configs" / "dropped-config.json").write_text(json.dumps(cfg))
    mix = json.loads((chip / "traffic" / "vqa.json").read_text())
    mix["arrivals"]["rate_per_s"] *= 2
    (chip / "traffic" / "dropped-mix.json").write_text(json.dumps(mix))
    (chip / "layer_metrics" / "dropped.metric.py").write_text(
        'LAYER = "test"\nSOURCE = "host_clock"\n\n\n'
        'def read(ctx):\n    return 42.0 + len(ctx["rec"]["reqs"]) * 0\n')
    (chip / "limits" / "dropped-cell.json").write_text(
        (chip / "limits" / "top1-vqa.json").read_text())
    b["configs"].append({"name": "dropped-config", "source": "x",
                         "file": "benchmarks/chip/configs/dropped-config.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "dropped-cell", "config": "dropped-config",
                           "traffic": "dropped-mix", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "dropped.metric", "unit": "1",
                           "better": "higher", "source": "host_clock",
                           "layer": "test", "moves": "itl_p95_ms",
                           "workloads": ["dropped-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    p = subprocess_run([str(chip / "run.py"), "--workload", "dropped-cell",
                        "--seed", "5", "--seconds", "3", "--trace", "1",
                        "--rehearsal"], cwd=root)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["metrics"]["dropped.metric"]["value"] == 42.0
    assert line["correct"] is True


def test_configuration_files_name_their_architecture():
    for c in bench()["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"]
        arch = load_module("models", cfg["architecture"])
        assert callable(arch.make_logprobs_fn) and arch.layout(cfg["model"])


def test_metric_files_match_the_benchmark():
    for m in bench()["per_layer"]:
        mod = load_module("layer_metrics", m["name"])
        assert mod.LAYER == m["layer"] and mod.SOURCE == m["source"]


# --- trace reduction, FLOPs, peaks ---------------------------------------------

TRACE = os.path.join(BENCH, "testdata", "top1-vqa.xplane.pb")
TRACE_EXPECT = os.path.join(BENCH, "testdata", "top1-vqa.expect.json")


@pytest.mark.skipif(not os.path.exists(TRACE), reason="no recorded trace")
def test_trace_reduction_on_a_recorded_chip_trace():
    with open(TRACE_EXPECT) as f:
        want = json.load(f)
    red = trace_reduce.reduce(trace_reduce.load(TRACE), want["kernels"])
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["steps"] == want["steps"]
    for k in ("window_s", "busy_s"):
        assert red[k] == pytest.approx(want[k], rel=1e-9)
    for k, v in want["kernel_seconds"].items():
        assert red["kernels"][k]["seconds"] == pytest.approx(v, rel=1e-9)
        assert red["kernels"][k]["calls"] > 0
    names = {n for n, _ in red["idle"]}
    assert names <= set(trace_reduce.HOST_NAMES) | {trace_reduce.NO_ANNOTATION}
    idle = sum(v for _, v in red["idle"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)


def test_flops_match_the_program_parameter_count():
    """Operations per decoded token, less attention, are twice the
    parameters a token passes through: everything but the embedding
    table, the norms and the projector, as the program lays them out."""
    import jax
    from repro.models import build_model
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "internvl2_2b-k2-top1.json")))
    m = cfg["model"]
    shapes = jax.eval_shape(build_model(ARCH.model_config(m, "x")).init,
                            jax.random.PRNGKey(0))
    count = {"/".join(str(getattr(k, "key", k)) for k in path): x.size
             for path, x in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    matmul = sum(v for k, v in count.items()
                 if k.startswith(("blocks/attn", "blocks/ffn", "embed/unembed")))
    per_token = ARCH.decode_flops(m, 0) - m["num_hidden_layers"] * \
        flops.attn_flops(m, 1)
    assert per_token == 2 * matmul
    proj = count["projector/w1"] + count["projector/w2"]
    assert ARCH.projector_flops(m) == 2 * proj
    import weights
    assert weights.n_params(ARCH.layout(m)) == sum(count.values())


def test_kernel_call_arithmetic():
    m = {"num_attention_heads": 16, "num_key_value_heads": 8, "head_dim": 128}
    f, b = flops.paged_decode_call(m, [0, 127])
    assert f == 4 * 16 * 128 * (1 + 128)
    assert b == (1 + 128) * 2 * 8 * 128 * 2 + 2 * (2 * 16 * 128 * 2)
    f, b = flops.chunk_prefill_call(m, 128, 128)
    assert f == 4 * 16 * 128 * sum(range(129, 257))
    assert b == 256 * 2 * 8 * 128 * 2 + 2 * 128 * 16 * 128 * 2


def test_an_unknown_device_kind_raises():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
