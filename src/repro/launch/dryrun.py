import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh)
combination with ShapeDtypeStruct inputs (zero allocation), print
memory/cost analysis, extract the roofline terms and the collective
schedule, and verify the decentralized mode's zero-cross-pod property.

The three lines above MUST stay the first statements in this module: jax
locks the platform and the device count at first init, and only the
dry-run may see 512 placeholder devices (smoke tests and benches see the
1 real CPU device). Pinning the CPU keeps the dry-run off an attached
accelerator: it would otherwise take the chip, and its 256/512-device
meshes cannot be built there.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3_8b \
        --shape train_4k --mesh multi --mode dense
    PYTHONPATH=src python -m repro.launch.dryrun --all  # full matrix
"""
import argparse
import json
import time
import traceback
from dataclasses import asdict, replace
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (ARCH_IDS, INPUT_SHAPES, InputShape,
                                ModelConfig, get_config)
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (RooflineReport, active_params,
                                   collective_summary, model_flops)
from repro.models import build_model
from repro.models.params import count_params, tree_shapes, tree_shardings
from repro.optim.adamw import AdamWConfig
from repro.sharding import rules as R
from repro.train.trainer import (TrainConfig, make_decentralized_train_step,
                                 make_train_step)

LONG_DECODE_WINDOW = 8192      # sliding window applied at long_500k


def _cost_dict(compiled) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` returns a dict on recent JAX but a
    one-element list of dicts on older releases — normalize to a dict."""
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost


def shape_cfg(cfg: ModelConfig, shape: InputShape) -> ModelConfig:
    """long_500k needs sub-quadratic attention: window the attention archs
    (xLSTM has none; whisper is skipped upstream)."""
    if shape.name == "long_500k" and cfg.family in ("dense", "moe", "vlm",
                                                    "hybrid"):
        return replace(cfg, sliding_window=LONG_DECODE_WINDOW)
    return cfg


def is_skipped(arch: str, shape: InputShape) -> Optional[str]:
    if arch == "whisper_small" and shape.name == "long_500k":
        return ("enc-dec with a 448-position decoder by construction; "
                "524k-token decode is out of family (DESIGN.md §Shape/skip)")
    return None


def batch_shapes(cfg: ModelConfig, shape: InputShape,
                 decentralized_k: int = 0) -> Dict[str, jax.ShapeDtypeStruct]:
    B = shape.global_batch
    if decentralized_k:
        B = B // decentralized_k
    lead = (decentralized_k,) if decentralized_k else ()
    S = shape.seq_len
    n_text = S - (cfg.n_patches if cfg.family == "vlm" else 0)
    sds = jax.ShapeDtypeStruct
    out = {"tokens": sds(lead + (B, n_text), jnp.int32),
           "labels": sds(lead + (B, n_text), jnp.int32)}
    if cfg.family == "vlm":
        out["patches"] = sds(lead + (B, cfg.n_patches, cfg.vision_dim),
                             jnp.bfloat16)
    if cfg.family == "audio":
        out["frames"] = sds(lead + (B, cfg.n_audio_frames, cfg.audio_dim),
                            jnp.bfloat16)
    return out


def state_struct(model, cfg: ModelConfig, decentralized_k: int = 0):
    """Abstract TrainState: bf16 params; f32 m/v/master; i32 count."""
    lead = (decentralized_k,) if decentralized_k else ()
    specs = model.param_specs()
    p = tree_shapes(specs, cfg.pdtype, extra_leading=lead)
    f = tree_shapes(specs, jnp.float32, extra_leading=lead)
    count = jax.ShapeDtypeStruct(lead, jnp.int32)
    return {"params": p,
            "opt": {"m": f, "v": f, "master": f, "count": count}}


def state_shardings(model, rules, mesh, decentralized_k: int = 0):
    lead = ("dexpert",) if decentralized_k else ()
    ps = tree_shardings(model.param_specs(), rules, mesh,
                        extra_leading_axes=lead)
    scalar = NamedSharding(
        mesh, P(rules["dexpert"]) if decentralized_k else P())
    return {"params": ps,
            "opt": {"m": ps, "v": ps, "master": ps, "count": scalar}}, scalar


def _if_divisible(mesh, axes, dim: int):
    """Return the mesh axes only when they evenly divide the dimension."""
    if axes is None:
        return None
    t = axes if isinstance(axes, tuple) else (axes,)
    ext = int(np.prod([mesh.shape[a] for a in t]))
    return axes if (dim % ext == 0 and dim >= ext) else None


def batch_shardings(rules, mesh, cfg, shapes: Dict, decentralized_k: int = 0):
    lead = (rules["dexpert"],) if decentralized_k else ()
    b = rules["act_batch"]
    out = {}
    for k, v in shapes.items():
        bdim = v.shape[len(lead)]
        trailing = [None] * (len(v.shape) - len(lead) - 1)
        out[k] = NamedSharding(mesh, P(*lead, _if_divisible(mesh, b, bdim),
                                       *trailing))
    return out


OVERRIDES: Dict[str, Any] = {}     # §Perf variants, set by --override
RULE_OVERRIDES: Dict[str, Any] = {}  # sharding-rule variants (--no-fsdp)


def apply_overrides(cfg: ModelConfig) -> ModelConfig:
    return replace(cfg, **OVERRIDES) if OVERRIDES else cfg


def probe_cfg(cfg: ModelConfig, G: int) -> ModelConfig:
    """Depth-G unrolled variant of the config (same widths). Used to fit
    f(G) = outside + G·per_group, correcting XLA cost analysis' once-per-
    while-body counting of scanned stacks."""
    over = {"unroll": True}          # keep the config's remat policy
    if cfg.family == "ssm":
        over["n_layers"] = cfg.ssm.slstm_every * G
    elif cfg.family == "hybrid":
        over["n_layers"] = cfg.ssm.shared_attn_every * G
    else:
        over["n_layers"] = G
    if cfg.family == "audio":
        over["n_enc_layers"] = G
    return replace(cfg, **over)


def build_case(arch: str, shape_name: str, mesh_name: str, mode: str,
               n_experts: int = 2, depth_probe: int = 0):
    """Returns (jitted_fn, example_args) ready to .lower()."""
    shape = INPUT_SHAPES[shape_name]
    multi_pod = mesh_name == "multi"
    decentralized = mode == "decentralized"
    K = n_experts if decentralized else 0
    cfg = apply_overrides(shape_cfg(get_config(arch), shape))
    if depth_probe:
        cfg = probe_cfg(cfg, depth_probe)
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    # §Perf H6: inference has no optimizer state — FSDP weight-sharding over
    # ``data`` only buys per-layer all-gathers (18.7× collective term on
    # qwen3-8b prefill). Serving rules therefore replicate weights over
    # ``data`` (tensor-parallel over ``model`` only); training keeps FSDP
    # (required for 405B-scale optimizer state).
    rule_kw = dict(RULE_OVERRIDES)
    rule_kw.setdefault("fsdp", shape.kind == "train")
    rules = R.logical_rules(multi_pod=multi_pod, decentralized=decentralized,
                            **rule_kw)

    opt = AdamWConfig()
    tc = TrainConfig(opt=opt)

    if shape.kind == "train":
        st_shapes = state_struct(model, cfg, K)
        st_shard, scalar_shard = state_shardings(model, rules, mesh, K)
        b_shapes = batch_shapes(cfg, shape, K)
        b_shard = batch_shardings(rules, mesh, cfg, b_shapes, K)
        fn = (make_decentralized_train_step(model, tc) if decentralized
              else make_train_step(model, tc))
        # metrics subtree: scalar (or per-expert) leaves — prefix sharding
        jfn = jax.jit(fn, in_shardings=(st_shard, b_shard),
                      out_shardings=(st_shard, scalar_shard))
        args = (st_shapes, b_shapes)

    elif shape.kind == "prefill":
        p_shapes = tree_shapes(model.param_specs(), cfg.pdtype)
        p_shard = tree_shardings(model.param_specs(), rules, mesh)
        b_shapes = batch_shapes(cfg, shape)
        b_shard = batch_shardings(rules, mesh, cfg, b_shapes)
        cache_sh = model.cache_shapes(shape.global_batch, shape.seq_len)
        cache_shard = R.cache_pspec_tree(cache_sh, rules, mesh)
        logits_shard = NamedSharding(
            mesh, P(_if_divisible(mesh, rules["act_batch"],
                                  shape.global_batch), None,
                    _if_divisible(mesh, "model", cfg.vocab)))
        fn = lambda p, b: model.prefill(p, b, shape.seq_len)
        jfn = jax.jit(fn, in_shardings=(p_shard, b_shard),
                      out_shardings=(logits_shard, cache_shard))
        args = (p_shapes, b_shapes)

    else:  # decode
        p_shapes = tree_shapes(model.param_specs(), cfg.pdtype)
        p_shard = tree_shardings(model.param_specs(), rules, mesh)
        B = shape.global_batch
        cache_sh = model.cache_shapes(B, shape.seq_len)
        cache_shard = R.cache_pspec_tree(cache_sh, rules, mesh)
        tok_shape = jax.ShapeDtypeStruct((B,), jnp.int32)
        pos_shape = jax.ShapeDtypeStruct((), jnp.int32)
        b_ax = _if_divisible(mesh, rules["act_batch"], B)
        tok_shard = NamedSharding(mesh, P(b_ax))
        pos_shard = NamedSharding(mesh, P())
        logits_shard = NamedSharding(
            mesh, P(b_ax, _if_divisible(mesh, "model", cfg.vocab)))
        fn = lambda p, c, t, pos: model.decode_step(p, c, t, pos)
        # donate the cache: the update is in-place (no fresh HBM allocation
        # + no copy of the untouched slots) — §Perf iteration 3
        jfn = jax.jit(fn, in_shardings=(p_shard, cache_shard, tok_shard,
                                        pos_shard),
                      out_shardings=(logits_shard, cache_shard),
                      donate_argnums=(1,))
        args = (p_shapes, cache_sh, tok_shape, pos_shape)

    return jfn, args, model, cfg, mesh, shape


def run_case(arch: str, shape_name: str, mesh_name: str, mode: str,
             n_experts: int = 2, save_hlo: Optional[str] = None) -> Dict:
    shape = INPUT_SHAPES[shape_name]
    skip = is_skipped(arch, shape)
    case_id = f"{arch}.{shape_name}.{mesh_name}.{mode}"
    if skip:
        return {"case": case_id, "status": "skipped", "reason": skip}

    t0 = time.time()
    jfn, args, model, cfg, mesh, shape = build_case(
        arch, shape_name, mesh_name, mode, n_experts)
    with mesh:
        lowered = jfn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = _cost_dict(compiled)
    hlo = compiled.as_text()
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    csum = collective_summary(hlo, pod_size=256)

    n_dev = int(np.prod(list(mesh.shape.values())))
    total_p = count_params(model.param_specs())
    act_p = active_params(cfg, total_p, model)
    K = n_experts if mode == "decentralized" else 0
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    # decentralized: each expert consumes batch/K → same total tokens; params
    # per device scale by K replicas of the model, but FLOPs per token match.
    mf = model_flops(cfg, act_p, tokens, shape.kind) / n_dev

    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    report = RooflineReport(
        arch=arch, shape=shape_name, mesh=mesh_name, mode=mode,
        flops_per_device=flops, bytes_per_device=bytes_acc,
        collective_bytes=float(csum["total_bytes"]),
        model_flops_per_device=mf).finalize()

    mem_info = {}
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            mem_info[k] = getattr(mem, k, None)

    rec = {
        "case": case_id, "status": "ok",
        "n_devices": n_dev,
        "params_total": total_p, "params_active": act_p,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem_info,
        "cost": {k: cost.get(k) for k in ("flops", "bytes accessed",
                                          "optimal_seconds")
                 if k in cost},
        "collectives": csum,
        "roofline": asdict(report),
    }
    return rec


def run_probe(arch: str, shape_name: str, mesh_name: str, mode: str,
              n_experts: int = 2) -> Dict:
    """Two unrolled shallow compiles (G=1, 2) → per-group + outside costs →
    depth-corrected roofline terms for the FULL config."""
    shape = INPUT_SHAPES[shape_name]
    skip = is_skipped(arch, shape)
    case_id = f"{arch}.{shape_name}.{mesh_name}.{mode}"
    if skip:
        return {"case": case_id, "status": "skipped", "reason": skip}
    meas = {}
    t0 = time.time()
    for G in (1, 2):
        jfn, args, model, cfg, mesh, _ = build_case(
            arch, shape_name, mesh_name, mode, n_experts, depth_probe=G)
        with mesh:
            compiled = jfn.lower(*args).compile()
        cost = _cost_dict(compiled)
        csum = collective_summary(compiled.as_text(), pod_size=256)
        meas[G] = {"flops": float(cost.get("flops", 0.0)),
                   "bytes": float(cost.get("bytes accessed", 0.0)),
                   "coll": float(csum["total_bytes"]),
                   "xpod": float(csum["cross_pod_bytes"])}

    full_model = build_model(shape_cfg(get_config(arch), shape))
    G_full = full_model.n_groups
    mesh_obj = make_production_mesh(multi_pod=mesh_name == "multi")
    n_dev = int(np.prod(list(mesh_obj.shape.values())))

    def fit(key):
        per = meas[2][key] - meas[1][key]
        outside = meas[1][key] - per
        return max(outside, 0.0) + G_full * max(per, 0.0)

    corr = {k: fit(k) for k in ("flops", "bytes", "coll", "xpod")}
    cfg = shape_cfg(get_config(arch), shape)
    total_p = count_params(full_model.param_specs())
    act_p = active_params(cfg, total_p, full_model)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mf = model_flops(cfg, act_p, tokens, shape.kind) / n_dev
    report = RooflineReport(
        arch=arch, shape=shape_name, mesh=mesh_name, mode=mode,
        flops_per_device=corr["flops"], bytes_per_device=corr["bytes"],
        collective_bytes=corr["coll"],
        model_flops_per_device=mf).finalize()
    return {"case": case_id, "status": "ok", "kind": "depth_probe",
            "n_devices": n_dev, "G_full": G_full,
            "measured": meas, "corrected": corr,
            "xpod_corrected": corr["xpod"],
            "wall_s": round(time.time() - t0, 1),
            "roofline": asdict(report)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--mode", choices=["dense", "decentralized"],
                    default="dense")
    ap.add_argument("--experts", type=int, default=2)
    ap.add_argument("--all", action="store_true",
                    help="full 10×4 matrix on the given mesh/mode")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--probe", action="store_true",
                    help="depth-corrected cost probes (2 unrolled shallow "
                         "compiles per case) instead of the full lowering")
    ap.add_argument("--override", default=None,
                    help="JSON dict of ModelConfig field overrides for "
                         "§Perf variants, e.g. '{\"remat\": \"dots\"}'")
    ap.add_argument("--tag", default="",
                    help="suffix for output filenames (perf variants)")
    ap.add_argument("--no-fsdp", action="store_true",
                    help="ZeRO-1 (replicated weights over data axis) "
                         "instead of ZeRO-3 weight sharding")
    args = ap.parse_args()
    if args.override:
        OVERRIDES.update(json.loads(args.override))
    if args.no_fsdp:
        RULE_OVERRIDES["fsdp"] = False

    os.makedirs(args.out, exist_ok=True)
    cases = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in INPUT_SHAPES:
                cases.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cases.append((args.arch, args.shape))

    failures = 0
    for arch, shape in cases:
        cid = f"{arch}.{shape}.{args.mesh}.{args.mode}"
        if args.tag:
            cid += f".{args.tag}"
        if args.probe:
            cid += ".probe"
        out_json = os.path.join(args.out, cid + ".json")
        if args.skip_existing and os.path.exists(out_json):
            try:
                with open(out_json) as f:
                    prev = json.load(f)
                if prev.get("status") in ("ok", "skipped"):
                    print(f"[cached ] {cid}", flush=True)
                    continue
            except Exception:
                pass
        hlo_path = (os.path.join(args.out, cid + ".hlo")
                    if args.save_hlo else None)
        try:
            if args.probe:
                rec = run_probe(arch, shape, args.mesh, args.mode,
                                args.experts)
            else:
                rec = run_case(arch, shape, args.mesh, args.mode,
                               args.experts, save_hlo=hlo_path)
        except Exception as e:
            failures += 1
            rec = {"case": cid, "status": "error",
                   "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
        with open(out_json, "w") as f:
            json.dump(rec, f, indent=1)
        status = rec["status"]
        extra = ""
        if status == "ok":
            r = rec["roofline"]
            extra = (f" compile={rec.get('compile_s', rec.get('wall_s'))}s"
                     f" bottleneck={r['bottleneck']}"
                     f" compute={r['compute_s']:.4f}s"
                     f" mem={r['memory_s']:.4f}s"
                     f" coll={r['collective_s']:.4f}s"
                     f" xpod={rec.get('collectives', {}).get('cross_pod_bytes', rec.get('xpod_corrected'))}")
        elif status == "error":
            extra = " " + rec["error"][:200]
        print(f"[{status:7s}] {cid}{extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} case(s) failed")


if __name__ == "__main__":
    main()
