"""Roofline analysis from compiled dry-run artifacts (no real TPU needed).

Three terms per (arch × shape × mesh), in seconds:

    compute    = HLO_FLOPs_per_device / peak_FLOP/s
    memory     = HLO_bytes_per_device / HBM_bw
    collective = collective_bytes_per_device / link_bw

FLOPs/bytes come from ``compiled.cost_analysis()`` (the compiled module is
the per-device SPMD program). Collective bytes are NOT in cost_analysis:
we parse the optimized HLO (``compiled.as_text()``) and sum the result-shape
bytes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute. Replica groups are parsed too (both explicit and iota
form) so we can verify the paper's zero-cross-pod-communication property of
decentralized training directly from the compiled module.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

# TPU v5e published peaks per chip (Google Cloud documentation, "TPU v5e")
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_LINE_RE = re.compile(
    r"=\s+(?P<shapes>[^=]*?)\s+(?P<op>all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)(?P<start>-start)?\(")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{(.*?)\}\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")


def _shape_bytes(shapes_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shapes_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _parse_groups(line: str) -> Optional[List[List[int]]]:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        g, n = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            perm = [int(p) for p in m.group(4).split(",")]
            if len(perm) == ids.ndim:       # ignore malformed perms
                ids = ids.transpose(perm)
        return ids.reshape(g, n).tolist()
    m = _GROUPS_LIST_RE.search(line)
    if m:
        inner = m.group(1)
        groups = []
        for grp in re.findall(r"\{([\d,\s]*)\}", "{" + inner + "}}"):
            ids = [int(x) for x in grp.replace(" ", "").split(",") if x]
            if ids:
                groups.append(ids)
        return groups or None
    return None


@dataclass
class CollectiveOp:
    op: str
    bytes: int
    groups: Optional[List[List[int]]]
    crosses_pod: Optional[bool]


def parse_collectives(hlo_text: str, *, pod_size: int = 256
                      ) -> List[CollectiveOp]:
    out = []
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if not m:
            continue
        b = _shape_bytes(m.group("shapes"))
        groups = _parse_groups(line)
        crosses = None
        if groups is not None:
            crosses = any(len({d // pod_size for d in g}) > 1 for g in groups)
        out.append(CollectiveOp(op=m.group("op"), bytes=b, groups=groups,
                                crosses_pod=crosses))
    return out


def collective_summary(hlo_text: str, *, pod_size: int = 256) -> Dict:
    ops = parse_collectives(hlo_text, pod_size=pod_size)
    per_op: Dict[str, int] = {}
    cross_bytes = 0
    for c in ops:
        per_op[c.op] = per_op.get(c.op, 0) + c.bytes
        if c.crosses_pod:
            cross_bytes += c.bytes
    return {
        "n_collectives": len(ops),
        "bytes_per_op": per_op,
        "total_bytes": sum(per_op.values()),
        "cross_pod_bytes": cross_bytes,
        "cross_pod_ops": sum(1 for c in ops if c.crosses_pod),
    }


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    mode: str
    flops_per_device: float
    bytes_per_device: float
    collective_bytes: float
    model_flops_per_device: float
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    bottleneck: str = ""
    useful_flops_ratio: float = 0.0

    def finalize(self) -> "RooflineReport":
        self.compute_s = self.flops_per_device / PEAK_FLOPS
        self.memory_s = self.bytes_per_device / HBM_BW
        self.collective_s = self.collective_bytes / ICI_BW
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        self.bottleneck = max(terms, key=terms.get)
        self.useful_flops_ratio = (
            self.model_flops_per_device / self.flops_per_device
            if self.flops_per_device else 0.0)
        return self


def model_flops(cfg, n_params_active: int, tokens: int, kind: str) -> float:
    """MODEL_FLOPS = 6·N·D (train) or 2·N·D (forward-only), N = active
    params (MoE: routed fraction + shared), D = tokens processed."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_params_active * tokens


def active_params(cfg, total_params: int, model) -> int:
    """MoE: count routed experts at top_k/n_experts utilization."""
    if cfg.moe.n_experts == 0:
        return total_params
    specs = model.param_specs()
    expert_leaves = 0
    for path, leaf in _iter_specs(specs["blocks"]):
        if "moe" in path and path.split("/")[-1] in ("w_gate", "w_up",
                                                     "w_down"):
            expert_leaves += int(np.prod(leaf.shape))
    dense_part = total_params - expert_leaves
    return int(dense_part +
               expert_leaves * cfg.moe.top_k / cfg.moe.n_experts)


def _iter_specs(tree, prefix=""):
    from repro.models.params import is_spec
    if is_spec(tree):
        yield prefix, tree
        return
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _iter_specs(v, f"{prefix}/{k}")
    elif isinstance(tree, (tuple, list)):
        for i, v in enumerate(tree):
            yield from _iter_specs(v, f"{prefix}/{i}")
