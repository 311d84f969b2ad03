"""Operations and bytes the work needs, computed from sizes and positions:
attention and its kernels' calls here, each architecture's operations
per token in ``models/<architecture>.py``.

Counts are of what the algorithm requires, never of what a kernel or the
compiler happens to do: padded rows, inactive slots and dead blocks add
nothing. A multiply-add is 2 operations. The peaks live in
``peaks.json``, keyed by ``device_kind``; a device that is not there is an
error, not a default.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str, path: str = os.path.join(HERE, "peaks.json")):
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {os.path.basename(path)}")
    return table["devices"][device_kind]


def attn_flops(m: dict, keys: int) -> int:
    """One query against ``keys`` positions in one layer (QK^T and PV)."""
    return 4 * m["num_attention_heads"] * m["head_dim"] * keys


def kv_bytes_per_position(m: dict, itemsize: int = 2) -> int:
    """K and V of one position in one layer."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * itemsize


def paged_decode_call(m: dict, positions, itemsize: int = 2):
    """(flops, bytes) of one paged-decode attention call (one layer, one
    lockstep step) over the active slots' ``positions``: each query reads
    K and V of positions 0..pos and its own q, and writes its output."""
    qo = 2 * m["num_attention_heads"] * m["head_dim"] * itemsize
    flops = sum(attn_flops(m, p + 1) for p in positions)
    nbytes = sum((p + 1) * kv_bytes_per_position(m, itemsize) + qo
                 for p in positions)
    return flops, nbytes


def chunk_prefill_call(m: dict, start: int, length: int, itemsize: int = 2):
    """(flops, bytes) of one chunked-prefill attention call (one layer):
    ``length`` valid queries at positions start..start+length-1, each
    attending causally, reading K and V of positions 0..start+length-1."""
    H, dh = m["num_attention_heads"], m["head_dim"]
    keys = length * start + length * (length + 1) // 2
    flops = 4 * H * dh * keys
    nbytes = ((start + length) * kv_bytes_per_position(m, itemsize)
              + 2 * length * H * dh * itemsize)
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
