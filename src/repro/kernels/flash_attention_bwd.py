"""Blocked flash-attention BACKWARD kernels (Pallas TPU).

Standard two-pass formulation from the saved row log-sum-exp:

    p   = exp(q·kᵀ·scale − lse)            (recomputed blockwise, never HBM)
    dv  = pᵀ · do
    ds  = p ⊙ (do·vᵀ − Δ),  Δ = rowsum(do ⊙ o)
    dk  = dsᵀ · q · scale
    dq  = ds · k · scale

Two kernels: ``_dq_kernel`` (grid B×H×nq, accumulating over kv blocks on the
minor axis) and ``_dkv_kernel`` (grid B×H×nk, accumulating over q blocks).
Both produce per-*query*-head dk/dv; the GQA reduction over the group
(H → KV heads) is a cheap jnp sum outside. VMEM working set per step:
4–5 tiles of (block, dh) + one (block_q, block_k) score tile — ≈3 MB at
128×128×128 f32, comfortably under the ~16 MB budget. Like the forward
kernel, both run on head-major operands (B, H, S, dh) with per-row
statistics as (B, H, S, 1), so every block's last two dims are
(rows, dh) or (rows, 1).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import heads_major

Array = jnp.ndarray
NEG_INF = -1e30


def _mask(rows, cols, causal: bool, window: int):
    if not causal:
        return None
    m = rows >= cols
    if window > 0:
        m &= (rows - cols) < window
    return m


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc, *, scale: float, block_q: int, block_k: int,
               causal: bool, window: int):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]                                 # (bq, 1) f32
    delta = delta_ref[0, 0]                             # (bq, 1) f32

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    m = _mask(rows, cols, causal, window)
    if m is not None:
        s = jnp.where(m, s, NEG_INF)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    acc[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _done():
        dq_ref[0, 0] = (acc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                block_q: int, block_k: int, causal: bool, window: int):
    ki, qi = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]                                 # (bq, 1) f32
    delta = delta_ref[0, 0]                             # (bq, 1) f32

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    m = _mask(rows, cols, causal, window)
    if m is not None:
        s = jnp.where(m, s, NEG_INF)
    p = jnp.exp(s - lse)                                # (bq, bk)
    dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _done():
        dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q: Array, k: Array, v: Array, out: Array,
                        lse: Array, do: Array, *, causal: bool = True,
                        window: int = 0, block_q: int = 128,
                        block_k: int = 128, interpret: bool = False
                        ) -> Tuple[Array, Array, Array]:
    """q,do,out: (B,S,H,dh); k,v: (B,S,KV,dh); lse: (B,S,H) →
    (dq (B,S,H,dh), dk (B,S,KV,dh), dv (B,S,KV,dh))."""
    B, S, H, dh = q.shape
    KV = k.shape[2]
    group = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    nq, nk = S // block_q, S // block_k
    scale = 1.0 / (dh ** 0.5)
    delta = jnp.einsum("bshd,bshd->bhs", do.astype(jnp.float32),
                       out.astype(jnp.float32))[..., None]   # Δ (B,H,S,1)
    lse = jnp.transpose(lse, (0, 2, 1))[..., None]           # (B,H,S,1)
    q, k, v, do = (heads_major(a) for a in (q, k, v, do))

    q_spec = pl.BlockSpec((1, 1, block_q, dh),
                          lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, dh),
                           lambda b, h, i, j, g=group: (b, h // g, j, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda b, h, i, j: (b, h, i, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal, window=window),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dkv: iterate q blocks on the minor axis for a fixed k/v block
    q_spec2 = pl.BlockSpec((1, 1, block_q, dh),
                           lambda b, h, j, i: (b, h, i, 0))
    kv_spec2 = pl.BlockSpec((1, 1, block_k, dh),
                            lambda b, h, j, i, g=group: (b, h // g, j, 0))
    row_spec2 = pl.BlockSpec((1, 1, block_q, 1),
                             lambda b, h, j, i: (b, h, i, 0))
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal, window=window),
        grid=(B, H, nk, nq),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2,
                  row_spec2],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, dh), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, dh), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, dh), k.dtype),
            jax.ShapeDtypeStruct((B, H, S, dh), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, dh), jnp.float32),
                        pltpu.VMEM((block_k, dh), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # GQA: reduce per-query-head dk/dv over each KV head's group
    dk = dk_h.reshape(B, KV, group, S, dh).sum(2)
    dv = dv_h.reshape(B, KV, group, S, dh).sum(2)
    return heads_major(dq), heads_major(dk), heads_major(dv)
