"""Flash attention for TPU (Pallas): blocked online-softmax over KV blocks,
GQA-aware, causal/sliding-window masking.

Tiling: grid = (batch, heads, q_blocks, kv_blocks); the kv axis is the
minor-most (sequential on TPU), carrying the online-softmax state
(m, l, acc) in VMEM scratch. Query/key blocks are MXU-aligned (128) when
the sequence allows. GQA: the key/value BlockSpec index map folds each
query head onto its KV head (h // group) — no materialized repeat.

The kernel runs on head-major operands, (B, H, S, dh) and (B, KV, S, dh):
a block's last two dims are then (rows, dh), which the TPU tiling accepts
(a head axis among the last two would give a block of 1 against H). The
wrapper transposes the (B, S, H, dh) model layout in and out; the row
log-sum-exp is kept as (B, H, S, 1) inside for the same reason.

VMEM working set per step: q(bq·dh) + k,v(bk·dh) + acc(bq·dh) + scores
(bq·bk), all f32 in scratch — ≤ ~2.5 MB at bq=bk=256, dh=128, far under
the ~16 MB/core budget, leaving room for double-buffered pipelines.
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jnp.ndarray
NEG_INF = -1e30


def heads_major(x: Array) -> Array:
    """(B, S, H, dh) ↔ (B, H, S, dh): the swap is its own inverse."""
    return jnp.swapaxes(x, 1, 2)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                  acc_scr, *, scale: float, block_q: int, block_k: int,
                  causal: bool, window: int):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                # (bq, dh)
    k = k_ref[0, 0].astype(jnp.float32)                # (bk, dh)
    v = v_ref[0, 0].astype(jnp.float32)                # (bk, dv)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    if causal:
        mask = rows >= cols
        if window > 0:
            mask &= (rows - cols) < window
        s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                             # (bq, bk)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _done():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)
        # log-sum-exp per query row — the residual the backward pass needs
        lse_ref[0, 0] = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: int = 0, block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> Array:
    out, _ = flash_attention_with_lse(q, k, v, causal=causal, window=window,
                                      block_q=block_q, block_k=block_k,
                                      interpret=interpret)
    return out


def flash_attention_with_lse(q: Array, k: Array, v: Array, *,
                             causal: bool = True, window: int = 0,
                             block_q: int = 128, block_k: int = 128,
                             interpret: bool = False):
    """q: (B,S,H,dh); k,v: (B,S,KV,dh), H % KV == 0 →
    (out (B,S,H,dh), lse (B,S,H) f32)."""
    B, S, H, dh = q.shape
    KV = k.shape[2]
    assert H % KV == 0
    group = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    nq, nk = S // block_q, S // block_k
    scale = 1.0 / (dh ** 0.5)

    kernel = functools.partial(_flash_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, causal=causal, window=window)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, dh), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=interpret,
    )(heads_major(q), heads_major(k), heads_major(v))
    return heads_major(out), jnp.transpose(lse[..., 0], (0, 2, 1))
