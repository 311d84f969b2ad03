"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

These are *definitions*, not optimizations — O(S²) attention materializes
the full score matrix, etc. Kernel tests sweep shapes/dtypes and assert
against these.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

Array = jnp.ndarray
NEG_INF = -1e30


def flash_attention_ref(q: Array, k: Array, v: Array, *, causal: bool = True,
                        window: int = 0) -> Array:
    """q: (B,S,H,dh); k,v: (B,S,KV,dh) with H % KV == 0 → (B,S,H,dh)."""
    B, S, H, dh = q.shape
    KV = k.shape[2]
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(jnp.float32(dh))
    if causal:
        i = jnp.arange(S)[:, None]
        j = jnp.arange(S)[None, :]
        m = j <= i
        if window > 0:
            m &= (i - j) < window
        logits = jnp.where(m[None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def decode_attention_ref(q: Array, k: Array, v: Array, pos: Array, *,
                         window: int = 0) -> Array:
    """q: (B,H,dh); k,v: (B,S,KV,dh); pos: (B,) → (B,H,dh).

    window > 0 means the cache is a ring buffer of size S: every slot is
    valid once pos ≥ S, otherwise only slots ≤ pos.
    """
    B, H, dh = q.shape
    S, KV = k.shape[1], k.shape[2]
    kf = jnp.repeat(k, H // KV, axis=2)
    vf = jnp.repeat(v, H // KV, axis=2)
    logits = jnp.einsum("bhd,bkhd->bhk", q, kf).astype(jnp.float32)
    logits = logits / jnp.sqrt(jnp.float32(dh))
    idx = jnp.arange(S)[None, :]
    if window > 0:
        valid = (idx <= pos[:, None]) | (pos[:, None] >= S)
    else:
        valid = idx <= pos[:, None]
    logits = jnp.where(valid[:, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhk,bkhd->bhd", w, vf)


def gather_pages(pool: Array, table: Array) -> Array:
    """The logical KV span behind a block table. pool: (P,KV,block,dh),
    head-major so a kernel's K/V block is (block, dh) in its last two dims;
    table: (..., NB) → (..., NB·block, KV, dh), the contiguous layout."""
    g = jnp.swapaxes(pool[table], -3, -2)          # (..., NB, block, KV, dh)
    return g.reshape(*table.shape[:-1], -1, *g.shape[-2:])


def paged_decode_attention_ref(q: Array, k_pool: Array, v_pool: Array,
                               pos: Array, block_tables: Array, *,
                               window: int = 0) -> Array:
    """q: (B,H,dh); k_pool,v_pool: (P,KV,block,dh); pos: (B,);
    block_tables: (B,NB) → (B,H,dh).

    Definitionally: gather each slot's logical KV span out of the block
    pool, then run the contiguous decode oracle over it. The slot's logical
    cache size is NB·block; ``window > 0`` applies the ring validity rule
    over that span.
    """
    NB, block = block_tables.shape[1], k_pool.shape[2]
    k = gather_pages(k_pool, block_tables)
    v = gather_pages(v_pool, block_tables)
    return decode_attention_ref(q, k, v, pos,
                                window=NB * block if window > 0 else 0)


def chunk_prefill_attention_ref(q: Array, k_pool: Array, v_pool: Array,
                                start: Array, block_table: Array) -> Array:
    """q: (C,H,dh) chunk queries (row c at absolute position start + c);
    k_pool,v_pool: (P,KV,block,dh); block_table: (NB,) → (C,H,dh).

    Definitionally: gather the request's logical KV span out of the pool,
    then run the contiguous decode oracle treating the chunk rows as a
    batch of single queries at positions start..start+C-1.
    """
    C = q.shape[0]
    k = gather_pages(k_pool, block_table)
    v = gather_pages(v_pool, block_table)
    kb = jnp.broadcast_to(k[None], (C,) + k.shape)
    vb = jnp.broadcast_to(v[None], (C,) + v.shape)
    pos = start + jnp.arange(C)
    return decode_attention_ref(q, kb, vb, pos)


def router_scores_ref(x: Array, centroids: Array,
                      temperature: float) -> Array:
    """Fused Eq. 28: L2-normalize both → cosine sims → τ-softmax.
    x: (B, D); centroids: (K, D) → (B, K)."""
    xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
    cn = centroids / jnp.maximum(
        jnp.linalg.norm(centroids, axis=-1, keepdims=True), 1e-12)
    sims = xn @ cn.T
    return jax.nn.softmax(temperature * sims.astype(jnp.float32), axis=-1
                          ).astype(x.dtype)


def chunk_scan_ref(qc: Array, kc: Array, vc: Array,
                   cum: Array) -> Tuple[Array, Array]:
    """Intra-chunk linear attention + per-chunk KV summary.

    qc,kc: (B,NC,L,H,dk); vc: (B,NC,L,H,dv); cum: (B,NC,L,H) inclusive
    cumulative log-decay. Returns (intra (B,NC,L,H,dv) f32,
    chunk_kv (B,NC,H,dk,dv) f32).
    """
    L = qc.shape[2]
    qc, kc, vc = (a.astype(jnp.float32) for a in (qc, kc, vc))
    decay = cum[:, :, :, None, :] - cum[:, :, None, :, :]      # (B,NC,L,L,H)
    tri = jnp.tril(jnp.ones((L, L), bool))
    D = jnp.exp(jnp.where(tri[None, None, :, :, None], decay, -jnp.inf))
    scores = jnp.einsum("bclhd,bcmhd->bclmh", qc, kc)
    intra = jnp.einsum("bclmh,bcmhv->bclhv", scores * D, vc)
    total = cum[:, :, -1]
    k_dec = kc.astype(jnp.float32) * jnp.exp(total[:, :, None, :]
                                             - cum)[..., None]
    chunk_kv = jnp.einsum("bclhd,bclhv->bchdv", k_dec, vc.astype(jnp.float32))
    return intra, chunk_kv
