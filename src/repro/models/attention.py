"""Grouped-query attention: train/prefill (full-sequence), decode (one token
against a KV cache), cross-attention (enc-dec), sliding-window masks.

The full-sequence path can route through the Pallas flash-attention kernel
(repro/kernels) — selectable per call so CPU tests use the jnp path and the
TPU dry-run claims the kernel's tiling.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ref import gather_pages

from .layers import apply_rope, rms_norm
from .params import ParamSpec

Array = jnp.ndarray

NEG_INF = -1e30


def attention_specs(cfg) -> Dict[str, ParamSpec]:
    D, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    specs = {
        "wq": ParamSpec((D, H, dh), ("embed", "heads", "head_dim"), "scaled"),
        "wk": ParamSpec((D, KV, dh), ("embed", "kv_heads", "head_dim"), "scaled"),
        "wv": ParamSpec((D, KV, dh), ("embed", "kv_heads", "head_dim"), "scaled"),
        "wo": ParamSpec((H, dh, D), ("heads", "head_dim", "embed"), "scaled"),
    }
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((dh,), (None,), "ones")
        specs["k_norm"] = ParamSpec((dh,), (None,), "ones")
    return specs


def _qkv(params, x: Array, cfg, positions: Array,
         rope: bool = True) -> Tuple[Array, Array, Array]:
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_sdpa(q: Array, k: Array, v: Array, mask: Optional[Array],
             softmax_dtype=jnp.float32) -> Array:
    """Grouped-query attention WITHOUT materializing repeated KV heads
    (§Perf H1b: a `jnp.repeat` expansion forced XLA to build — and, with a
    sharded cache, all-gather — an H-headed K/V temp; the grouped einsum
    keeps K/V at their native KV heads).

    q: (B, Sq, H, dh); k, v: (B, Sk, KV, dh) with H % KV == 0;
    mask: broadcastable to (B, Sq, Sk) or None.
    """
    B, Sq, H, dh = q.shape
    KV = k.shape[2]
    g = H // KV
    qg = q.reshape(B, Sq, KV, g, dh)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(softmax_dtype)
    logits = logits / jnp.sqrt(jnp.asarray(dh, softmax_dtype))
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", weights, v)
    return out.reshape(B, Sq, H, dh)


def causal_mask(S: int, window: int = 0) -> Array:
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    m = j <= i
    if window > 0:
        m &= (i - j) < window
    return m[None, :, :]                      # (1, S, S)


def full_attention(params, x: Array, cfg, *, causal: bool = True,
                   use_kernel: bool = False,
                   positions: Optional[Array] = None) -> Array:
    """Train / prefill self-attention over the whole sequence."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q, k, v = _qkv(params, x, cfg, positions)
    if use_kernel:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=causal,
                                   window=cfg.sliding_window)
    else:
        mask = causal_mask(S, cfg.sliding_window) if causal else None
        out = gqa_sdpa(q, k, v, mask, jnp.dtype(cfg.attn_softmax_dtype))
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))


def prefill_attention(params, x: Array, cfg, cache_len: int,
                      use_kernel: bool = False):
    """Like full_attention but also returns the (K, V) to seed the cache,
    right-padded to ``cache_len``."""
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    q, k, v = _qkv(params, x, cfg, positions)
    if use_kernel:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=True,
                                   window=cfg.sliding_window)
    else:
        mask = causal_mask(S, cfg.sliding_window)
        out = gqa_sdpa(q, k, v, mask, jnp.dtype(cfg.attn_softmax_dtype))
    proj = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    pad = [(0, 0), (0, cache_len - S), (0, 0), (0, 0)]
    return proj, (jnp.pad(k, pad), jnp.pad(v, pad))


def decode_attention(params, x: Array, cfg, cache: Tuple[Array, Array],
                     pos: Array, *, use_kernel: bool = False,
                     rope: bool = True):
    """One-token decode. x: (B, 1, D); cache K/V: (B, S_cache, KV, dh);
    pos: () or (B,) current position. Returns (out (B,1,D), new cache).

    With ``cfg.sliding_window > 0`` the cache is a ring buffer of size
    S_cache = window (positions wrap); otherwise it is the full context.
    """
    B, _, D = x.shape
    k_cache, v_cache = cache
    S_cache = k_cache.shape[1]
    pos = jnp.asarray(pos)
    pos_b = jnp.broadcast_to(pos, (B,))
    q, k_new, v_new = _qkv(params, x, cfg, pos_b[:, None], rope=rope)
    if pos.ndim == 0:
        # §Perf H1: scalar position (the serve_step case) — in-place
        # dynamic_update_slice touches ONE cache slot instead of the
        # masked-rewrite of the whole cache (which forced SPMD to fully
        # rematerialize/replicate the cache every step).
        slot = pos % S_cache if cfg.sliding_window > 0 else pos
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k_new.astype(k_cache.dtype), (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v_new.astype(v_cache.dtype), (0, slot, 0, 0))
    else:
        slot = pos_b % S_cache if cfg.sliding_window > 0 else pos_b
        oh = jax.nn.one_hot(slot, S_cache, dtype=k_cache.dtype)  # (B, S)
        k_cache = k_cache * (1 - oh)[:, :, None, None] + \
            oh[:, :, None, None] * k_new.astype(k_cache.dtype)
        v_cache = v_cache * (1 - oh)[:, :, None, None] + \
            oh[:, :, None, None] * v_new.astype(v_cache.dtype)
    if use_kernel:
        from repro.kernels import ops as kops
        out = kops.decode_attention(q[:, 0], k_cache, v_cache,
                                    pos_b, window=cfg.sliding_window)
        out = out[:, None]
    else:
        idx = jnp.arange(S_cache)[None, :]
        if cfg.sliding_window > 0:
            # ring buffer: every slot is valid once pos >= S_cache; before
            # wrapping only slots ≤ pos have been written.
            valid = (idx <= pos_b[:, None]) | (pos_b[:, None] >= S_cache)
        else:
            valid = idx <= pos_b[:, None]
        mask = valid[:, None, :]              # (B, 1, S_cache)
        out = gqa_sdpa(q, k_cache, v_cache, mask, jnp.dtype(cfg.attn_softmax_dtype))
    proj = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return proj, (k_cache, v_cache)


def paged_decode_attention(params, x: Array, cfg,
                           pool: Tuple[Array, Array], pos: Array,
                           block_tables: Array, *,
                           use_kernel: bool = False, rope: bool = True):
    """One-token decode against a PAGED KV cache. x: (B, 1, D); pool K/V:
    (P, KV, block, dh) shared block pool; pos: (B,) current positions;
    block_tables: (B, NB) logical-block → physical-block map per slot.
    Returns (out (B, 1, D), new pool).

    Logical capacity is NB·block per slot; with ``cfg.sliding_window > 0``
    the slot's logical span is addressed as a ring of that size (the
    scheduler sizes NB so it equals the contiguous ring length). Unallocated
    table entries point at physical block 0 — the reserved scratch block —
    and are masked out by the position rule, so a slot never reads another
    slot's blocks.
    """
    B = x.shape[0]
    k_pool, v_pool = pool
    bs = k_pool.shape[2]
    NB = block_tables.shape[1]
    S_log = NB * bs
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))
    q, k_new, v_new = _qkv(params, x, cfg, pos_b[:, None], rope=rope)
    # scatter the new token's K/V into each slot's current block — physical
    # blocks are uniquely owned, so the batched scatter never collides
    # (inactive slots all write block 0 offset 0, the scratch block).
    r = pos_b % S_log if cfg.sliding_window > 0 else pos_b
    blk = jnp.take_along_axis(block_tables, (r // bs)[:, None], axis=1)[:, 0]
    off = r % bs
    k_pool = k_pool.at[blk, :, off].set(k_new[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[blk, :, off].set(v_new[:, 0].astype(v_pool.dtype))
    if use_kernel:
        from repro.kernels import ops as kops
        out = kops.paged_decode_attention(q[:, 0], k_pool, v_pool, pos_b,
                                          block_tables,
                                          window=cfg.sliding_window)
        out = out[:, None]
    else:
        kf = gather_pages(k_pool, block_tables)
        vf = gather_pages(v_pool, block_tables)
        idx = jnp.arange(S_log)[None, :]
        if cfg.sliding_window > 0:
            valid = (idx <= pos_b[:, None]) | (pos_b[:, None] >= S_log)
        else:
            valid = idx <= pos_b[:, None]
        out = gqa_sdpa(q, kf, vf, valid[:, None, :],
                       jnp.dtype(cfg.attn_softmax_dtype))
    proj = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return proj, (k_pool, v_pool)


def paged_verify_attention(params, x: Array, cfg,
                           pool: Tuple[Array, Array], pos: Array,
                           block_tables: Array, *,
                           use_kernel: bool = False, rope: bool = True):
    """Speculative multi-token verify against a PAGED KV cache.

    x: (B, L, D) — row ℓ of slot b is the candidate token sitting at
    absolute position ``pos[b] + ℓ`` (row 0 is the slot's committed next
    token, rows 1..L-1 are draft tokens); pool K/V: (P, KV, block, dh);
    pos: (B,) each slot's current write position; block_tables: (B, NB).
    Returns (out (B, L, D), new pool).

    All L candidate K/V are scattered into the pool FIRST, then every row
    attends under the span-causal rule ``key position ≤ pos + ℓ`` — the
    same single masking rule as chunked prefill, so a candidate sees the
    committed prefix plus the earlier candidates of its own span.
    Rejected-tail writes are rolled back by OVERWRITE: they sit at
    positions strictly greater than the post-accept position, the mask
    hides them from every later query, and the next span (or vanilla
    step) re-scatters those offsets before anything attends there.
    Positions past the table horizon scatter into the reserved scratch
    block 0 (inactive slots — pos 0, zeroed tables — land there too).
    Sliding-window (ring) addressing is not supported — the scheduler
    only routes speculation-capable (windowless) models here.
    """
    B, L, D = x.shape
    k_pool, v_pool = pool
    bs = k_pool.shape[2]
    NB = block_tables.shape[1]
    S_log = NB * bs
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))
    positions = pos_b[:, None] + jnp.arange(L)[None, :]          # (B, L)
    q, k_new, v_new = _qkv(params, x, cfg, positions, rope=rope)
    flat_pos = positions.reshape(-1)                             # (B·L,)
    rows = jnp.repeat(jnp.arange(B), L)
    safe = flat_pos < S_log
    blk = jnp.where(
        safe, block_tables[rows, jnp.clip(flat_pos // bs, 0, NB - 1)], 0)
    off = jnp.where(safe, flat_pos % bs, 0)
    k_pool = k_pool.at[blk, :, off].set(
        k_new.reshape(B * L, *k_new.shape[2:]).astype(k_pool.dtype))
    v_pool = v_pool.at[blk, :, off].set(
        v_new.reshape(B * L, *v_new.shape[2:]).astype(v_pool.dtype))
    if use_kernel:
        from repro.kernels import ops as kops
        out = kops.paged_verify_attention(q, k_pool, v_pool, pos_b,
                                          block_tables)
    else:
        kf = gather_pages(k_pool, block_tables)
        vf = gather_pages(v_pool, block_tables)
        idx = jnp.arange(S_log)[None, None, :]
        valid = idx <= positions[:, :, None]                # (B, L, S_log)
        out = gqa_sdpa(q, kf, vf, valid, jnp.dtype(cfg.attn_softmax_dtype))
    proj = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return proj, (k_pool, v_pool)


def chunk_attention(params, x: Array, cfg, pool: Tuple[Array, Array],
                    start: Array, length: Array, block_table: Array, *,
                    use_kernel: bool = False):
    """Chunked-prefill self-attention THROUGH the paged pool.

    x: (1, C, D) chunk hidden states whose row c sits at absolute position
    ``start + c``; pool K/V: (P, KV, block, dh) shared block pool;
    ``length``: () int32 valid rows in this chunk (a final partial chunk is
    right-padded to C); block_table: (NB,) int32 — THIS request's logical →
    physical block map. Returns (out (1, C, D), new pool).

    The chunk's K/V are scattered into the pool *first*, so within-chunk
    causality flows through the same block-table read as the prefix written
    by earlier chunks — one masking rule (key position ≤ query position)
    covers both. Padded rows scatter into the reserved scratch block 0 and
    their outputs are garbage the caller discards; padded keys sit at
    positions no valid query can attend, so they never leak.
    """
    B, C, D = x.shape
    k_pool, v_pool = pool
    bs = k_pool.shape[2]
    NB = block_table.shape[0]
    S_log = NB * bs
    offs = jnp.arange(C)
    pos_c = start + offs                                     # (C,)
    q, k_new, v_new = _qkv(params, x, cfg, pos_c[None, :])
    valid = offs < length
    blk = jnp.where(valid,
                    block_table[jnp.clip(pos_c // bs, 0, NB - 1)], 0)
    off = jnp.where(valid, pos_c % bs, 0)
    k_pool = k_pool.at[blk, :, off].set(k_new[0].astype(k_pool.dtype))
    v_pool = v_pool.at[blk, :, off].set(v_new[0].astype(v_pool.dtype))
    if use_kernel:
        from repro.kernels import ops as kops
        out = kops.chunk_prefill_attention(q[0], k_pool, v_pool, start,
                                           block_table)[None]
    else:
        kf = gather_pages(k_pool, block_table)[None]
        vf = gather_pages(v_pool, block_table)[None]
        mask = (jnp.arange(S_log)[None, :] <= pos_c[:, None])[None]
        out = gqa_sdpa(q, kf, vf, mask, jnp.dtype(cfg.attn_softmax_dtype))
    proj = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return proj, (k_pool, v_pool)


# ---------------------------------------------------------------------------
# Cross-attention (enc-dec)
# ---------------------------------------------------------------------------

def cross_attention(params, x: Array, enc_kv: Tuple[Array, Array],
                    cfg) -> Array:
    """x: (B, S_dec, D); enc_kv: precomputed (K, V) each (B, S_enc, KV, dh).
    No RoPE on cross-attention queries (content-based addressing)."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
    k, v = enc_kv
    out = gqa_sdpa(q, k.astype(dt), v.astype(dt), None, jnp.dtype(cfg.attn_softmax_dtype))
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))


def encode_kv(params, enc_out: Array, cfg) -> Tuple[Array, Array]:
    """Project encoder output once into cross-attention K/V."""
    dt = enc_out.dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"].astype(dt))
    return k, v
