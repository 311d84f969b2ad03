"""Single-token GQA decode attention (Pallas): one query vector per head
attends over the KV cache in blocks, online-softmax carried in scratch.

Grid = (batch, kv_heads, kv_blocks). All ``group = H/KV`` query heads that
share a KV head are processed together as a (group, dh) tile — the natural
GQA layout on the MXU (the group dim rides the sublane axis). Position
masking (including the ring-buffer validity rule for sliding-window caches)
is computed from a per-batch position scalar held in SMEM (a
scalar-prefetch operand).

Every K/V block is (positions, dh) in its last two dims — the TPU tiling
rule wants those divisible by (8, 128) or equal to the array's own, and a
head axis among them would give a block of 1 against KV. Two cache layouts
share the kernel body:

* contiguous — K/V are (B, S, KV, dh) slot rows, transposed head-major to
  (B, KV, S, dh) by the wrapper; the ki-th grid step reads the ki-th
  sequence block of row b;
* paged — K/V live in a shared head-major (L, P, KV, block, dh) block
  pool holding every layer, and the ki-th grid step of layer ``layer``
  reads physical block ``block_tables[b, ki]``: the layer and the per-slot
  block table are scalar-prefetch operands, so the index map resolves the
  indirection at DMA-issue time and the body never sees it (the classic
  paged-attention gather). The caller's layer loop carries the whole pool
  and never slices a layer out of it. Unallocated table entries point at
  the reserved scratch block 0 and are killed by the position mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jnp.ndarray
NEG_INF = -1e30


def _accum_block(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, block: int, ki, pos, window: int,
                 s_cache: int):
    """Online-softmax accumulation of one KV block — the single source of
    the masking fence and the m/l/acc rescaling recurrence, shared by the
    contiguous and paged kernels so their numerics can never diverge."""
    q = q_ref[0, 0].astype(jnp.float32)                # (group, dh)
    k = k_ref[0, 0].astype(jnp.float32)                # (block, dh)
    v = v_ref[0, 0].astype(jnp.float32)                # (block, dv)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    idx = ki * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if window > 0:
        valid = (idx <= pos) | (pos >= s_cache)        # ring buffer
    else:
        valid = idx <= pos
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   scale: float, block_k: int, window: int, s_cache: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    _accum_block(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, scale=scale,
                 block=block_k, ki=ki, pos=pos_ref[b], window=window,
                 s_cache=s_cache)

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[0, 0, :, :] = (acc_scr[...] /
                             jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def decode_attention(q: Array, k: Array, v: Array, pos: Array, *,
                     window: int = 0, block_k: int = 256,
                     interpret: bool = False) -> Array:
    """q: (B,H,dh); k,v: (B,S,KV,dh); pos: (B,) int32 → (B,H,dh).

    The cache is read head-major, so this wrapper transposes K/V to
    (B, KV, S, dh) first: a full copy of the cache per call. The paged
    kernels below keep their pool head-major and copy nothing."""
    B, H, dh = q.shape
    S, KV = k.shape[1], k.shape[2]
    assert H % KV == 0
    group = H // KV
    block_k = min(block_k, S)
    assert S % block_k == 0, (S, block_k)
    nk = S // block_k
    scale = 1.0 / (dh ** 0.5)
    # regroup query heads by their KV head: (B, KV, group, dh)
    qg = q.reshape(B, KV, group, dh)

    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k,
                               window=window, s_cache=S)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                        # pos
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec((1, 1, group, dh),
                         lambda b, h, ki, pos_r: (b, h, 0, 0)),   # q
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b, h, ki, pos_r: (b, h, ki, 0)),  # k
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b, h, ki, pos_r: (b, h, ki, 0)),  # v
        ],
        out_specs=pl.BlockSpec((1, 1, group, dh),
                               lambda b, h, ki, pos_r: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, group, dh), q.dtype),
        interpret=interpret,
    )(pos.astype(jnp.int32), qg, jnp.swapaxes(k, 1, 2),
      jnp.swapaxes(v, 1, 2))
    return out.reshape(B, H, dh)


def _paged_decode_kernel(pos_ref, bt_ref, layer_ref, q_ref, *refs,
                         scale: float, block: int, window: int, s_log: int,
                         bps: int, nb: int):
    """Same online-softmax body as ``_decode_kernel``; the physical-block
    indirection already happened in the index maps, so the logical block
    index ``ki`` drives the masking rules unchanged.

    One grid step processes ``bps`` logical blocks: the j-th sub-tile is a
    separate kernel operand whose index map fetched logical block
    ``kc·bps + j`` — clamped to the slot's horizon block ``pos // block``
    (windowless caches), so past-the-horizon sub-tiles re-fetch the
    horizon block and the revisit rule elides their DMAs entirely; the
    body then skips them via ``live``. Sub-tiles accumulate in ascending
    ``ki`` order, so the m/l/acc recurrence is bit-identical to bps=1.
    """
    k_refs, v_refs = refs[:bps], refs[bps:2 * bps]
    o_ref = refs[2 * bps]
    m_scr, l_scr, acc_scr = refs[2 * bps + 1:]
    b = pl.program_id(0)
    kc = pl.program_id(2)
    nkc = pl.num_programs(2)

    @pl.when(kc == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[b]
    for j in range(bps):
        ki = kc * bps + j
        # a logical block is dead when every one of its positions is
        # masked; skipping it saves the two MXU dots (and, clamped, its
        # DMA). The ``ki < nb`` guard kills the padded tail when bps does
        # not divide nb (its clamped fetch aliases a live block).
        live = ((ki * block <= pos) if window <= 0
                else ((ki * block <= pos) | (pos >= s_log))) & (ki < nb)

        @pl.when(live)
        def _accum(j=j, ki=ki):
            _accum_block(q_ref, k_refs[j], v_refs[j], m_scr, l_scr,
                         acc_scr, scale=scale, block=block, ki=ki, pos=pos,
                         window=window, s_cache=s_log)

    @pl.when(kc == nkc - 1)
    def _done():
        o_ref[0, 0, :, :] = (acc_scr[...] /
                             jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _chunk_prefill_kernel(start_ref, bt_ref, layer_ref, q_ref, *refs,
                          scale: float, block: int, group: int, C: int,
                          bps: int, nb: int):
    """Prefix-aware chunked-prefill flash attention over PAGED blocks.

    Rows are the chunk's (c, group) query pairs flattened c-major; row r is
    the query at absolute position ``start + r // group``. ``ki`` is the
    LOGICAL block index — the physical indirection already happened in the
    index maps (scalar-prefetched block table), exactly like the paged
    decode kernel, with the same ``blocks_per_step`` sub-tiling (the
    horizon here is the last query position's block). The chunk's own K/V
    were written into the pool before the call, so the single fence
    ``key position ≤ query position`` covers both the prefix and
    within-chunk causality.
    """
    k_refs, v_refs = refs[:bps], refs[bps:2 * bps]
    o_ref = refs[2 * bps]
    m_scr, l_scr, acc_scr = refs[2 * bps + 1:]
    kc = pl.program_id(1)
    nkc = pl.num_programs(1)

    @pl.when(kc == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = start_ref[0]
    for j in range(bps):
        ki = kc * bps + j
        # blocks entirely above the last query position are dead for
        # every row; the ``ki < nb`` guard kills the padded tail
        live = (ki * block <= start + (C - 1)) & (ki < nb)

        @pl.when(live)
        def _accum(j=j, ki=ki):
            q = q_ref[0].astype(jnp.float32)             # (C·group, dh)
            k = k_refs[j][0, 0].astype(jnp.float32)      # (block, dh)
            v = v_refs[j][0, 0].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
            cols = ki * block + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols <= start + rows, s, NEG_INF)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[...] = alpha * l_scr[...] + \
                jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[...] = m_new

    @pl.when(kc == nkc - 1)
    def _done():
        o_ref[0, :, :] = (acc_scr[...] /
                          jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def chunk_prefill_attention(q: Array, k_pool: Array, v_pool: Array,
                            layer: Array, start: Array, block_table: Array,
                            *, blocks_per_step: int = 1,
                            interpret: bool = False) -> Array:
    """q: (C,H,dh) one request's chunk queries; k_pool,v_pool:
    (L,P,KV,block,dh) with the chunk's K/V already written in; layer: ()
    int32 the pool layer to read; start: () int32 absolute position of
    chunk row 0; block_table: (NB,) int32 → (C,H,dh).

    Grid = (kv_heads, ⌈NB / blocks_per_step⌉ logical-block groups);
    ``start``, the block table and ``layer`` are scalar-prefetch operands
    so the K/V index maps resolve the physical block at DMA-issue time. As
    in
    ``paged_decode_attention``, each of the ``blocks_per_step`` sub-tiles
    is its own operand whose index map clamps the fetched logical index to
    the chunk's horizon block ``(start + C - 1) // block`` — dead blocks
    alias the horizon block and the DMA revisit rule elides the fetch.
    Unallocated entries alias scratch block 0 and are killed by the
    position fence.
    """
    C, H, dh = q.shape
    KV, block = k_pool.shape[2], k_pool.shape[3]
    NB = block_table.shape[0]
    assert H % KV == 0
    group = H // KV
    scale = 1.0 / (dh ** 0.5)
    bps = max(1, min(blocks_per_step, NB))
    nkc = -(-NB // bps)
    # rows flattened c-major per KV head: (KV, C·group, dh)
    qg = jnp.transpose(q.reshape(C, KV, group, dh), (1, 0, 2, 3)) \
        .reshape(KV, C * group, dh)

    def kv_spec(j):
        def imap(h, kc, start_r, bt_r, l_r):
            # repro: bounds bt_r holds pool block ids < P (the pool's
            # block dim) — the allocator only writes ids it owns and
            # masks unallocated table rows to the reserved scratch block
            # 0; ki is clamped to NB - 1 above, so bt_r[ki] never reads
            # past the table
            # repro: bounds l_r[0] is the layer loop's scanned index < L
            ki = jnp.minimum(jnp.minimum(kc * bps + j,
                                         (start_r[0] + C - 1) // block),
                             NB - 1)
            return (l_r[0], bt_r[ki], h, 0, 0)
        return pl.BlockSpec((pl.Squeezed(), 1, 1, block, dh), imap)

    kernel = functools.partial(_chunk_prefill_kernel, scale=scale,
                               block=block, group=group, C=C,
                               bps=bps, nb=NB)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                # start, block_table, layer
        grid=(KV, nkc),
        in_specs=[
            pl.BlockSpec((1, C * group, dh),
                         lambda h, kc, *_: (h, 0, 0)),                  # q
            *[kv_spec(j) for j in range(bps)],                          # k
            *[kv_spec(j) for j in range(bps)],                          # v
        ],
        out_specs=pl.BlockSpec((1, C * group, dh),
                               lambda h, kc, *_: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((C * group, 1), jnp.float32),
            pltpu.VMEM((C * group, 1), jnp.float32),
            pltpu.VMEM((C * group, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((KV, C * group, dh), q.dtype),
        interpret=interpret,
    )(jnp.asarray(start, jnp.int32).reshape(1),
      block_table.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qg,
      *([k_pool] * bps), *([v_pool] * bps))
    return jnp.transpose(out.reshape(KV, C, group, dh),
                         (1, 0, 2, 3)).reshape(C, H, dh)


def _paged_verify_kernel(pos_ref, bt_ref, layer_ref, q_ref, *refs,
                         scale: float, block: int, group: int, L: int,
                         bps: int, nb: int):
    """Speculative span verify over PAGED blocks — the chunk-prefill body
    batched over slots.

    Rows are one slot's (ℓ, group) query pairs flattened ℓ-major; row r is
    the candidate at absolute position ``pos[b] + r // group``. ``ki`` is
    the LOGICAL block index — the physical indirection happened in the
    scalar-prefetched index maps, with the same ``blocks_per_step``
    sub-tiling as the paged decode kernel. The span's own K/V were
    written into the pool before the call, so the single fence
    ``key position ≤ pos + row offset`` covers the committed prefix AND
    within-span causality; rejected-tail keys at later offsets are hidden
    from every accepted row by the same rule.
    """
    k_refs, v_refs = refs[:bps], refs[bps:2 * bps]
    o_ref = refs[2 * bps]
    m_scr, l_scr, acc_scr = refs[2 * bps + 1:]
    b = pl.program_id(0)
    kc = pl.program_id(2)
    nkc = pl.num_programs(2)

    @pl.when(kc == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[b]
    for j in range(bps):
        ki = kc * bps + j
        # blocks entirely above the span's LAST position are dead for
        # every row; the ``ki < nb`` guard kills the padded tail
        live = (ki * block <= pos + (L - 1)) & (ki < nb)

        @pl.when(live)
        def _accum(j=j, ki=ki):
            q = q_ref[0, 0].astype(jnp.float32)          # (L·group, dh)
            k = k_refs[j][0, 0].astype(jnp.float32)      # (block, dh)
            v = v_refs[j][0, 0].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
            cols = ki * block + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols <= pos + rows, s, NEG_INF)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[...] = alpha * l_scr[...] + \
                jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[...] = m_new

    @pl.when(kc == nkc - 1)
    def _done():
        o_ref[0, 0, :, :] = (acc_scr[...] /
                             jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_verify_attention(q: Array, k_pool: Array, v_pool: Array,
                           layer: Array, pos: Array, block_tables: Array,
                           *, blocks_per_step: int = 1,
                           interpret: bool = False) -> Array:
    """q: (B,L,H,dh) span queries (row ℓ of slot b sits at absolute
    position ``pos[b] + ℓ``, its K/V already written into the pool);
    k_pool,v_pool: (layers,P,KV,block,dh); layer: () int32 the pool layer
    to read; pos: (B,) int32; block_tables: (B,NB) int32 → (B,L,H,dh).

    Grid = (batch, kv_heads, ⌈NB / blocks_per_step⌉), one (L·group, dh)
    query tile per slot per KV head (span offsets ride the sublane axis
    next to the GQA group, exactly like the chunk-prefill kernel's rows).
    ``pos``, the block tables and ``layer`` are scalar-prefetch operands;
    each of the
    ``blocks_per_step`` K/V sub-tiles is its own operand whose index map
    clamps the fetched logical index to the span's horizon block
    ``(pos + L - 1) // block`` — dead blocks alias the horizon block and
    the DMA revisit rule elides the fetch. Sliding-window (ring) caches
    are not supported: the scheduler only routes windowless models here.
    """
    B, L, H, dh = q.shape
    KV, block = k_pool.shape[2], k_pool.shape[3]
    NB = block_tables.shape[1]
    assert H % KV == 0
    group = H // KV
    scale = 1.0 / (dh ** 0.5)
    bps = max(1, min(blocks_per_step, NB))
    nkc = -(-NB // bps)
    # rows flattened ℓ-major per slot per KV head: (B, KV, L·group, dh)
    qg = jnp.transpose(q.reshape(B, L, KV, group, dh), (0, 2, 1, 3, 4)) \
        .reshape(B, KV, L * group, dh)

    def kv_spec(j):
        def imap(b, h, kc, pos_r, bt_r, l_r):
            # repro: bounds bt_r holds pool block ids < P (the pool's
            # block dim) — allocator invariant; ki is clamped to NB - 1,
            # so bt_r[b, ki] stays in-table
            # repro: bounds l_r[0] is the layer loop's scanned index < L
            ki = jnp.minimum(jnp.minimum(kc * bps + j,
                                         (pos_r[b] + L - 1) // block),
                             NB - 1)
            return (l_r[0], bt_r[b, ki], h, 0, 0)
        return pl.BlockSpec((pl.Squeezed(), 1, 1, block, dh), imap)

    kernel = functools.partial(_paged_verify_kernel, scale=scale,
                               block=block, group=group, L=L,
                               bps=bps, nb=NB)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                # pos, block_tables, layer
        grid=(B, KV, nkc),
        in_specs=[
            pl.BlockSpec((1, 1, L * group, dh),
                         lambda b, h, kc, *_: (b, h, 0, 0)),            # q
            *[kv_spec(j) for j in range(bps)],                          # k
            *[kv_spec(j) for j in range(bps)],                          # v
        ],
        out_specs=pl.BlockSpec((1, 1, L * group, dh),
                               lambda b, h, kc, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((L * group, 1), jnp.float32),
            pltpu.VMEM((L * group, 1), jnp.float32),
            pltpu.VMEM((L * group, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, L * group, dh), q.dtype),
        interpret=interpret,
    )(pos.astype(jnp.int32), block_tables.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qg,
      *([k_pool] * bps), *([v_pool] * bps))
    return jnp.transpose(out.reshape(B, KV, L, group, dh),
                         (0, 2, 1, 3, 4)).reshape(B, L, H, dh)


def paged_decode_attention(q: Array, k_pool: Array, v_pool: Array,
                           layer: Array, pos: Array, block_tables: Array,
                           *, window: int = 0, blocks_per_step: int = 1,
                           interpret: bool = False) -> Array:
    """q: (B,H,dh); k_pool,v_pool: (L,P,KV,block,dh); layer: () int32 the
    pool layer to read; pos: (B,) int32; block_tables: (B,NB) int32 →
    (B,H,dh).

    Grid = (batch, kv_heads, ⌈NB / blocks_per_step⌉). ``pos``, the block
    table and ``layer`` are scalar-prefetch operands: the K/V index maps
    pick the layer's physical block out of the whole pool, so neither the
    layer slice nor the gather is ever materialized — it happens in the DMA
    engine, not the kernel body — amortized over ``blocks_per_step``
    logical blocks per grid step (each sub-tile is its own operand with
    its own index map). Windowless maps clamp the fetched logical index to
    the slot's horizon block ``pos // block``: every past-the-horizon grid
    step re-fetches the horizon block, which the DMA revisit rule elides —
    dead blocks cost neither bandwidth nor MXU work, replacing the old
    fetch-then-mask scheme. ``window > 0`` applies the ring validity rule
    over the slot's logical span NB·block (= the ring size; the whole ring
    stays live once wrapped, so only the NB bound is clamped).
    """
    B, H, dh = q.shape
    KV, block = k_pool.shape[2], k_pool.shape[3]
    NB = block_tables.shape[1]
    assert H % KV == 0
    group = H // KV
    scale = 1.0 / (dh ** 0.5)
    bps = max(1, min(blocks_per_step, NB))
    nkc = -(-NB // bps)
    qg = q.reshape(B, KV, group, dh)

    def kv_spec(j):
        if window <= 0:
            def imap(b, h, kc, pos_r, bt_r, l_r):
                # repro: bounds bt_r holds pool block ids < P (the
                # pool's block dim) — allocator invariant; ki is clamped
                # to NB - 1, so bt_r[b, ki] stays in-table
                # repro: bounds l_r[0] is the layer loop's scanned index
                ki = jnp.minimum(jnp.minimum(kc * bps + j,
                                             pos_r[b] // block), NB - 1)
                return (l_r[0], bt_r[b, ki], h, 0, 0)
        else:
            def imap(b, h, kc, pos_r, bt_r, l_r):
                # repro: bounds bt_r holds pool block ids < P (the
                # pool's block dim) — allocator invariant; ki is clamped
                # to NB - 1, so bt_r[b, ki] stays in-table
                # repro: bounds l_r[0] is the layer loop's scanned index
                ki = jnp.minimum(kc * bps + j, NB - 1)
                return (l_r[0], bt_r[b, ki], h, 0, 0)
        return pl.BlockSpec((pl.Squeezed(), 1, 1, block, dh), imap)

    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               block=block, window=window, s_log=NB * block,
                               bps=bps, nb=NB)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                # pos, block_tables, layer
        grid=(B, KV, nkc),
        in_specs=[
            pl.BlockSpec((1, 1, group, dh),
                         lambda b, h, kc, *_: (b, h, 0, 0)),           # q
            *[kv_spec(j) for j in range(bps)],                         # k
            *[kv_spec(j) for j in range(bps)],                         # v
        ],
        out_specs=pl.BlockSpec((1, 1, group, dh),
                               lambda b, h, kc, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, group, dh), q.dtype),
        interpret=interpret,
    )(pos.astype(jnp.int32), block_tables.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qg,
      *([k_pool] * bps), *([v_pool] * bps))
    return out.reshape(B, H, dh)
