"""Unified model assembly for every assigned architecture family.

One ``Model`` object per ModelConfig exposes:

* ``param_specs()`` — ParamSpec pytree (single source for init/sharding/dry-run)
* ``init(key)`` — materialized parameters
* ``forward(params, batch)`` — teacher-forced logits (training/eval)
* ``loss(params, batch)`` — next-token CE with masking (VLM/audio aware)
* ``prefill(params, batch)`` — full-sequence forward that also builds the
  decode state (KV caches / recurrent states), right-sized to ``cache_len``
* ``decode_step(params, cache, tokens, pos)`` — ONE new token (serve_step)
* ``init_cache`` / ``cache_shapes`` — zeros or ShapeDtypeStructs (dry-run)

Layer stacks are ``jax.lax.scan``-ed over stacked parameters (compile time
independent of depth — essential for the 126-layer 405B dry-run) with an
optional remat policy. Heterogeneous stacks (xLSTM's periodic sLSTM, Zamba2's
periodically-applied *shared* attention block) scan over homogeneous groups.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import attention as attn
from . import moe as moe_lib
from . import ssm as ssm_lib
from .layers import (cross_entropy_loss, embed, embedding_specs, rms_norm,
                     swiglu, swiglu_specs, unembed)
from .params import ParamSpec, init_params, is_spec

Array = jnp.ndarray


def stack_specs(tree, n: int):
    """Prepend a scanned layer dim to every ParamSpec in the tree."""
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layer",) + s.logical,
                            s.init, s.scale),
        tree, is_leaf=is_spec)


def _norm_spec(d):
    return ParamSpec((d,), (None,), "ones")


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def scan_layers(body, carry, xs, cfg: ModelConfig, *, indexed: bool = False):
    """``jax.lax.scan`` over a stacked layer dim — or, when ``cfg.unroll``
    is set (dry-run depth probes), an unrolled python loop producing
    straight-line HLO with identical semantics. Named scope ``layers``:
    the loop's own slicing of ``xs`` (each layer's weights and direct
    state) and writing back of the stacked ``ys`` are attributed to it in
    a device trace, the body's ops to their inner scopes.

    ``indexed`` calls ``body(carry, x, i)`` with the layer's index ``i``:
    a scanned ``jnp.arange(L)`` element, a Python int when unrolled. The
    paged serving steps use it to address the layer-stacked KV pool they
    carry whole in ``carry`` (a pool in ``xs`` would be sliced out and
    re-stacked every step)."""
    with jax.named_scope("layers"):
        L = jax.tree.leaves(xs)[0].shape[0]
        if not cfg.unroll:
            if not indexed:
                return jax.lax.scan(body, carry, xs)
            return jax.lax.scan(lambda c, xi: body(c, xi[0], xi[1]), carry,
                                (xs, jnp.arange(L, dtype=jnp.int32)))
        ys = []
        for i in range(L):
            layer = jax.tree.map(lambda a, i=i: a[i], xs)
            carry, y = body(carry, layer, i) if indexed \
                else body(carry, layer)
            ys.append(y)
        if all(y is None for y in ys):
            return carry, None
        stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *ys)
        return carry, stacked


@dataclass(frozen=True)
class PagedLayout:
    """Block-table indirection descriptor for the pageable cache leaves.

    ``seq_axes`` mirrors the cache pytree: for leaves that live in the
    shared block pool it gives the index of the *sequence* axis in the
    contiguous layout (e.g. attention KV (L, B, S, KV, dh) → 2); leaves
    that stay on the direct per-slot path (recurrent states, enc-dec
    cross-attention KV) carry ``-1``. In the pool layout a paged leaf's
    batch axis becomes the physical-block axis (n_blocks) and its sequence
    axis moves in front of the last (head_dim) axis as the in-block offset:
    attention KV (L, B, S, KV, dh) pages as (L, n_blocks, KV, block_size,
    dh), head-major, so an attention kernel's K/V block is (block_size, dh)
    in its last two dims. Blocks are addressed through a per-slot block
    table — so every slot pays only for the blocks it has actually written
    instead of a full-length cache row.
    """
    block_size: int
    seq_axes: Any


@dataclass(frozen=True)
class CacheSpec:
    """Layout descriptor for a model family's decode cache.

    ``batch_axes`` is a pytree with the same structure as the cache whose
    leaves give the index of the request/slot (batch) axis in the matching
    cache leaf — e.g. attention KV caches are (L, B, S, KV, dh) → 1, Mamba2
    states are (G, gm, B, ...) → 2. Slot servers use it to splice one
    request's prefill state into a batched cache without knowing the family.

    ``paged`` (optional) describes the block-pool variant of the same cache:
    which leaves are addressed through a block table and at what block size.
    """
    batch_axes: Any
    paged: Optional[PagedLayout] = None

    def shifted(self, by: int = 1) -> "CacheSpec":
        """Spec for the same cache with ``by`` extra dims inserted before
        every batch axis (e.g. the stacked-expert K dim of the mixture
        decode core, which leads each pool leaf and sits after every other
        leaf's scan dim — before the batch axis either way). Memoized so
        repeat callers share one spec — and with it the jitted splice
        functions below (a fresh spec would recompile them).

        Every splice below consumes (donates) its ``cache`` argument so
        the pool is written in place; callers rebind the result."""
        memo = self.__dict__.setdefault("_shifted_memo", {})
        if by not in memo:
            paged = self.paged
            if paged is not None:
                paged = PagedLayout(paged.block_size,
                                    jax.tree.map(lambda a: a + by if a >= 0
                                                 else a, paged.seq_axes))
            memo[by] = CacheSpec(
                jax.tree.map(lambda a: a + by, self.batch_axes), paged)
        return memo[by]

    def insert(self, cache, row_cache, slot: int):
        """Write a single-request cache (batch extent 1 on each leaf's batch
        axis) into ``cache`` at slot index ``slot``."""
        return self._insert_jit(cache, row_cache, jnp.int32(slot))

    @cached_property
    def _insert_jit(self):
        # one jitted splice for ALL slots (the index is a traced scalar):
        # per-leaf unjitted updates each dispatch separately and copy the
        # whole leaf, which shows up as per-admission latency
        def insert_row(cache, row_cache, slot):
            return jax.tree.map(
                lambda full, row, ax: jax.lax.dynamic_update_slice_in_dim(
                    full, row.astype(full.dtype), slot, axis=ax),
                cache, row_cache, self.batch_axes)
        return jax.jit(insert_row, donate_argnums=(0,))

    def insert_paged(self, cache, row_cache, slot: int, blocks: Array):
        """Splice a single-request contiguous prefill cache into the paged
        cache: pool leaves scatter the first ``len(blocks) * block_size``
        cache-row positions into the physical blocks listed in ``blocks``
        (int32 (nb,)); direct leaves behave exactly like ``insert``."""
        assert self.paged is not None, "insert_paged needs a paged spec"
        return self._insert_paged_jit(cache, row_cache, jnp.int32(slot),
                                      blocks)

    @cached_property
    def _insert_paged_jit(self):
        bs = self.paged.block_size

        # jitted across slots (traced scalar); retraces once per distinct
        # block-count nb — bounded by the slot's table length
        def insert_paged(cache, row_cache, slot, blocks):
            nb = blocks.shape[0]

            def one(full, row, b_ax, s_ax):
                if s_ax < 0:
                    return jax.lax.dynamic_update_slice_in_dim(
                        full, row.astype(full.dtype), slot, axis=b_ax)
                # pool leaf: contiguous row is (..., 1, S, ..., dh) with
                # the batch extent-1 at b_ax and the sequence at
                # s_ax == b_ax + 1; the pool is (..., P, ..., bs, dh)
                # (see PagedLayout).
                assert s_ax == b_ax + 1, (b_ax, s_ax)
                row = jnp.squeeze(row, axis=b_ax)      # seq now at b_ax
                take = min(nb * bs, row.shape[b_ax])
                row = jax.lax.slice_in_dim(row, 0, take, axis=b_ax)
                if take < nb * bs:                     # cache_len ∤ block
                    pad = [(0, 0)] * row.ndim
                    pad[b_ax] = (0, nb * bs - take)
                    row = jnp.pad(row, pad)
                row = row.reshape(row.shape[:b_ax] + (nb, bs)
                                  + row.shape[b_ax + 1:])
                row = jnp.moveaxis(row, b_ax + 1, -2)  # offset before dh
                idx = (slice(None),) * b_ax + (blocks,)
                return full.at[idx].set(row.astype(full.dtype))

            seq = self.paged.seq_axes
            return jax.tree.map(one, cache, row_cache, self.batch_axes, seq)
        return jax.jit(insert_paged, donate_argnums=(0,))

    def insert_direct(self, cache, carry, slot: int):
        """Write a chunked-prefill carry (single-request DIRECT-leaf decode
        states; pool-leaf entries are placeholders — their data was written
        straight into the block pool chunk by chunk) into the batched cache
        at ``slot``. Without a paged layout every leaf is direct."""
        return self._insert_direct_jit(cache, carry, jnp.int32(slot))

    @cached_property
    def _insert_direct_jit(self):
        seq = self.paged.seq_axes if self.paged is not None else \
            jax.tree.map(lambda _: -1, self.batch_axes)

        def insert_direct(cache, carry, slot):
            def one(full, row, ax, s_ax):
                if s_ax >= 0:
                    return full
                return jax.lax.dynamic_update_slice_in_dim(
                    full, row.astype(full.dtype), slot, axis=ax)

            return jax.tree.map(one, cache, carry, self.batch_axes, seq)
        return jax.jit(insert_direct, donate_argnums=(0,))

    def take(self, cache, slot: int):
        """Read one slot's cache back out (batch extent 1 preserved)."""
        return self._take_jit(cache, jnp.int32(slot))

    @cached_property
    def _take_jit(self):
        def take_row(cache, slot):
            return jax.tree.map(
                lambda full, ax: jax.lax.dynamic_slice_in_dim(full, slot, 1,
                                                              axis=ax),
                cache, self.batch_axes)
        return jax.jit(take_row)

    def swap_out(self, cache, slot: int, blocks):
        """Read one slot's paged decode state out for host-side parking
        (QoS preemption by swap): pool leaves gather the listed physical
        blocks' contents (``take`` cannot do this — it slices batch axes,
        and a pool leaf's batch axis is the *block* axis shared by every
        slot); direct leaves slice the slot's row, extent 1 preserved.
        The payload pytree mirrors the cache and round-trips through
        ``swap_in``. Not jitted: parking is rare and the block count
        varies per victim, so a trace per count would cost more than the
        per-leaf dispatches."""
        assert self.paged is not None, "swap_out needs a paged spec"
        blocks = jnp.asarray(blocks, jnp.int32)

        def one(full, b_ax, s_ax):
            if s_ax < 0:
                return jax.lax.dynamic_slice_in_dim(full, slot, 1,
                                                    axis=b_ax)
            # pool leaf: the physical-block axis sits at b_ax
            idx = (slice(None),) * b_ax + (blocks,)
            return full[idx]

        return jax.tree.map(one, cache, self.batch_axes,
                            self.paged.seq_axes)

    def swap_in(self, cache, payload, slot: int, blocks):
        """Scatter a ``swap_out`` payload back: pool-leaf contents land in
        the (freshly allocated) physical blocks listed in ``blocks`` —
        positionally matching the payload's gather order — and direct
        leaves overwrite the resumed slot's row. ``slot``/``blocks`` need
        not match the ones swapped out; the block *table* mapping logical
        to physical order is the caller's to rebuild. Jitted (one trace
        per block count, as resumes are rare) so it can consume its
        ``cache`` in place."""
        assert self.paged is not None, "swap_in needs a paged spec"
        return self._swap_in_jit(cache, payload, jnp.int32(slot),
                                 jnp.asarray(blocks, jnp.int32))

    @cached_property
    def _swap_in_jit(self):
        def swap_in(cache, payload, slot, blocks):
            def one(full, row, b_ax, s_ax):
                row = jnp.asarray(row, full.dtype)
                if s_ax < 0:
                    return jax.lax.dynamic_update_slice_in_dim(
                        full, row, slot, axis=b_ax)
                idx = (slice(None),) * b_ax + (blocks,)
                return full.at[idx].set(row)

            return jax.tree.map(one, cache, payload, self.batch_axes,
                                self.paged.seq_axes)
        return jax.jit(swap_in, donate_argnums=(0,))


@dataclass
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------------
    # Parameter specs
    # ------------------------------------------------------------------

    def _block_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        D = cfg.d_model
        if cfg.family in ("dense", "vlm"):
            return {"ln1": _norm_spec(D), "attn": attn.attention_specs(cfg),
                    "ln2": _norm_spec(D), "ffn": swiglu_specs(D, cfg.d_ff)}
        if cfg.family == "moe":
            return {"ln1": _norm_spec(D), "attn": attn.attention_specs(cfg),
                    "ln2": _norm_spec(D), "moe": moe_lib.moe_specs(cfg)}
        if cfg.family == "audio":      # decoder block
            return {"ln1": _norm_spec(D), "self_attn": attn.attention_specs(cfg),
                    "ln2": _norm_spec(D), "cross_attn": attn.attention_specs(cfg),
                    "ln3": _norm_spec(D), "ffn": swiglu_specs(D, cfg.d_ff)}
        if cfg.family == "ssm":        # xLSTM group: (k−1) mLSTM + 1 sLSTM
            gm = self.group_m
            return {
                "m_ln": stack_specs(_norm_spec(D), gm),
                "mlstm": stack_specs(ssm_lib.mlstm_specs(cfg), gm),
                "s_ln": _norm_spec(D),
                "slstm": ssm_lib.slstm_specs(cfg),
            }
        if cfg.family == "hybrid":     # Zamba2 group: k Mamba2 (+ shared attn)
            gm = self.group_m
            return {
                "m_ln": stack_specs(_norm_spec(D), gm),
                "mamba": stack_specs(ssm_lib.mamba2_specs(cfg), gm),
            }
        raise ValueError(cfg.family)

    @cached_property
    def group_m(self) -> int:
        """Homogeneous sub-layers per scanned group (ssm/hybrid)."""
        cfg = self.cfg
        if cfg.family == "ssm":
            k = cfg.ssm.slstm_every or cfg.n_layers
            return max(k - 1, 1)
        if cfg.family == "hybrid":
            return cfg.ssm.shared_attn_every or cfg.n_layers
        return 1

    @cached_property
    def n_groups(self) -> int:
        cfg = self.cfg
        if cfg.family == "ssm":
            return cfg.n_layers // (self.group_m + 1)
        if cfg.family == "hybrid":
            return cfg.n_layers // self.group_m
        return cfg.n_layers

    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        D = cfg.d_model
        specs: Dict[str, Any] = {
            "embed": embedding_specs(cfg.padded_vocab, D, cfg.tie_embeddings),
            "blocks": stack_specs(self._block_specs(), self.n_groups),
            "final_norm": _norm_spec(D),
        }
        if cfg.family == "vlm":
            specs["projector"] = {
                "w1": ParamSpec((cfg.vision_dim, D), ("vision", "embed"), "scaled"),
                "w2": ParamSpec((D, D), ("embed", None), "scaled"),
            }
        if cfg.family == "audio":
            enc_block = {"ln1": _norm_spec(D), "attn": attn.attention_specs(cfg),
                         "ln2": _norm_spec(D), "ffn": swiglu_specs(D, cfg.d_ff)}
            specs["encoder"] = {
                "in_proj": ParamSpec((cfg.audio_dim, D), ("audio", "embed"), "scaled"),
                "blocks": stack_specs(enc_block, cfg.n_enc_layers),
                "norm": _norm_spec(D),
            }
        if cfg.family == "hybrid":
            specs["shared_attn"] = {
                "ln1": _norm_spec(D), "attn": attn.attention_specs(cfg),
                "ln2": _norm_spec(D), "ffn": swiglu_specs(D, cfg.d_ff),
            }
        return specs

    def init(self, key, dtype=None):
        return init_params(key, self.param_specs(),
                           dtype or self.cfg.pdtype)

    # ------------------------------------------------------------------
    # Input embedding (modality frontends are stubs per DESIGN.md)
    # ------------------------------------------------------------------

    def _embed_inputs(self, params, batch) -> Array:
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = embed(params["embed"], batch["tokens"], cfg.cdtype)
            if cfg.family == "vlm":
                p = params["projector"]
                patches = batch["patches"].astype(cfg.cdtype)  # (B, Np, Dv)
                proj = jax.nn.gelu(patches @ p["w1"].astype(cfg.cdtype))
                proj = proj @ p["w2"].astype(cfg.cdtype)
                x = jnp.concatenate([proj, x], axis=1)         # image prefix
        return x

    def _lm_head(self, params, x: Array) -> Array:
        """Final norm + unembedding (named scope ``lm_head``)."""
        cfg = self.cfg
        with jax.named_scope("lm_head"):
            x = rms_norm(x, params["final_norm"], cfg.norm_eps)
            return unembed(params["embed"], x, cfg.tie_embeddings, cfg.vocab)

    def _attn_mlp_layer(self, layer, x: Array, attend):
        """One dense/vlm/moe decoder layer of the serving paths.
        ``attend(attn_params, normed_x)`` → ``(out, kv)`` is the layer's
        attention (its projections, cache write and kernel carry their own
        scopes); the pre-norm is attributed to ``attn.qkv``, the residual
        to ``attn.out`` and the feed-forward half to ``mlp``."""
        cfg = self.cfg
        with jax.named_scope("attn.qkv"):
            xn = rms_norm(x, layer["ln1"], cfg.norm_eps)
        a, kv = attend(layer["attn"], xn)
        with jax.named_scope("attn.out"):
            h = x + a
        with jax.named_scope("mlp"):
            y = rms_norm(h, layer["ln2"], cfg.norm_eps)
            out = h + (moe_lib.moe_ffn(layer["moe"], y, cfg)
                       if cfg.family == "moe" else swiglu(layer["ffn"], y))
        return out, kv

    def _encode_audio(self, params, frames: Array) -> Array:
        cfg = self.cfg
        enc = params["encoder"]
        x = frames.astype(cfg.cdtype) @ enc["in_proj"].astype(cfg.cdtype)

        def body(h, layer):
            h = h + attn.full_attention(layer["attn"],
                                        rms_norm(h, layer["ln1"], cfg.norm_eps),
                                        cfg, causal=False)
            h = h + swiglu(layer["ffn"], rms_norm(h, layer["ln2"], cfg.norm_eps))
            return h, None

        x, _ = scan_layers(_maybe_remat(body, cfg), x, enc["blocks"], cfg)
        return rms_norm(x, enc["norm"], cfg.norm_eps)

    # ------------------------------------------------------------------
    # Teacher-forced forward (train / eval)
    # ------------------------------------------------------------------

    def forward(self, params, batch, *, use_kernel: bool = False) -> Array:
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        enc_out = None
        if cfg.family == "audio":
            enc_out = self._encode_audio(params, batch["frames"])

        block = self._train_block(use_kernel, enc_out,
                                  params.get("shared_attn"))
        x, _ = scan_layers(_maybe_remat(block, cfg), x, params["blocks"], cfg)
        return self._lm_head(params, x)

    def _train_block(self, use_kernel: bool, enc_out: Optional[Array],
                     shared=None):
        cfg = self.cfg

        if cfg.family in ("dense", "vlm", "moe"):
            def body(x, layer):
                h = x + attn.full_attention(
                    layer["attn"], rms_norm(x, layer["ln1"], cfg.norm_eps),
                    cfg, causal=True, use_kernel=use_kernel)
                y = rms_norm(h, layer["ln2"], cfg.norm_eps)
                if cfg.family == "moe":
                    return h + moe_lib.moe_ffn(layer["moe"], y, cfg), None
                return h + swiglu(layer["ffn"], y), None
            return body

        if cfg.family == "audio":
            def body(x, layer):
                h = x + attn.full_attention(
                    layer["self_attn"], rms_norm(x, layer["ln1"], cfg.norm_eps),
                    cfg, causal=True, use_kernel=use_kernel)
                kv = attn.encode_kv(layer["cross_attn"], enc_out, cfg)
                h = h + attn.cross_attention(
                    layer["cross_attn"], rms_norm(h, layer["ln2"], cfg.norm_eps),
                    kv, cfg)
                return h + swiglu(layer["ffn"],
                                  rms_norm(h, layer["ln3"], cfg.norm_eps)), None
            return body

        if cfg.family == "ssm":
            def body(x, group):
                def m_body(h, m):
                    return h + ssm_lib.mlstm_block(
                        m["core"], rms_norm(h, m["ln"], cfg.norm_eps), cfg,
                        use_kernel=use_kernel), None
                x, _ = scan_layers(
                    m_body, x, {"ln": group["m_ln"], "core": group["mlstm"]}, cfg)
                y, _ = ssm_lib.slstm_scan(
                    group["slstm"], rms_norm(x, group["s_ln"], cfg.norm_eps), cfg)
                return x + y, None
            return body

        if cfg.family == "hybrid":
            def body(x, group):
                def m_body(h, m):
                    return h + ssm_lib.mamba2_block(
                        m["core"], rms_norm(h, m["ln"], cfg.norm_eps), cfg,
                        use_kernel=use_kernel), None
                x, _ = scan_layers(
                    m_body, x, {"ln": group["m_ln"], "core": group["mamba"]}, cfg)
                h = x + attn.full_attention(
                    shared["attn"], rms_norm(x, shared["ln1"], cfg.norm_eps),
                    cfg, causal=True, use_kernel=use_kernel)
                return h + swiglu(shared["ffn"],
                                  rms_norm(h, shared["ln2"], cfg.norm_eps)), None
            return body

        raise ValueError(cfg.family)

    def loss(self, params, batch) -> Tuple[Array, Dict[str, Array]]:
        cfg = self.cfg
        logits = self.forward(params, batch)
        labels = batch["labels"]
        if cfg.family == "vlm":     # image prefix carries no LM loss
            Np = cfg.n_patches
            logits = logits[:, Np:]
        mask = batch.get("loss_mask")
        nll = cross_entropy_loss(logits[:, :-1], labels[:, 1:],
                                 None if mask is None else mask[:, 1:])
        return nll, {"loss": nll}

    # ------------------------------------------------------------------
    # Decode state (KV caches / recurrent states)
    # ------------------------------------------------------------------

    def _cache_struct(self, batch: int, cache_len: int, as_shape: bool):
        """Pytree of zeros (as_shape=False) or ShapeDtypeStructs."""
        cfg = self.cfg
        dt = cfg.cdtype
        L, KV, dh = self.n_groups, cfg.n_kv_heads, cfg.head_dim
        win = cfg.sliding_window
        S_kv = min(cache_len, win) if win > 0 else cache_len
        mk = (lambda s, d=dt: jax.ShapeDtypeStruct(s, d)) if as_shape \
            else (lambda s, d=dt: jnp.zeros(s, d))
        if cfg.family in ("dense", "vlm", "moe"):
            kv = (L, batch, S_kv, KV, dh)
            return {"k": mk(kv), "v": mk(kv)}
        if cfg.family == "audio":
            kv = (L, batch, S_kv, KV, dh)
            xkv = (L, batch, cfg.n_audio_frames, KV, dh)
            return {"k": mk(kv), "v": mk(kv),
                    "xk": mk(xkv), "xv": mk(xkv)}
        if cfg.family == "ssm":
            G, gm = self.n_groups, self.group_m
            m_shape = (G, gm) + ssm_lib.mlstm_state_shape(cfg, batch)
            s_shapes = ssm_lib.slstm_state_shapes(cfg, batch)
            return {"mlstm": mk(m_shape, jnp.float32),
                    "slstm": tuple(mk((G,) + s, jnp.float32)
                                   for s in s_shapes)}
        if cfg.family == "hybrid":
            G, gm = self.n_groups, self.group_m
            ssm_s, conv_s = ssm_lib.mamba2_state_shapes(cfg, batch)
            kv = (G, batch, S_kv, KV, dh)
            return {"ssm": mk((G, gm) + ssm_s, jnp.float32),
                    "conv": mk((G, gm) + conv_s),
                    "k": mk(kv), "v": mk(kv)}
        raise ValueError(cfg.family)

    def init_cache(self, batch: int, cache_len: int):
        return self._cache_struct(batch, cache_len, as_shape=False)

    def cache_shapes(self, batch: int, cache_len: int):
        return self._cache_struct(batch, cache_len, as_shape=True)

    @property
    def prefix_cacheable(self) -> bool:
        """True when a prompt's pool-resident KV fully determines its
        decode state, so the radix prefix cache may splice cached blocks
        into a new request's block table and skip prefilling those
        positions. Attention-only decode state qualifies (dense/moe/vlm;
        audio's cross-attention KV is recomputed per request from the
        frames, independent of decoder positions). Recurrent families
        (ssm, hybrid) carry state that accumulates across EVERY prompt
        position outside the pool — skipping a cached prefix would
        silently corrupt it — so they take the direct (uncached) path."""
        return self.cfg.family not in ("ssm", "hybrid")

    @property
    def speculative_capable(self) -> bool:
        """True when a multi-token verify span can be ROLLED BACK by
        position: rejecting a draft must leave the decode state exactly
        as if the rejected positions were never fed. Paged attention KV
        qualifies — rejected-tail writes sit at positions the causal mask
        hides, and the next span overwrites them before anything attends
        there. Recurrent families (ssm, hybrid) fold every fed token into
        a running state that cannot be positionally unwound, and
        sliding-window (ring) caches overwrite live slots when the span
        wraps — both degrade to the vanilla one-token step instead (the
        scheduler consults this flag; speculation is a pure optimization,
        so degrading costs correctness nothing)."""
        return self.cfg.family not in ("ssm", "hybrid") \
            and self.cfg.sliding_window <= 0

    def cache_spec(self, block_size: int = 0) -> CacheSpec:
        """Batch-axis descriptor matching ``_cache_struct``'s layouts.

        With ``block_size > 0`` the spec also carries the paged layout:
        attention KV leaves page through a block pool; recurrent states and
        enc-dec cross-attention KV (written once, fixed extent) stay on the
        direct per-slot path (seq axis ``-1``).

        Memoized per ``block_size``: every server built on this model gets
        the SAME spec object, so the spec's jitted splice functions
        (``insert``/``insert_paged``/``take``) compile once per model
        instead of once per server.
        """
        memo = self.__dict__.setdefault("_cache_spec_memo", {})
        if block_size in memo:
            return memo[block_size]
        cfg = self.cfg
        if cfg.family in ("dense", "vlm", "moe"):
            axes = {"k": 1, "v": 1}
            seq = {"k": 2, "v": 2}
        elif cfg.family == "audio":
            axes = {"k": 1, "v": 1, "xk": 1, "xv": 1}
            seq = {"k": 2, "v": 2, "xk": -1, "xv": -1}
        elif cfg.family == "ssm":
            n_slstm = len(ssm_lib.slstm_state_shapes(cfg, 1))
            axes = {"mlstm": 2, "slstm": tuple(1 for _ in range(n_slstm))}
            seq = {"mlstm": -1, "slstm": tuple(-1 for _ in range(n_slstm))}
        elif cfg.family == "hybrid":
            axes = {"ssm": 2, "conv": 2, "k": 1, "v": 1}
            seq = {"ssm": -1, "conv": -1, "k": 2, "v": 2}
        else:
            raise ValueError(cfg.family)
        paged = PagedLayout(block_size, seq) if block_size > 0 else None
        memo[block_size] = CacheSpec(axes, paged)
        return memo[block_size]

    def _paged_cache_struct(self, n_slots: int, n_blocks: int,
                            block_size: int, cache_len: int, as_shape: bool):
        """Paged decode cache: pool leaves turn their batch axis into
        n_blocks and move the in-block offset (block_size) in front of the
        head dim (``PagedLayout``) — one shared pool addressed through
        per-slot block tables; direct leaves keep their n_slots rows."""
        base = self._cache_struct(n_slots, cache_len, as_shape=True)
        spec = self.cache_spec(block_size)

        def one(s, b_ax, s_ax):
            if s_ax < 0:
                return s
            shape = s.shape[:b_ax] + (n_blocks,) + s.shape[s_ax + 1:-1] \
                + (block_size,) + s.shape[-1:]
            return jax.ShapeDtypeStruct(shape, s.dtype)

        shapes = jax.tree.map(one, base, spec.batch_axes,
                              spec.paged.seq_axes)
        if as_shape:
            return shapes
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

    def init_paged_cache(self, n_slots: int, n_blocks: int, block_size: int,
                         cache_len: int):
        return self._paged_cache_struct(n_slots, n_blocks, block_size,
                                        cache_len, as_shape=False)

    def paged_cache_shapes(self, n_slots: int, n_blocks: int,
                           block_size: int, cache_len: int):
        return self._paged_cache_struct(n_slots, n_blocks, block_size,
                                        cache_len, as_shape=True)

    # ------------------------------------------------------------------
    # Prefill: full sequence forward + decode state construction
    # ------------------------------------------------------------------

    def prefill(self, params, batch, cache_len: int, *,
                use_kernel: bool = False):
        """Returns (logits (B,S,V), cache). For windowed configs the cache
        holds the last ``window`` positions (ring layout, slot = pos % win)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        B, S, _ = x.shape
        win = cfg.sliding_window
        S_kv = min(cache_len, win) if win > 0 else cache_len

        def pad_kv(k):
            """(B,S,KV,dh) → ring/right-padded (B,S_kv,KV,dh)."""
            if win > 0 and S >= S_kv:
                tail = k[:, S - S_kv:]
                # ring layout: slot = pos % S_kv
                start = (S - S_kv) % S_kv
                return jnp.roll(tail, start, axis=1)
            return jnp.pad(k, [(0, 0), (0, S_kv - S), (0, 0), (0, 0)])

        if cfg.family in ("dense", "vlm", "moe"):
            def body(x, layer):
                h_in = rms_norm(x, layer["ln1"], cfg.norm_eps)
                a, (k, v) = attn.prefill_attention(layer["attn"], h_in, cfg, S,
                                                   use_kernel=use_kernel)
                h = x + a
                y = rms_norm(h, layer["ln2"], cfg.norm_eps)
                out = h + (moe_lib.moe_ffn(layer["moe"], y, cfg)
                           if cfg.family == "moe" else swiglu(layer["ffn"], y))
                return out, (pad_kv(k[:, :S]), pad_kv(v[:, :S]))
            x, (ks, vs) = scan_layers(body, x, params["blocks"], cfg)
            cache = {"k": ks, "v": vs}

        elif cfg.family == "audio":
            enc_out = self._encode_audio(params, batch["frames"])

            def body(x, layer):
                h_in = rms_norm(x, layer["ln1"], cfg.norm_eps)
                a, (k, v) = attn.prefill_attention(layer["self_attn"], h_in,
                                                   cfg, S, use_kernel=use_kernel)
                h = x + a
                xkv = attn.encode_kv(layer["cross_attn"], enc_out, cfg)
                h = h + attn.cross_attention(
                    layer["cross_attn"], rms_norm(h, layer["ln2"], cfg.norm_eps),
                    xkv, cfg)
                out = h + swiglu(layer["ffn"],
                                 rms_norm(h, layer["ln3"], cfg.norm_eps))
                return out, (pad_kv(k[:, :S]), pad_kv(v[:, :S]),
                             xkv[0], xkv[1])
            x, (ks, vs, xks, xvs) = scan_layers(body, x, params["blocks"], cfg)
            cache = {"k": ks, "v": vs, "xk": xks, "xv": xvs}

        elif cfg.family == "ssm":
            def body(x, group):
                def m_body(h, m):
                    q, k, v, log_f, z = ssm_lib._mlstm_qkvg(
                        m["core"], rms_norm(h, m["ln"], cfg.norm_eps), cfg)
                    v_ext = jnp.concatenate([v, jnp.ones_like(v[..., :1])], -1)
                    y, st = ssm_lib.chunked_linear_attention(
                        q, k, v_ext, log_f, cfg.ssm.chunk,
                        use_kernel=use_kernel)
                    num, den = y[..., :-1], y[..., -1:]
                    hh = (num / (jnp.abs(den) + 1.0)).reshape(B, S, -1)
                    hh = rms_norm(hh, m["core"]["norm"], cfg.norm_eps) \
                        * jax.nn.silu(z)
                    return h + hh @ m["core"]["w_out"].astype(h.dtype), st
                x, m_states = scan_layers(
                    m_body, x, {"ln": group["m_ln"], "core": group["mlstm"]}, cfg)
                y, s_state = ssm_lib.slstm_scan(
                    group["slstm"], rms_norm(x, group["s_ln"], cfg.norm_eps), cfg)
                return x + y, (m_states, s_state)
            x, (m_states, s_states) = scan_layers(body, x, params["blocks"], cfg)
            cache = {"mlstm": m_states, "slstm": s_states}

        elif cfg.family == "hybrid":
            shared = params["shared_attn"]

            def body(x, group):
                def m_body(h, m):
                    y, st = self._mamba2_prefill(m["core"],
                                                 rms_norm(h, m["ln"],
                                                          cfg.norm_eps),
                                                 use_kernel)
                    return h + y, st
                x, m_states = scan_layers(
                    m_body, x, {"ln": group["m_ln"], "core": group["mamba"]}, cfg)
                h_in = rms_norm(x, shared["ln1"], cfg.norm_eps)
                a, (k, v) = attn.prefill_attention(shared["attn"], h_in, cfg, S,
                                                   use_kernel=use_kernel)
                h = x + a
                out = h + swiglu(shared["ffn"],
                                 rms_norm(h, shared["ln2"], cfg.norm_eps))
                return out, (m_states, pad_kv(k[:, :S]), pad_kv(v[:, :S]))
            x, (m_states, ks, vs) = scan_layers(body, x, params["blocks"], cfg)
            cache = {"ssm": m_states[0], "conv": m_states[1],
                     "k": ks, "v": vs}
        else:
            raise ValueError(cfg.family)

        logits = self._lm_head(params, x)
        return logits, cache

    # ------------------------------------------------------------------
    # Chunked prefill: consume a prompt in fixed-size chunks
    # ------------------------------------------------------------------

    def embed_prompt(self, params, batch) -> Array:
        """Embedded decoder inputs for chunked prefill: token embeddings
        plus any modality prefix (VLM image projection). (1, W, D)."""
        return self._embed_inputs(params, batch)

    def init_chunk_carry(self, params, batch, cache_len: int):
        """Per-request carry threaded between prefill chunks: the DIRECT
        (non-pool) decode-state leaves at batch extent 1, at their true
        initial values. Pool leaves get (1,)-shaped placeholders — their
        chunk writes go straight into the shared block pool. Audio computes
        its cross-attention KV here, once per request instead of per chunk.
        """
        cfg = self.cfg
        dummy = jnp.zeros((1,), cfg.cdtype)
        if cfg.family in ("dense", "vlm", "moe"):
            return {"k": dummy, "v": dummy}
        if cfg.family == "audio":
            enc_out = self._encode_audio(params, batch["frames"])

            def body(c, layer):
                return c, attn.encode_kv(layer["cross_attn"], enc_out, cfg)

            _, (xks, xvs) = scan_layers(body, 0, params["blocks"], cfg)
            return {"k": dummy, "v": dummy, "xk": xks, "xv": xvs}
        if cfg.family == "ssm":
            G, gm = self.n_groups, self.group_m
            s_shapes = ssm_lib.slstm_state_shapes(cfg, 1)
            slstm = [jnp.zeros((G,) + s, jnp.float32) for s in s_shapes]
            slstm[2] = jnp.full((G,) + s_shapes[2], -1e30, jnp.float32)
            return {"mlstm": jnp.zeros(
                        (G, gm) + ssm_lib.mlstm_state_shape(cfg, 1),
                        jnp.float32),
                    "slstm": tuple(slstm)}
        if cfg.family == "hybrid":
            G, gm = self.n_groups, self.group_m
            ssm_s, conv_s = ssm_lib.mamba2_state_shapes(cfg, 1)
            return {"ssm": jnp.zeros((G, gm) + ssm_s, jnp.float32),
                    "conv": jnp.zeros((G, gm) + conv_s, cfg.cdtype),
                    "k": dummy, "v": dummy}
        raise ValueError(cfg.family)

    def prefill_chunk(self, params, cache, carry, x: Array, start: Array,
                      length: Array, block_table: Array, *,
                      use_kernel: bool = False):
        """Consume one chunk of a prompt. x: (1, C, D) embedded inputs
        (``embed_prompt`` output sliced at ``start``, right-padded to C);
        start: () int32 absolute position of chunk row 0; length: () int32
        valid rows; block_table: (NB,) int32 — this request's block map
        (unused by families without pageable leaves).

        Attention KV leaves are written straight into the paged pool
        (``attn.chunk_attention``) and attend over the previously-inserted
        blocks; recurrent / conv / cross-attention state flows through
        ``carry``. Returns (last_logits (1, V) — the greedy next-token
        distribution at the chunk's final valid position — new_carry,
        new_cache). Padded rows are exact no-ops on carry, and on the pool
        write only positions past the prompt, which decode writes before
        any query reads them. ``start`` is a multiple of the block size
        whenever C is (a prompt's chunks start at its cached prefix, whole
        blocks, plus whole chunks).
        """
        cfg = self.cfg
        C = x.shape[1]

        if cfg.family in ("dense", "vlm", "moe"):
            def body(c, layer, li):
                xh, pool = c
                return self._attn_mlp_layer(
                    layer, xh, lambda p, xn: attn.chunk_attention(
                        p, xn, cfg, pool, li, start, length, block_table,
                        use_kernel=use_kernel)), None
            (x, (ks, vs)), _ = scan_layers(
                body, (x, (cache["k"], cache["v"])), params["blocks"], cfg,
                indexed=True)
            new_cache = {"k": ks, "v": vs}
            new_carry = carry

        elif cfg.family == "audio":
            def body(c, layer_and_x, li):
                xh, pool = c
                layer, (xk, xv) = layer_and_x
                a, pool = attn.chunk_attention(
                    layer["self_attn"],
                    rms_norm(xh, layer["ln1"], cfg.norm_eps),
                    cfg, pool, li, start, length, block_table,
                    use_kernel=use_kernel)
                h = xh + a
                h = h + attn.cross_attention(
                    layer["cross_attn"],
                    rms_norm(h, layer["ln2"], cfg.norm_eps), (xk, xv), cfg)
                out = h + swiglu(layer["ffn"],
                                 rms_norm(h, layer["ln3"], cfg.norm_eps))
                return (out, pool), None
            (x, (ks, vs)), _ = scan_layers(
                body, (x, (cache["k"], cache["v"])),
                (params["blocks"], (carry["xk"], carry["xv"])), cfg,
                indexed=True)
            new_cache = {"k": ks, "v": vs,
                         "xk": cache["xk"], "xv": cache["xv"]}
            new_carry = carry

        elif cfg.family == "ssm":
            valid = jnp.arange(C) < length

            def body(xh, group_and_state):
                group, (m_st, s_st) = group_and_state

                def m_body(h, mc):
                    m, st = mc
                    q, k, v, log_f, z = ssm_lib._mlstm_qkvg(
                        m["core"], rms_norm(h, m["ln"], cfg.norm_eps), cfg)
                    k = k * valid[None, :, None, None].astype(k.dtype)
                    log_f = jnp.where(valid[None, :, None], log_f, 0.0)
                    v_ext = jnp.concatenate(
                        [v, jnp.ones_like(v[..., :1])], -1)
                    y, st = ssm_lib.chunked_linear_attention(
                        q, k, v_ext, log_f, cfg.ssm.chunk, state=st,
                        use_kernel=use_kernel)
                    num, den = y[..., :-1], y[..., -1:]
                    hh = (num / (jnp.abs(den) + 1.0)).reshape(1, C, -1)
                    hh = rms_norm(hh, m["core"]["norm"], cfg.norm_eps) \
                        * jax.nn.silu(z)
                    return h + hh @ m["core"]["w_out"].astype(h.dtype), st
                xh, m_st = scan_layers(
                    m_body, xh,
                    ({"ln": group["m_ln"], "core": group["mlstm"]}, m_st),
                    cfg)
                y, s_st = ssm_lib.slstm_scan(
                    group["slstm"], rms_norm(xh, group["s_ln"], cfg.norm_eps),
                    cfg, state=s_st, length=length)
                return xh + y, (m_st, s_st)
            x, (m_states, s_states) = scan_layers(
                body, x, (params["blocks"],
                          (carry["mlstm"], carry["slstm"])), cfg)
            new_carry = {"mlstm": m_states, "slstm": s_states}
            new_cache = cache

        elif cfg.family == "hybrid":
            shared = params["shared_attn"]

            def body(c, group_and_st, gi):
                xh, pool = c
                group, (ssm_st, conv_st) = group_and_st

                def m_body(h, mc):
                    m, st = mc
                    y, st = self._mamba2_chunk(
                        m["core"], rms_norm(h, m["ln"], cfg.norm_eps), st,
                        length, use_kernel)
                    return h + y, st
                xh, (ssm_st, conv_st) = scan_layers(
                    m_body, xh,
                    ({"ln": group["m_ln"], "core": group["mamba"]},
                     (ssm_st, conv_st)), cfg)
                a, pool = attn.chunk_attention(
                    shared["attn"], rms_norm(xh, shared["ln1"], cfg.norm_eps),
                    cfg, pool, gi, start, length, block_table,
                    use_kernel=use_kernel)
                h = xh + a
                out = h + swiglu(shared["ffn"],
                                 rms_norm(h, shared["ln2"], cfg.norm_eps))
                return (out, pool), (ssm_st, conv_st)
            (x, (ks, vs)), (ssm_s, conv_s) = scan_layers(
                body, (x, (cache["k"], cache["v"])),
                (params["blocks"], (carry["ssm"], carry["conv"])), cfg,
                indexed=True)
            new_carry = {"ssm": ssm_s, "conv": conv_s,
                         "k": carry["k"], "v": carry["v"]}
            new_cache = {"ssm": cache["ssm"], "conv": cache["conv"],
                         "k": ks, "v": vs}
        else:
            raise ValueError(cfg.family)

        with jax.named_scope("lm_head"):
            x = rms_norm(x, params["final_norm"], cfg.norm_eps)
            h_last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
            logits = unembed(params["embed"], h_last, cfg.tie_embeddings,
                             cfg.vocab)
        return logits[:, 0], new_carry, new_cache

    def _mamba2_chunk(self, p, x, state, length, use_kernel):
        """``_mamba2_prefill`` with an inter-chunk carry: the conv window
        and SSM state flow in from the previous chunk, and padded positions
        (≥ length) are exact no-ops on both (dt → 0 ⇒ zero k and unit
        decay; the conv carry is sliced at the valid end)."""
        cfg = self.cfg
        ssm_state, conv_carry = state
        xs, z, Bm, Cm, dt_raw, (B, S, Di, N, H, P) = \
            ssm_lib._mamba2_inner(p, x, cfg)
        conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
        W = p["conv_w"].shape[0]
        conv_out, _ = ssm_lib._causal_conv(
            conv_in, p["conv_w"].astype(x.dtype), conv_carry)
        if W > 1:
            ext = jnp.concatenate([conv_carry, conv_in], axis=1)
            conv_carry = jax.lax.dynamic_slice_in_dim(ext, length, W - 1,
                                                      axis=1)
        xs, Bm, Cm = jnp.split(conv_out, [Di, Di + N], axis=-1)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) +
                             p["dt_bias"].astype(jnp.float32))
        dt = jnp.where((jnp.arange(S) < length)[None, :, None], dt, 0.0)
        A = -jnp.exp(p["A_log"].astype(jnp.float32))
        log_g = dt * A[None, None, :]
        q = jnp.broadcast_to(Cm[:, :, None, :], (B, S, H, N))
        k = jnp.broadcast_to(Bm[:, :, None, :], (B, S, H, N)) * \
            dt[..., None].astype(x.dtype)
        v = xs.reshape(B, S, H, P)
        y, st = ssm_lib.chunked_linear_attention(q, k, v, log_g,
                                                 cfg.ssm.chunk,
                                                 state=ssm_state,
                                                 use_kernel=use_kernel)
        y = y + p["D_skip"].astype(x.dtype)[None, None, :, None] * v
        y = y.reshape(B, S, Di) * jax.nn.silu(z)
        y = rms_norm(y, p["norm"], cfg.norm_eps)
        return y @ p["w_out"].astype(x.dtype), (st, conv_carry)

    def _mamba2_prefill(self, p, x, use_kernel):
        """mamba2_block that also returns (ssm_state, conv_carry)."""
        cfg = self.cfg
        xs, z, Bm, Cm, dt_raw, (B, S, Di, N, H, P) = \
            ssm_lib._mamba2_inner(p, x, cfg)
        conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
        conv_out, conv_carry = ssm_lib._causal_conv(
            conv_in, p["conv_w"].astype(x.dtype))
        W = p["conv_w"].shape[0]
        conv_carry = conv_in[:, -(W - 1):] if W > 1 else conv_carry
        xs, Bm, Cm = jnp.split(conv_out, [Di, Di + N], axis=-1)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) +
                             p["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(p["A_log"].astype(jnp.float32))
        log_g = dt * A[None, None, :]
        q = jnp.broadcast_to(Cm[:, :, None, :], (B, S, H, N))
        k = jnp.broadcast_to(Bm[:, :, None, :], (B, S, H, N)) * \
            dt[..., None].astype(x.dtype)
        v = xs.reshape(B, S, H, P)
        y, st = ssm_lib.chunked_linear_attention(q, k, v, log_g, cfg.ssm.chunk,
                                                 use_kernel=use_kernel)
        y = y + p["D_skip"].astype(x.dtype)[None, None, :, None] * v
        y = y.reshape(B, S, Di) * jax.nn.silu(z)
        y = rms_norm(y, p["norm"], cfg.norm_eps)
        return y @ p["w_out"].astype(x.dtype), (st, conv_carry)

    # ------------------------------------------------------------------
    # Decode: ONE new token (serve_step body)
    # ------------------------------------------------------------------

    def decode_step(self, params, cache, tokens: Array, pos: Array, *,
                    use_kernel: bool = False):
        """tokens: (B,) int32; pos: () int32 current position. Returns
        (logits (B, V), new cache)."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = embed(params["embed"], tokens[:, None], cfg.cdtype)  # (B,1,D)

        if cfg.family in ("dense", "vlm", "moe"):
            def body(x, layer_and_cache):
                layer, kv = layer_and_cache
                return self._attn_mlp_layer(
                    layer, x, lambda p, xn: attn.decode_attention(
                        p, xn, cfg, kv, pos, use_kernel=use_kernel))
            x, (ks, vs) = scan_layers(
                body, x, (params["blocks"], (cache["k"], cache["v"])), cfg)
            new_cache = {"k": ks, "v": vs}

        elif cfg.family == "audio":
            def body(x, layer_and_cache):
                layer, (k, v, xk, xv) = layer_and_cache
                a, kv = attn.decode_attention(
                    layer["self_attn"], rms_norm(x, layer["ln1"], cfg.norm_eps),
                    cfg, (k, v), pos, use_kernel=use_kernel)
                h = x + a
                h = h + attn.cross_attention(
                    layer["cross_attn"], rms_norm(h, layer["ln2"], cfg.norm_eps),
                    (xk, xv), cfg)
                out = h + swiglu(layer["ffn"],
                                 rms_norm(h, layer["ln3"], cfg.norm_eps))
                return out, kv + (xk, xv)
            x, (ks, vs, xks, xvs) = scan_layers(
                body, x, (params["blocks"],
                          (cache["k"], cache["v"], cache["xk"], cache["xv"])), cfg)
            new_cache = {"k": ks, "v": vs, "xk": xks, "xv": xvs}

        elif cfg.family == "ssm":
            def body(x, group_and_cache):
                group, (m_st, s_st) = group_and_cache
                def m_body(h, mc):
                    m, st = mc
                    y, st = ssm_lib.mlstm_step(
                        m["core"], rms_norm(h, m["ln"], cfg.norm_eps), cfg, st)
                    return h + y, st
                x, m_st = scan_layers(
                    m_body, x,
                    (({"ln": group["m_ln"], "core": group["mlstm"]}), m_st), cfg)
                y, s_st = ssm_lib.slstm_scan(
                    group["slstm"], rms_norm(x, group["s_ln"], cfg.norm_eps),
                    cfg, state=s_st)
                return x + y, (m_st, s_st)
            x, (m_states, s_states) = scan_layers(
                body, x, (params["blocks"],
                          (cache["mlstm"], cache["slstm"])), cfg)
            new_cache = {"mlstm": m_states, "slstm": s_states}

        elif cfg.family == "hybrid":
            shared = params["shared_attn"]

            def body(x, group_and_cache):
                group, (ssm_st, conv_st, k, v) = group_and_cache
                def m_body(h, mc):
                    m, st = mc
                    y, st = ssm_lib.mamba2_step(
                        m["core"], rms_norm(h, m["ln"], cfg.norm_eps), cfg, st)
                    return h + y, st
                x, (ssm_st, conv_st) = scan_layers(
                    m_body, x, ({"ln": group["m_ln"], "core": group["mamba"]},
                                (ssm_st, conv_st)), cfg)
                a, kv = attn.decode_attention(
                    shared["attn"], rms_norm(x, shared["ln1"], cfg.norm_eps),
                    cfg, (k, v), pos, use_kernel=use_kernel)
                h = x + a
                out = h + swiglu(shared["ffn"],
                                 rms_norm(h, shared["ln2"], cfg.norm_eps))
                return out, (ssm_st, conv_st) + kv
            x, (ssm_s, conv_s, ks, vs) = scan_layers(
                body, x, (params["blocks"],
                          (cache["ssm"], cache["conv"],
                           cache["k"], cache["v"])), cfg)
            new_cache = {"ssm": ssm_s, "conv": conv_s, "k": ks, "v": vs}
        else:
            raise ValueError(cfg.family)

        logits = self._lm_head(params, x)
        return logits[:, 0], new_cache

    def decode_step_paged(self, params, cache, tokens: Array, pos: Array,
                          block_tables: Array, *, use_kernel: bool = False):
        """One-token decode against the paged cache. tokens: (B,) int32;
        pos: (B,) int32 per-slot positions; block_tables: (B, NB) int32
        logical-block → physical-pool-block maps (one table per slot,
        shared by every attention layer). Attention KV leaves are written
        in place in the layer-stacked pool the layer loop carries
        (``_paged_layers``); recurrent and cross-attention leaves run the
        direct path unchanged."""
        cfg = self.cfg
        if cfg.family == "ssm":       # no pageable leaves: direct path
            return self.decode_step(params, cache, tokens, pos,
                                    use_kernel=use_kernel)
        with jax.named_scope("embed"):
            x = embed(params["embed"], tokens[:, None], cfg.cdtype)  # (B,1,D)

        def attend(p, xn, pool, li):
            return attn.paged_decode_attention(
                p, xn, cfg, pool, li, pos, block_tables,
                use_kernel=use_kernel)
        logits, cache = self._paged_layers(params, cache, x, attend)
        return logits[:, 0], cache

    def _paged_layers(self, params, cache, x: Array, attend):
        """The layer loop of the paged serving steps (decode and verify):
        ``attend(attn_params, normed_x, pool, layer)`` → ``(out, pool)``
        is the layer's self-attention against the layer-stacked pool
        (``(k, v)``), which rides the scan carry whole and is updated in
        place; direct state (audio's cross K/V, hybrid's conv/SSM) is
        scanned as before. Returns (logits at every row, new cache)."""
        cfg = self.cfg
        pool = (cache["k"], cache["v"])
        if cfg.family in ("dense", "vlm", "moe"):
            def body(c, layer, li):
                x, pool = c
                return self._attn_mlp_layer(
                    layer, x, lambda p, xn: attend(p, xn, pool, li)), None
            (x, (ks, vs)), _ = scan_layers(body, (x, pool),
                                           params["blocks"], cfg,
                                           indexed=True)
            new_cache = {"k": ks, "v": vs}

        elif cfg.family == "audio":
            def body(c, layer_and_x, li):
                x, pool = c
                layer, (xk, xv) = layer_and_x
                a, pool = attend(layer["self_attn"],
                                 rms_norm(x, layer["ln1"], cfg.norm_eps),
                                 pool, li)
                h = x + a
                h = h + attn.cross_attention(
                    layer["cross_attn"], rms_norm(h, layer["ln2"],
                                                  cfg.norm_eps),
                    (xk, xv), cfg)
                out = h + swiglu(layer["ffn"],
                                 rms_norm(h, layer["ln3"], cfg.norm_eps))
                return (out, pool), None
            (x, (ks, vs)), _ = scan_layers(
                body, (x, pool), (params["blocks"],
                                  (cache["xk"], cache["xv"])), cfg,
                indexed=True)
            new_cache = {"k": ks, "v": vs, "xk": cache["xk"],
                         "xv": cache["xv"]}

        elif cfg.family == "hybrid":
            shared = params["shared_attn"]

            def body(c, group_and_st, gi):
                x, pool = c
                group, (ssm_st, conv_st) = group_and_st

                def m_body(h, mc):
                    m, st = mc
                    y, st = ssm_lib.mamba2_step(
                        m["core"], rms_norm(h, m["ln"], cfg.norm_eps), cfg,
                        st)
                    return h + y, st
                x, (ssm_st, conv_st) = scan_layers(
                    m_body, x, ({"ln": group["m_ln"], "core": group["mamba"]},
                                (ssm_st, conv_st)), cfg)
                a, pool = attend(shared["attn"],
                                 rms_norm(x, shared["ln1"], cfg.norm_eps),
                                 pool, gi)
                h = x + a
                out = h + swiglu(shared["ffn"],
                                 rms_norm(h, shared["ln2"], cfg.norm_eps))
                return (out, pool), (ssm_st, conv_st)
            (x, (ks, vs)), (ssm_s, conv_s) = scan_layers(
                body, (x, pool), (params["blocks"],
                                  (cache["ssm"], cache["conv"])), cfg,
                indexed=True)
            new_cache = {"ssm": ssm_s, "conv": conv_s, "k": ks, "v": vs}
        else:
            raise ValueError(cfg.family)

        return self._lm_head(params, x), new_cache

    def verify_step_paged(self, params, cache, tokens: Array, pos: Array,
                          block_tables: Array, *, use_kernel: bool = False):
        """Speculative span verify against the paged cache: score L
        candidate positions per slot in ONE forward. tokens: (B, L) int32
        — column 0 is each slot's committed next token, columns 1..L-1
        its draft tokens; pos: (B,) int32 the position column 0 writes
        at; block_tables: (B, NB). Returns (logits (B, L, V), new cache):
        logits row j is the next-token distribution AFTER feeding tokens
        0..j, i.e. what a vanilla ``decode_step_paged`` at position
        ``pos + j`` would have produced had drafts 0..j-1 been committed.

        Only speculation-capable families run here (see
        ``speculative_capable``) — the span's K/V writes are rolled back
        by overwrite, which recurrent state cannot do."""
        cfg = self.cfg
        if not self.speculative_capable:
            raise ValueError(
                f"family '{cfg.family}' (window={cfg.sliding_window}) "
                "cannot verify speculative spans — check "
                "speculative_capable before dispatching")
        with jax.named_scope("embed"):
            x = embed(params["embed"], tokens, cfg.cdtype)       # (B,L,D)

        def attend(p, xn, pool, li):
            return attn.paged_verify_attention(
                p, xn, cfg, pool, li, pos, block_tables,
                use_kernel=use_kernel)
        return self._paged_layers(params, cache, x, attend)

    def fused_verify_step(self, params, cache, state, drafts: Array, *,
                          cache_len: int, use_kernel: bool = False):
        """One WHOLE speculative step as a single traceable computation:
        the span verify forward over ``[committed token, drafts]``
        followed by the accept/reject epilogue (deterministic token-match
        against the seeded stream, per-offset stop/budget/context checks,
        variable-length position advance) from ``repro.serve.fused``.

        drafts: (B, L-1) int32 draft tokens per slot. Returns
        ``(new_cache, new_state, toks, n_emit, done)`` — the host reads
        back the ``(toks, n_emit, done)`` triple in one ``device_get``.
        """
        # function-level import: repro.serve pulls in the schedulers, which
        # import this module — the epilogue itself is a leaf
        from repro.serve.fused import verify_epilogue
        tokens = jnp.concatenate([state["tok"][:, None], drafts], axis=1)
        scores, cache = self.verify_step_paged(
            params, cache, tokens, state["pos"], state["tables"],
            use_kernel=use_kernel)
        state, toks, n_emit, done = verify_epilogue(
            scores, drafts, state, cache_len=cache_len)
        return cache, state, toks, n_emit, done

    def fused_decode_step(self, params, cache, state, *, cache_len: int,
                          use_kernel: bool = False, paged: bool = False):
        """One WHOLE decode token as a single traceable computation: the
        forward (contiguous or paged — ``state["tables"]`` carries the
        per-slot block tables when paged) followed by the serving epilogue
        (seeded ``sample_tokens``, stop/eos ids, budget and context-bound
        checks, position advance) from ``repro.serve.fused``.

        ``state`` is the scheduler's per-slot device-state dict; returns
        ``(new_cache, new_state, next_tok, done)`` where ``done`` is the
        per-slot ``DONE_REASONS`` bitmap the host reads back instead of
        inspecting tokens per slot.
        """
        # function-level import: repro.serve pulls in the schedulers, which
        # import this module — the epilogue itself is a leaf
        from repro.serve.fused import decode_epilogue
        if paged:
            scores, cache = self.decode_step_paged(
                params, cache, state["tok"], state["pos"], state["tables"],
                use_kernel=use_kernel)
        else:
            scores, cache = self.decode_step(params, cache, state["tok"],
                                             state["pos"],
                                             use_kernel=use_kernel)
        state, nxt, done = decode_epilogue(scores, state,
                                           cache_len=cache_len)
        return cache, state, nxt, done


def build_model(cfg: ModelConfig) -> Model:
    m = Model(cfg)
    return m
