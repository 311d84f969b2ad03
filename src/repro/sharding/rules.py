"""Logical-axis sharding rules (the distribution configuration).

Meshes (launch/mesh.py):
    single-pod : (16, 16)      axes ("data", "model")
    multi-pod  : (2, 16, 16)   axes ("pod", "data", "model")

Two training modes:

* ``dense`` (the paper's centralized baseline): one model; batch and FSDP
  shard over (pod, data) — gradient all-reduce and FSDP all-gathers CROSS
  the pod boundary. This is the cost the paper's scheme removes.
* ``decentralized`` (the paper's scheme): K experts stacked on a leading
  ``dexpert`` dim sharded over ``pod``. Every collective's replica group
  stays inside one pod — the lowered HLO contains no cross-pod collective
  (launch/roofline.py verifies this from the compiled text).

Tensor parallelism (``model`` axis) rules are shared: vocab/heads/ffn/expert
dims shard over ``model``; kv_heads fall back to replicated when the head
count does not divide the axis (e.g. llama3 kv=8 on model=16).
"""
from __future__ import annotations

from typing import Dict, Tuple

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def logical_rules(*, multi_pod: bool, decentralized: bool,
                  fsdp: bool = True) -> Dict[str, object]:
    """Logical axis name → mesh axis (or tuple) mapping."""
    if decentralized:
        fsdp_axes = ("data",)          # pod is the expert axis
    else:
        fsdp_axes = ("pod", "data") if multi_pod else ("data",)
    rules: Dict[str, object] = {
        # ---- parameter axes
        "vocab": "model",
        "embed": fsdp_axes if fsdp else None,    # ZeRO-3-style weight shard
        "mlp": "model",
        "expert_mlp": None,
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "expert": "model",                        # MoE expert parallelism
        "inner": "model",
        "inner_qkv": "model",
        "vision": None,
        "audio": None,
        "layer": None,                            # scanned dim, never sharded
        # ---- decentralized expert stacking dim
        "dexpert": "pod" if (multi_pod and decentralized) else None,
        # ---- activation/batch axes
        "act_batch": (("pod", "data") if (multi_pod and not decentralized)
                      else ("data",)),
        "act_seq": None,
        "act_embed": None,
        "act_heads": "model",
        "act_vocab": "model",
        "kv_cache_batch": (("pod", "data") if (multi_pod and not decentralized)
                           else ("data",)),
        "kv_cache_heads": "model",
    }
    return rules


def batch_pspec(rules) -> P:
    return P(rules["act_batch"])


def data_shardings(rules, mesh: Mesh, cfg, kind: str,
                   decentralized_k: int = 0) -> Dict[str, NamedSharding]:
    """Shardings for the input batch pytree (tokens/labels/patches/frames).

    decentralized_k > 0 prepends the expert dim (sharded over pod).
    """
    lead: Tuple = (rules["dexpert"],) if decentralized_k else ()
    b = rules["act_batch"]

    def ns(*axes):
        return NamedSharding(mesh, P(*lead, *axes))

    shardings = {"tokens": ns(b, None), "labels": ns(b, None)}
    if cfg.family == "vlm":
        shardings["patches"] = ns(b, None, None)
    if cfg.family == "audio":
        shardings["frames"] = ns(b, None, None)
    return shardings


def stacked_cache_pspec_tree(stacked_cache_shapes, rules, mesh: Mesh,
                             seq_axes=None):
    """Shardings for the stacked-expert decode core's cache: every leaf
    carries the K (``dexpert``) dim at axis 1 — after its scan dim, the
    transpose-free layout of ``core/ensemble.stack_experts_for_decode`` —
    sharded over ``pod`` under the decentralized rules, with the per-expert
    remainder placed exactly as ``cache_pspec_tree`` places the unstacked
    cache. This makes the vmapped mixture ``decode_step`` one SPMD op whose
    expert slices stay on their own pods (the serving analogue of
    zero-communication training).

    Pass ``seq_axes`` — the UNSTACKED ``CacheSpec.paged.seq_axes`` pytree —
    when the stacked cache is the paged layout, so pool leaves get their
    block-pool placement."""
    import jax

    def strip(s):
        return jax.ShapeDtypeStruct(s.shape[:1] + s.shape[2:], s.dtype)

    stripped = jax.tree.map(strip, stacked_cache_shapes)
    if seq_axes is None:
        inner = cache_pspec_tree(stripped, rules, mesh)
    else:
        inner = paged_pool_pspec_tree(stripped, rules, mesh, seq_axes)
    return jax.tree.map(
        lambda ns: NamedSharding(
            mesh, P(ns.spec[0] if len(ns.spec) else None,
                    rules["dexpert"], *ns.spec[1:])), inner)


def _cache_leaf_spec(shape_struct, rules, mesh: Mesh) -> P:
    """Contiguous cache-leaf placement: batch over data, heads over model
    when divisible. Cache layouts all carry the layer/group dim first and
    batch second (attention) or inside (states) — we shard batch and leave
    exotic dims replicated when indivisible."""
    shape = shape_struct.shape
    ndim = len(shape)
    b_axes = rules["kv_cache_batch"]
    extent = 1
    for a in (b_axes if isinstance(b_axes, tuple) else (b_axes,)):
        extent *= mesh.shape[a]
    spec = [None] * ndim
    # find the batch dim: layouts here are (L, B, ...) or (G, gm, B, ...)
    for cand in (1, 2):
        if ndim > cand and shape[cand] % extent == 0 and shape[cand] > 1:
            spec[cand] = b_axes
            break
    # (L,B,S,KV,dh) attention-cache layouts: shard kv-heads over model
    # when divisible, else shard the *sequence* dim (distributed-decode
    # partial-softmax layout — XLA inserts the reduction collectives).
    if ndim == 5 and spec[1] == b_axes:
        kv, seq = shape[-2], shape[2]
        if kv % mesh.shape["model"] == 0 and kv > 1:
            spec[-2] = "model"
        elif seq % mesh.shape["model"] == 0 and seq > 1:
            spec[2] = "model"
    return P(*spec)


def cache_pspec_tree(cache_shapes, rules, mesh: Mesh):
    """KV-cache / recurrent-state shardings for the contiguous layout."""
    import jax
    return jax.tree.map(
        lambda s: NamedSharding(mesh, _cache_leaf_spec(s, rules, mesh)),
        cache_shapes)


def chunk_carry_pspec_tree(carry_shapes, rules, mesh: Mesh):
    """Shardings for a chunked-prefill carry (one request's direct-leaf
    decode states plus (1,)-shaped pool placeholders). The carry's batch
    extent is 1 — a single request mid-prefill — so nothing shards over the
    kv-cache batch axes; kv-heads of 5-D cross-attention leaves still
    follow ``model`` when divisible (they are full per-layer KV rows), and
    everything else is replicated alongside the dispatch that consumes it.
    The stacked-mixture carry additionally carries ``dexpert`` at axis 1 of
    every leaf, exactly like the stacked cache — reuse
    ``stacked_cache_pspec_tree`` semantics by mapping over this result."""
    import jax

    def one(shape_struct):
        shape = shape_struct.shape
        spec = [None] * len(shape)
        if len(shape) == 5:                    # (L, 1, F, KV, dh) cross KV
            kv = shape[-2]
            heads_ax = rules["kv_cache_heads"]
            if kv % mesh.shape[heads_ax] == 0 and kv > 1:
                spec[-2] = heads_ax
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, carry_shapes)


def block_table_pspec(rules, mesh: Mesh) -> NamedSharding:
    """Placement for the paged-cache METADATA operands: the per-step
    (n_slots, NB) decode block tables and the single-request (NB,) chunk
    table. The radix prefix-cache tree, refcounts, and LRU list are host
    state and never reach a device; the block table is the one
    device-visible piece of metadata, and it must be REPLICATED — with the
    pool's *physical block* axis sharded over the kv-cache batch axes
    (``paged_pool_pspec_tree``), every shard resolves its own
    ``pool[table]`` gather locally, so the tiny int32 table rides along
    with each dispatch instead of being scattered (and a shared-prefix
    block is readable from every shard that holds it, whichever slot's
    table points at it)."""
    return NamedSharding(mesh, P())


def slot_state_pspec_tree(state_like, rules, mesh: Mesh):
    """Placement for the fused decode step's per-slot device state (the
    tok/pos/temps/top_ks/seeds/counts/max_new/stop_ids/tables/weights dict
    of ``_SlotTable._device_state``): REPLICATED, like the block tables it
    now carries (``block_table_pspec``) — every leaf is a few-hundred-byte
    int/float row, so each shard keeps its own copy and the fused
    epilogue's sampling + stop/budget checks run locally with zero
    collectives; only the model forward inside the same dispatch touches
    sharded operands."""
    import jax
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), state_like)


def paged_pool_pspec_tree(paged_cache_shapes, rules, mesh: Mesh, seq_axes):
    """Shardings for the PAGED decode cache. ``seq_axes`` is the
    ``CacheSpec.paged.seq_axes`` pytree: leaves marked ``-1`` are direct
    per-slot rows and keep their contiguous placement; pool leaves
    (scan, P, KV, block, dh) shard the *physical block* axis over the
    kv-cache batch axes — blocks, not slots, are the unit of placement, so
    the pool scales with device count while the per-slot block table stays
    replicated host state — and kv-heads over ``model`` when divisible
    (block positions are never sharded: a block is the DMA granule)."""
    import jax

    def one(shape_struct, s_ax):
        if s_ax < 0:
            return NamedSharding(mesh,
                                 _cache_leaf_spec(shape_struct, rules, mesh))
        shape = shape_struct.shape
        ndim = len(shape)
        b_axes = rules["kv_cache_batch"]
        extent = 1
        for a in (b_axes if isinstance(b_axes, tuple) else (b_axes,)):
            extent *= mesh.shape[a]
        spec = [None] * ndim
        pool_ax = s_ax - 1          # the axis the slot (batch) axis held
        if shape[pool_ax] % extent == 0 and shape[pool_ax] > 1:
            spec[pool_ax] = b_axes
        if ndim == 5:
            kv = shape[-3]
            if kv % mesh.shape["model"] == 0 and kv > 1:
                spec[-3] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, paged_cache_shapes, seq_axes)
