"""Expert-ensemble inference (paper §5.2).

At each decode step the global generating velocity is the router-weighted
sum of expert velocities (Eq. 27). Because every expert velocity is affine
in its next-token conditional (u_k = c_k − δ_mask) and the router weights
sum to one, mixing velocities is *identical* to mixing the experts'
next-token probability distributions:

    p_mix(a | prefix) = Σ_k r_k(features) · softmax(logits_k)[a]

with r the top-k-filtered Eq. 28 router. With top-1 routing this degenerates
to "run only the selected expert" — the compute-matched setting of the
paper's main tables; the engine exploits that by gathering the single
selected expert's parameters instead of running all K.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .decentralize import mix_expert_distributions
from .router import CentroidRouter

Array = jnp.ndarray

# Floor applied before taking logs of mixture probabilities — shared by every
# consumer (engine sampling, eval NLL) so the clamp is identical everywhere.
PROB_FLOOR = 1e-30


def mix_expert_logits(expert_logits: Array, weights: Array,
                      *, log_space: bool = False) -> Array:
    """Combine expert next-token logits into ensemble probabilities.

    expert_logits: (K, ..., V); weights: (..., K) (already top-k filtered,
    rows summing to 1). Returns probabilities (..., V) — the exact Eq. 27
    recomposition (probability space, not logit averaging).
    """
    probs = jax.nn.softmax(expert_logits, axis=-1)          # (K, ..., V)
    w = jnp.moveaxis(weights, -1, 0)                        # (K, ...)
    mixed = mix_expert_distributions(probs, w)
    if log_space:
        return jnp.log(jnp.maximum(mixed, PROB_FLOOR))
    return mixed


@dataclass
class EnsembleSpec:
    """Static description of a decentralized ensemble."""

    n_experts: int
    top_k: int = 1
    temperature: float = 10.0


def ensemble_next_token_probs(router: CentroidRouter, features: Array,
                              expert_logits: Array) -> Array:
    """features: (B, D) routing features for each request; expert_logits:
    (K, B, V) per-expert next-token logits → (B, V) mixed probabilities."""
    weights = router.route(features)                        # (B, K)
    return mix_expert_logits(expert_logits, weights)


def stack_expert_params(expert_params):
    """K per-expert parameter pytrees → one pytree with a leading K dim on
    every leaf — the serving twin of ``trainer.stack_expert_states``. The
    leading dim is the ``dexpert`` axis that shards over the ``pod`` mesh
    axis (sharding/rules.py), so a vmapped decode over it is one sharded op
    with zero cross-pod traffic."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *expert_params)


def stack_experts_for_decode(expert_params):
    """Stack experts in the DECODE layout: scanned layer stacks (the
    ``blocks`` subtrees) carry the K dim at axis 1, *after* the scanned
    layer dim; everything else leads with K.

    ``decode_step``/``prefill`` consume layer stacks with ``lax.scan``,
    which requires the scan axis first — vmapping over a leading K would
    make XLA transpose every parameter (and cache) leaf to (L, K, …) on
    EVERY step. Pre-storing the scanned stacks layer-major makes the
    vmapped step transpose-free (~1.4× decode steps/sec at K=4 on CPU).
    The K dim still shards over ``pod`` regardless of its position.

    Returns ``(stacked, in_axes)`` where ``in_axes`` is the per-leaf vmap
    axis tree to pass to ``jax.vmap``.

    Each leaf is stacked straight into its decode-layout axis, one leaf
    at a time, with no K-major intermediate to transpose. Experts held on
    the device stay there beside the stacked copy (two full-width
    InternVL2-2B experts: 7 GiB + 7 GiB, more than one 16 GB chip has
    room for next to anything else); experts passed as host (numpy)
    arrays are uploaded one leaf at a time, so the device holds the
    stacked copy plus one leaf's K inputs.
    """
    axes = jax.tree.map(lambda _: 0, expert_params[0])
    if isinstance(axes, dict) and "blocks" in axes:
        axes = dict(axes)
        axes["blocks"] = jax.tree.map(lambda _: 1, axes["blocks"])
        if "encoder" in axes:             # audio enc-dec: encoder stack too
            axes["encoder"] = dict(axes["encoder"])
            axes["encoder"]["blocks"] = jax.tree.map(
                lambda _: 1, axes["encoder"]["blocks"])
    stacked = jax.tree.map(lambda ax, *leaves: jnp.stack(leaves, axis=ax),
                           axes, *expert_params)
    return stacked, axes


def stacked_cache_axes(cache_like):
    """vmap axis tree for a stacked decode cache: every cache leaf carries
    its scan (layer/group) dim first, so the expert dim lives at axis 1."""
    return jax.tree.map(lambda _: 1, cache_like)


def make_stacked_serving(model, expert_params, cache_len: int, *,
                         use_kernel: bool = False, paged: bool = False):
    """Build the stacked-expert decode core shared by every mixture server
    (``DecentralizedServer``, ``MixtureSlotServer``, serve_bench): experts
    stacked in the decode layout plus jitted whole-ensemble steps.

    Returns ``(stacked, param_axes, prefill_all, mix_decode)`` where

    * ``prefill_all(stacked, batch)`` → ``(logits (K, B, S, V), caches)``
    * ``mix_decode(stacked, caches, tok, pos, weights)`` →
      ``(Eq. 27 mixed probabilities (B, V), new caches)`` — ONE vmapped
      ``decode_step`` over the K dim with the mixing fused into the jit.

    With ``paged`` the caches are the block-pool layout (pool leaves carry
    the K dim at axis 1, exactly like the direct leaves) and ``mix_decode``
    takes the per-slot block tables as a trailing argument, shared across
    all K experts (``in_axes=None`` under the vmap).
    """
    stacked, param_axes = stack_experts_for_decode(expert_params)
    # axis tree only depends on the cache STRUCTURE (paged and contiguous
    # caches share it): every leaf carries K at axis 1, after its scan dim
    cache_axes = stacked_cache_axes(model.cache_shapes(1, cache_len))

    def prefill_all(stacked_p, batch):
        return jax.vmap(
            lambda p: model.prefill(p, batch, cache_len,
                                    use_kernel=use_kernel),
            in_axes=(param_axes,), out_axes=(0, cache_axes))(stacked_p)

    if paged:
        def mix_decode(stacked_p, caches, tok, pos, weights, block_tables):
            logits, caches = jax.vmap(
                lambda p, c: model.decode_step_paged(
                    p, c, tok, pos, block_tables, use_kernel=use_kernel),
                in_axes=(param_axes, cache_axes),
                out_axes=(0, cache_axes))(stacked_p, caches)  # (K, B, V)
            return mix_expert_logits(logits, weights), caches
    else:
        def mix_decode(stacked_p, caches, tok, pos, weights):
            logits, caches = jax.vmap(
                lambda p, c: model.decode_step(p, c, tok, pos,
                                               use_kernel=use_kernel),
                in_axes=(param_axes, cache_axes),
                out_axes=(0, cache_axes))(stacked_p, caches)  # (K, B, V)
            return mix_expert_logits(logits, weights), caches

    return stacked, param_axes, jax.jit(prefill_all), jax.jit(mix_decode)


def make_stacked_chunk_fns(model, stacked, param_axes, cache_len: int,
                           chunk: int, *, use_kernel: bool = False):
    """Chunked-prefill companions to ``make_stacked_serving`` for the
    stacked-expert mixture core.

    Returns ``(prep_all, chunk_all)``:

    * ``prep_all(stacked, batch)`` → (embedded prompt (K, 1, W, D) — every
      expert owns its embedding table; admission slices off any cached
      prefix and pre-splits the suffix into per-chunk tensors, keeping the
      chunk step dispatch-free — per-expert chunk carries with the K dim
      at axis 1 of every leaf, the same slot the stacked cache keeps it
      in, so ``CacheSpec.shifted(1).insert_direct`` splices the finished
      carry without a transpose);
    * ``chunk_all(stacked, caches, carry, xc, start, length, block_table,
      weights)`` → (Eq. 27 mixed next-token probs (1, V) at the chunk's
      last valid position, new carry, new caches) — ONE vmapped
      ``prefill_chunk`` over the K dim; the block table is shared by all K
      experts (``in_axes=None``), exactly like the paged decode path.

    ``chunk_all`` is returned un-jitted so the mixture server can fuse it
    with the decode step into a single dispatch; ``prep_all`` is jitted
    (it runs once per admission, retracing per padded prompt width).
    """
    cache_axes = stacked_cache_axes(model.cache_shapes(1, cache_len))

    def prep_all(stacked_p, batch):
        x = jax.vmap(lambda p: model.embed_prompt(p, batch),
                     in_axes=(param_axes,))(stacked_p)     # (K, 1, W, D)
        carry = jax.vmap(
            lambda p: model.init_chunk_carry(p, batch, cache_len),
            in_axes=(param_axes,), out_axes=1)(stacked_p)
        return x, carry

    def chunk_all(stacked_p, caches, carry, xc, start, length, block_table,
                  weights):
        logits, carry, caches = jax.vmap(
            lambda p, c, cr, x: model.prefill_chunk(
                p, c, cr, x, start, length, block_table,
                use_kernel=use_kernel),
            in_axes=(param_axes, cache_axes, 1, 0),
            out_axes=(0, 1, cache_axes))(stacked_p, caches, carry, xc)
        return mix_expert_logits(logits, weights), carry, caches

    return jax.jit(prep_all), chunk_all


def make_stacked_fused(model, param_axes, cache_len: int, *,
                       chunk_all=None, use_kernel: bool = False,
                       paged: bool = False):
    """Fused-step companions to ``make_stacked_serving``: the vmapped
    Eq. 27 mixture decode PLUS the serving epilogue (seeded sampling, stop
    ids, budget/context checks, position advance — ``from_probs``: the
    mixed scores are probabilities) in one jitted dispatch, so a mixture
    decode token costs a single kernel launch like the single-model path.

    Returns ``(step, step_chunk, chunk_only)``:

    * ``step(stacked, caches, state)`` → ``(caches, state, next_tok,
      done)`` — ``state`` is the scheduler's per-slot device-state dict
      (``state["weights"]`` carries the (n_slots, K) router weights,
      ``state["tables"]`` the block tables when paged);
    * ``step_chunk(stacked, caches, state, carry, xc, start, length, cbt,
      w_row, temp, top_k, seed)`` → additionally consumes one prefill
      chunk and returns its (fused, device-side) first-token pick;
    * ``chunk_only(...)`` — the chunk + pick without a decode.

    ``step_chunk``/``chunk_only`` are None without ``chunk_all`` (pass the
    un-jitted chunk fn from ``make_stacked_chunk_fns``).
    """
    # function-level import: serve.fused imports PROB_FLOOR from here
    from repro.serve.fused import decode_epilogue, pick_first
    cache_axes = stacked_cache_axes(model.cache_shapes(1, cache_len))

    if paged:
        def mix(stacked_p, caches, st):
            logits, caches = jax.vmap(
                lambda p, c: model.decode_step_paged(
                    p, c, st["tok"], st["pos"], st["tables"],
                    use_kernel=use_kernel),
                in_axes=(param_axes, cache_axes),
                out_axes=(0, cache_axes))(stacked_p, caches)
            return mix_expert_logits(logits, st["weights"]), caches
    else:
        def mix(stacked_p, caches, st):
            logits, caches = jax.vmap(
                lambda p, c: model.decode_step(p, c, st["tok"], st["pos"],
                                               use_kernel=use_kernel),
                in_axes=(param_axes, cache_axes),
                out_axes=(0, cache_axes))(stacked_p, caches)
            return mix_expert_logits(logits, st["weights"]), caches

    def step(stacked_p, caches, st):
        probs, caches = mix(stacked_p, caches, st)
        st, nxt, done = decode_epilogue(probs, st, cache_len=cache_len,
                                        from_probs=True)
        return caches, st, nxt, done

    if chunk_all is None:
        return jax.jit(step), None, None

    def step_chunk(stacked_p, caches, st, carry, xc, start, length, cbt,
                   w_row, temp, top_k, seed):
        probs, caches = mix(stacked_p, caches, st)
        c_probs, carry, caches = chunk_all(stacked_p, caches, carry, xc,
                                           start, length, cbt, w_row)
        st, nxt, done = decode_epilogue(probs, st, cache_len=cache_len,
                                        from_probs=True)
        first = pick_first(c_probs, temp, top_k, seed, from_probs=True)
        return caches, st, nxt, done, first, carry

    def chunk_only(stacked_p, caches, carry, xc, start, length, cbt,
                   w_row, temp, top_k, seed):
        c_probs, carry, caches = chunk_all(stacked_p, caches, carry, xc,
                                           start, length, cbt, w_row)
        first = pick_first(c_probs, temp, top_k, seed, from_probs=True)
        return first, carry, caches

    return jax.jit(step), jax.jit(step_chunk), jax.jit(chunk_only)


def make_stacked_verify(model, param_axes, cache_len: int, spec_len: int, *,
                        use_kernel: bool = False, expert_draft: bool = True):
    """Speculative verify step for the stacked mixture core: score all
    ``spec_len`` candidate positions with the Eq. 27 mixture and accept
    the longest prefix matching the vanilla trajectory — one jitted
    dispatch, same contract as ``Model.fused_verify_step``
    (``state["weights"]`` carries the router weights, as in
    ``make_stacked_fused``).

    With ``expert_draft=True`` the drafts are SELF-generated on device:
    the draft model is the stacked params at expert index 0, sliced
    axes-aware inside the jit (a gather, free under XLA). Its KV trail is
    equally free: every expert writes its own cache slice during mixture
    decode/verify, so the expert-0 slice of the SHARED caches already
    holds expert-0's keys for every committed position — no separate
    draft cache to maintain, no catch-up forward. The draft loop runs
    ``spec_len - 1`` sequential greedy expert-0 ``decode_step_paged``
    micro-steps on a locally-threaded copy of that slice, then DISCARDS
    it: the vmapped verify re-scatters all K experts' K/V at every span
    position, so the draft's tentative writes never touch the real pool.
    Returns a jitted ``verify(stacked, caches, state)`` →
    ``(caches, state, toks, n_emit, done)``.

    With ``expert_draft=False`` the drafts arrive as an argument (the
    scheduler's host-side n-gram proposer):
    ``verify(stacked, caches, state, drafts)`` with the same outputs.
    """
    # function-level import: serve.fused imports PROB_FLOOR from here
    from repro.serve.fused import verify_epilogue
    cache_axes = stacked_cache_axes(model.cache_shapes(1, cache_len))

    def verify_core(stacked_p, caches, st, drafts):
        tokens = jnp.concatenate([st["tok"][:, None], drafts], axis=1)
        logits, caches = jax.vmap(
            lambda p, c: model.verify_step_paged(
                p, c, tokens, st["pos"], st["tables"],
                use_kernel=use_kernel),
            in_axes=(param_axes, cache_axes),
            out_axes=(0, cache_axes))(stacked_p, caches)  # (K, B, L, V)
        probs = mix_expert_logits(logits, st["weights"][:, None, :])
        st, toks, n_emit, done = verify_epilogue(
            probs, drafts, st, cache_len=cache_len, from_probs=True)
        return caches, st, toks, n_emit, done

    if not expert_draft:
        return jax.jit(verify_core)

    def verify(stacked_p, caches, st):
        draft_p = jax.tree.map(lambda leaf, ax: jnp.take(leaf, 0, axis=ax),
                               stacked_p, param_axes)
        draft_c = jax.tree.map(lambda leaf, ax: jnp.take(leaf, 0, axis=ax),
                               caches, cache_axes)
        tok = st["tok"]
        drafts = []
        for j in range(spec_len - 1):
            logits, draft_c = model.decode_step_paged(
                draft_p, draft_c, tok, st["pos"] + j, st["tables"],
                use_kernel=use_kernel)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            drafts.append(tok)
        drafts = jnp.stack(drafts, axis=1)               # (B, L-1)
        return verify_core(stacked_p, caches, st, drafts)

    return jax.jit(verify)


def select_expert_params(stacked_params, expert_idx: Array):
    """Top-1 fast path: gather one expert's parameter slice out of a pytree
    whose leaves carry a leading K dim. With the expert axis sharded over the
    ``pod`` mesh axis this lowers to a cross-pod gather of exactly one
    expert — the serving analogue of zero-communication training."""
    return jax.tree.map(lambda leaf: jnp.take(leaf, expert_idx, axis=0),
                        stacked_params)
