"""The fused decode-step epilogue: everything a scheduler used to do on the
host after the model's forward — pick the next token (greedy or seeded
sampling), check stop/eos ids, check the token budget and the context
bound, and advance the per-slot position — expressed as pure device ops so
the whole decode token is ONE jitted dispatch.

The host's per-step work shrinks to a single ``device_get`` of the
``(next_token, done)`` pair: ``next_token`` feeds the per-request output
streams, and ``done`` is a small per-slot bitmap (0 = keep decoding, else
a ``DONE_REASONS`` code) that replaces per-slot Python token inspection
for retirement detection.

The per-slot sampling state (temps / top_ks / seeds / counts / stop ids /
budgets) lives in a dict of persistent device arrays — see
``_SlotTable._device_state`` — rebuilt only when admission, retirement or
block-table growth changes it, never per step.

Semantics are kept EXACTLY equal to the unfused host epilogue
(``_SlotTable._advance`` + ``Request.reason_now``):

* stop ids match only *generated* tokens (the state is consulted for the
  token decoded this step — prompt tokens never reach it);
* reason precedence is stop > length > truncated;
* the capacity bound is position-exact: position ``cache_len - 1`` is
  decodable, the write that would land at ``cache_len`` is not.

``verify_epilogue`` is the speculative sibling: given the scores of L
candidate positions and the L-1 draft tokens that produced them, it
computes the deterministic seeded-sampling accept rule (the token the
vanilla trajectory WOULD emit at each offset — count ``c0 + j`` of the
request's seeded stream — accepted while the draft matches it), the
per-offset stop/budget/context checks with the same precedence, and the
variable-length position advance, still as pure device ops.

**The single-dispatch contract** (shared with ``serve/scheduler.py``):

* on device, per step: the model forward (decode, verify or co-scheduled
  chunk), Eq. 27 mixture mixing, seeded sampling / the speculative accept
  rule, stop/eos/budget/context checks, and the position advance — one
  jitted dispatch, no intermediate host sync;
* the host may read back ONE ``jax.device_get`` per step — the
  ``(next_token, done)`` pair (vanilla) or ``(tokens, n_emit, done)``
  triple (speculative) — plus nothing else on the hot path;
* host-side state mutation (slot tables, block allocator, request
  streams) is driven entirely by that readback; the persistent device
  state dict is rebuilt only on admission/retirement/growth events.

repro-lint enforces the contract: the step loop and this module are
``# repro: hot-path`` scope (eager device ops and implicit syncs are
flagged), the dispatch entry points are ``# repro: jit`` scope (retrace
hazards are flagged), and the kernels' index maps carry
``# repro: bounds`` justifications.

This module is a leaf: it imports only jax and the shared ``PROB_FLOOR``
so every consumer (schedulers, the model's fused entry point, the stacked
mixture core) can pull it in without import cycles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.ensemble import PROB_FLOOR

__all__ = ["DONE_REASONS", "argmax_tokens", "decode_epilogue", "pick_first",
           "sample_tokens", "sample_tokens_probs", "verify_epilogue",
           "_sample_tokens"]

#: ``done`` bitmap code → finish reason (0 means "keep decoding").
DONE_REASONS = {1: "stop", 2: "length", 3: "truncated"}


def _sample_tokens(scores, temps, top_ks, seeds, counts):
    """Per-slot seeded sampling step (jitted once, batched over slots).

    scores: (B, V) next-token logits (or log-probabilities — argmax and
    categorical are both invariant to the difference up to the temperature
    semantics documented on ``Request``); temps: (B,) float32, ≤ 0 rows
    take the greedy argmax; top_ks: (B,) int32, 0 → full vocabulary;
    seeds/counts: (B,) uint32/int32 — token ``counts[b]`` of request
    ``seeds[b]`` draws from ``fold_in(PRNGKey(seed), count)``, so a
    request's sampled continuation depends only on (seed, scores), never
    on slot placement or co-scheduled traffic. Named scope ``sample``.
    """
    with jax.named_scope("sample"):
        V = scores.shape[-1]
        greedy = jnp.argmax(scores, axis=-1).astype(jnp.int32)
        k = jnp.where(top_ks <= 0, V, jnp.minimum(top_ks, V))
        srt = jnp.sort(scores, axis=-1)                  # ascending
        thresh = jnp.take_along_axis(srt, (V - k)[:, None], axis=-1)
        masked = jnp.where(scores >= thresh, scores, -jnp.inf)
        scaled = masked / jnp.maximum(temps, 1e-6)[:, None]
        keys = jax.vmap(lambda s, c: jax.random.fold_in(
            jax.random.PRNGKey(s), c))(seeds, counts)
        sampled = jax.vmap(jax.random.categorical)(keys, scaled)
        return jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)


sample_tokens = jax.jit(_sample_tokens)


def _sample_tokens_probs(probs, temps, top_ks, seeds, counts):
    """``sample_tokens`` over Eq. 27 mixture *probabilities*: the floor +
    log transform runs inside the same dispatch, so callers holding probs
    (the stacked mixture core) pay no eager ``jnp.log`` on the host path."""
    return _sample_tokens(jnp.log(jnp.maximum(probs, PROB_FLOOR)),
                          temps, top_ks, seeds, counts)


sample_tokens_probs = jax.jit(_sample_tokens_probs)

#: Greedy next-token pick as ONE jitted dispatch — the all-greedy fast path
#: of ``_SlotTable._next_tokens``. The eager ``jnp.argmax`` it replaces was
#: an un-fused device dispatch (and implicit sync) per step on the host
#: side of the legacy epilogue (the PR 6 incident repro-lint now flags).
@jax.jit
def argmax_tokens(scores):
    return jnp.argmax(scores, axis=-1).astype(jnp.int32)


def pick_first(row, temp, top_k, seed, *, from_probs: bool = False):
    """First token from a prefill's last-position scores (``row``: (1, V))
    — count 0 of the request's seeded stream, greedy when ``temp <= 0``.
    Pure (meant to be fused into the prefill/chunk dispatch); returns the
    (1,) int32 token on device. Named scope ``sample``."""
    with jax.named_scope("sample"):
        if from_probs:
            row = jnp.log(jnp.maximum(row, PROB_FLOOR))
        return _sample_tokens(row, temp, top_k, seed,
                              jnp.zeros((1,), jnp.int32))


def decode_epilogue(scores, state, *, cache_len: int,
                    from_probs: bool = False):
    """One lockstep decode step's host epilogue as device ops.

    scores: (n_slots, V) this step's next-token scores; state: the per-slot
    device-state dict (see ``_SlotTable._device_state``) with at least

        tok/pos (int32), active (bool), temps (f32), top_ks (i32),
        seeds (u32), counts (i32), max_new (i32), stop_ids (i32, padded
        with -1 — token ids are non-negative, so pad rows never match)

    Returns ``(new_state, next_tok, done)``: the state advanced for the
    next step (finished rows parked at tok/pos 0 — the scratch-writing
    idle configuration — and deactivated), the (n_slots,) tokens decoded
    this step (inactive rows keep their input token and must be ignored),
    and the (n_slots,) ``DONE_REASONS`` bitmap. Named scopes: ``sample``
    for the token pick, ``epilogue`` for the checks and the advance.
    """
    with jax.named_scope("sample"):
        if from_probs:
            scores = jnp.log(jnp.maximum(scores, PROB_FLOOR))
        nxt = _sample_tokens(scores, state["temps"], state["top_ks"],
                             state["seeds"], state["counts"])
    with jax.named_scope("epilogue"):
        active = state["active"]
        nxt = jnp.where(active, nxt, state["tok"]).astype(jnp.int32)
        counts = state["counts"] + active.astype(jnp.int32)
        pos = state["pos"] + active.astype(jnp.int32)
        # reason precedence mirrors Request.reason_now + _advance exactly:
        # stop > length > truncated, each gated on the slot being active
        is_stop = active & jnp.any(nxt[:, None] == state["stop_ids"],
                                   axis=-1)
        is_len = active & (counts >= state["max_new"])
        is_trunc = active & (pos >= cache_len)
        done = jnp.where(is_stop, 1,
                         jnp.where(is_len, 2,
                                   jnp.where(is_trunc, 3, 0))
                         ).astype(jnp.int32)
        fin = done > 0
        new_state = dict(state,
                         tok=jnp.where(fin, 0, nxt).astype(jnp.int32),
                         pos=jnp.where(fin, 0, pos).astype(jnp.int32),
                         counts=counts,
                         active=active & ~fin)
    return new_state, nxt, done


def verify_epilogue(scores, drafts, state, *, cache_len: int,
                    from_probs: bool = False):
    """The speculative span's accept/reject + bookkeeping as device ops.

    scores: (n_slots, L, V) — row j is the model's next-token scores at
    position ``pos + j``, i.e. after feeding the slot's committed token
    (offset 0) and draft tokens ``drafts[:, :j]`` (offsets 1..j);
    drafts: (n_slots, L-1) int32 candidate tokens; state: the same
    device-state dict as ``decode_epilogue``.

    The accept rule is DETERMINISTIC token-match: seeded sampling makes
    the vanilla trajectory a pure function of (seed, count, scores), so
    the "true" token at offset j is ``_sample_tokens(scores[:, j], ...,
    counts + j)`` — exactly what a vanilla step with the same prefix
    would emit — and a draft is accepted iff it EQUALS it. This is
    standard rejection sampling degenerated to its deterministic special
    case (the proposal is accepted with probability 1 when it matches
    the target draw, 0 otherwise), which is what makes the token-for-
    token parity invariant hold for sampled requests, not just greedy.
    Offset j's scores are only consulted when drafts 1..j all matched,
    so every emitted token saw exactly the vanilla prefix.

    Per-offset finish checks replay ``decode_epilogue`` at each emitted
    offset (count ``c0+j+1`` vs budget, position ``p0+j+1`` vs context,
    stop-id membership; precedence stop > length > truncated): the span
    is truncated at the FIRST halting offset, so a stop token accepted
    mid-span retires the request once and the rest of the draft is
    discarded on device — the host never sees the dead tail.

    Returns ``(new_state, toks, n_emit, done)``: ``toks`` (n_slots, L)
    holds the emitted tokens left-aligned (rows of inactive slots are
    zeroed), ``n_emit`` (n_slots,) how many of them are real — at least
    1 for an active slot (offset 0 never needs a draft: all-reject spans
    still make forward progress), at most L — and ``done`` the
    ``DONE_REASONS`` bitmap. One ``device_get`` of the triple is the
    step's entire host readback. Named scopes as ``decode_epilogue``'s.
    """
    B, L, V = scores.shape
    with jax.named_scope("sample"):
        if from_probs:
            scores = jnp.log(jnp.maximum(scores, PROB_FLOOR))
    with jax.named_scope("epilogue"):
        active = state["active"]
        offs = jnp.arange(L, dtype=jnp.int32)
        # the vanilla trajectory's token at each offset: count c0 + j of the
        # request's seeded stream (greedy rows take the argmax, same as ever)
        true = jnp.stack(
            [_sample_tokens(scores[:, j], state["temps"], state["top_ks"],
                            state["seeds"], state["counts"] + j)
             for j in range(L)], axis=1)                          # (B, L)
        if L > 1:
            match = (drafts == true[:, :L - 1]).astype(jnp.int32)
            n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)   # (B,)
        else:
            n_acc = jnp.zeros((B,), jnp.int32)
        m_max = n_acc + 1            # accepted drafts + the free bonus token
        cnt_after = state["counts"][:, None] + 1 + offs[None, :]  # (B, L)
        pos_after = state["pos"][:, None] + 1 + offs[None, :]
        is_stop = jnp.any(true[:, :, None] == state["stop_ids"][:, None, :],
                          axis=-1)
        is_len = cnt_after >= state["max_new"][:, None]
        is_trunc = pos_after >= cache_len
        halt = is_stop | is_len | is_trunc                        # (B, L)
        first_halt = jnp.where(jnp.any(halt, axis=1),
                               jnp.argmax(halt, axis=1), L).astype(jnp.int32)
        m = jnp.minimum(m_max, first_halt + 1)
        m = jnp.where(active, m, 0).astype(jnp.int32)
        halted = active & (first_halt < m_max)
        code = jnp.where(is_stop, 1, jnp.where(is_len, 2, 3))
        h = jnp.clip(first_halt, 0, L - 1)
        done = jnp.where(halted,
                         jnp.take_along_axis(code, h[:, None], axis=1)[:, 0],
                         0).astype(jnp.int32)
        fin = done > 0
        counts = state["counts"] + m
        pos = state["pos"] + m
        last = jnp.take_along_axis(
            true, jnp.maximum(m - 1, 0)[:, None], axis=1)[:, 0]
        nxt = jnp.where(active, last, state["tok"]).astype(jnp.int32)
        new_state = dict(state,
                         tok=jnp.where(fin, 0, nxt).astype(jnp.int32),
                         pos=jnp.where(fin, 0, pos).astype(jnp.int32),
                         counts=counts,
                         active=active & ~fin)
        toks = jnp.where(active[:, None], true, 0).astype(jnp.int32)
    return new_state, toks, m, done
