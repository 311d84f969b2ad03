"""Continuous batching: slot-based request schedulers over the decode core
(vLLM-style, with paged KV caching and chunked-prefill co-scheduling).

Every engine here exposes the incremental request-lifecycle API from
``repro.serve.api`` as its *primitive* surface:

* ``add_request(prompt, SamplingParams(...), ...) -> rid`` — submit a
  prompt (or a prebuilt ``Request``) to the engine's waiting queue, at any
  time. Admission into a slot happens inside ``step()``.
* ``step() -> list[RequestOutput]`` — run one scheduler step (admission +
  one co-scheduled prefill-chunk/decode dispatch) and stream back a
  per-token update for EVERY request that progressed, not just the
  retirements: each ``RequestOutput`` carries the new ``TokenDelta``s
  (stamped for TTFT/ITL), the cumulative ids, and — once finished — a
  ``finish_reason`` in {length, stop, aborted, truncated}.
* ``abort(rid) -> RequestOutput | None`` — cancel a request at any point
  in its life: still queued, mid-prefill, or mid-decode. Frees its slot,
  returns its pool blocks, and drops its prefix-cache references; returns
  the terminal output (``finish_reason == "aborted"``) or None if the rid
  is unknown or already finished (a no-op).
* ``has_unfinished() -> bool`` — anything still waiting or active.

``make_engine(model, params | experts=..., router=..., config=EngineConfig)``
builds the right engine for a deployment; the legacy ``serve(queue)`` is
now a thin drain loop over exactly these primitives (submit everything,
step until idle, collect the finished outputs) and keeps exact greedy
parity with the pre-redesign servers.

Requests arrive with different prompt lengths and budgets; a server admits
each into a free slot, decodes ALL active slots in lockstep with a per-slot
position vector, and retires finished requests — so new work never waits
for the longest running request. Two admission modes:

* **monolithic** (``chunk=0``) — admission runs one single-row prefill and
  inserts the decode state into the batched cache via the model's
  ``CacheSpec``. Simple, but every active decode slot stalls for the full
  prefill of each arriving prompt.
* **chunked** (``chunk>0``) — admission only embeds the prompt (pre-split
  into per-chunk tensors) and reserves its KV blocks; the step loop then
  consumes the prompt ``chunk`` positions at a time, written straight into
  the paged pool through the slot's block table
  (``attn.chunk_attention`` / the prefix-aware flash kernel), with
  recurrent / conv / cross-attention state threaded through a per-request
  carry. Each chunk rides the SAME jitted dispatch as the lockstep decode
  (safe: decode writes and chunk writes touch disjoint physical blocks,
  and the chunk's truth lives in its carry). A ``token_budget`` bounds the
  per-step token work — decoding slots count 1 each, the chunk counts
  ``chunk`` — so decode throughput under bursty prompt arrivals is bounded
  below by construction instead of collapsing to zero during prefills.

Every cache family is supported: the model's cache descriptor says where
each cache leaf's slot axis lives, so the same admission/step machinery
drives attention KV rings (dense/moe/vlm), enc-dec cross-attention caches
(audio), and recurrent states (ssm/hybrid).

Two cache layouts share the machinery:

* **contiguous** (``page_block=0``) — each slot owns a fixed-length cache
  row of ``cache_len`` positions: simple, but every request pays for the
  longest possible row and the server's memory is O(n_slots × cache_len).
* **paged** (``page_block>0``) — attention KV leaves live in one shared
  block pool; each slot holds a *block table* mapping its logical blocks
  to physical pool blocks. Admission reserves only the blocks its prompt
  needs (``BlockAllocator`` free list), decode steps grow the reservation
  lazily, and retirement returns the blocks — so a request can decode past
  its initial reservation (no silent truncation) and pool memory is sized
  to expected load, not worst case. Recurrent/cross-attention leaves keep
  their direct per-slot rows (they are O(1) per slot already). Physical
  block 0 is reserved as a scratch target so inactive slots' lockstep
  writes never touch a live request's blocks.

A request that hits the serving context bound (``cache_len``) before its
token budget retires with ``Request.truncated = True`` — distinguishable
from normal completion. The bound is capacity-exact: position
``cache_len - 1`` is decodable (the seed retired one token early).

The decentralized deployment (paper §5.2) is ``DecentralizedSlotServer``:
the parameter-free centroid router (Eq. 28) runs at the front end on each
request's frozen-encoder features and either

* dispatches the request to its top-1 expert's pod — one ``SlotServer`` per
  expert, the paper's compute-matched setting — or
* admits it into the stacked-expert mixture core (``MixtureSlotServer``):
  expert parameters carry a stacked K (``dexpert``) dim in the decode
  layout (K after each scanned stack's layer dim — transpose-free for the
  scan), one jitted decode step vmaps over it and fuses the Eq. 27
  probability mixture, so the top-k path is a single sharded op instead of
  K sequential engine calls. In the paged layout all K experts share one
  block table per slot (the pool carries the ``dexpert`` dim).

**The single-dispatch contract.** Every steady-state scheduler step is
ONE jitted device dispatch followed by ONE ``jax.device_get``: the model
forward (plus any co-scheduled prefill chunk), Eq. 27 mixing where
applicable, seeded sampling, the stop/budget/context checks and the
position advance all run on device (``repro.serve.fused``), and the host
reads back only the ``(next_tok, done)`` pair — or, speculating, the
``(toks, n_emit, done)`` triple. Host code between dispatches does pure
numpy bookkeeping; anything that would force an extra device sync in the
step loop belongs inside the fused step (repro-lint's host-sync rule
enforces this mechanically).

Speculative decoding (``EngineConfig(speculative="ngram" | "expert",
spec_len=L)``) turns the per-step dispatch into a draft + multi-token
verify: a cheap proposer guesses ``L - 1`` tokens (host n-gram prompt
lookup — ``repro.serve.speculate`` — or the mixture core's expert 0
drafting on device), ``Model.verify_step_paged`` scores all ``L``
candidate positions in one launch over the paged pool, and the fused
accept rule (``verify_epilogue``) keeps the longest prefix that matches
the request's OWN seeded sampling stream — so outputs are token-for-token
identical to vanilla decode, speculating or not, greedy or sampled.
Rejected candidates need no undo: their K/V writes sit past the accepted
position and the next span overwrites them before any query can attend
that far (rollback-by-overwrite). Steps that cannot speculate — chunk
co-scheduling, pool pressure on the span reservation, non-capable model
families (``Model.speculative_capable``) — fall back to the vanilla
one-token step; the trajectory is unchanged, only the step size.
"""
from __future__ import annotations

import json
import logging
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.sanitizer import PoolSanitizer
from repro.core.ensemble import (PROB_FLOOR, make_stacked_chunk_fns,
                                 make_stacked_fused, make_stacked_serving,
                                 make_stacked_verify, mix_expert_logits,
                                 stacked_cache_axes)
from repro.models.model import Model
from repro.obs import metrics as _obs_metrics
from repro.obs.engine import NULL_SPAN, EngineObs
from repro.obs.trace import ADMIT_TID, merge_chrome
from repro.serve.api import (EngineConfig, RequestOutput, SamplingParams,
                             TokenDelta, effective_page_block, stop_id_row)
from repro.serve.fused import (DONE_REASONS, _sample_tokens, argmax_tokens,
                               decode_epilogue, pick_first, sample_tokens,
                               sample_tokens_probs)
from repro.serve.prefix_cache import PrefixCache, block_keys
from repro.serve.qos import (DEFAULT_ADMIT_LOOKAHEAD, ParkedState,
                             QoSConfig, TenantScheduler, predict_ttft,
                             priority_of, tenant_of)
from repro.serve.speculate import NGramProposer

Array = jnp.ndarray

logger = logging.getLogger(__name__)


@dataclass
class Request:
    """One in-flight request. ``SamplingParams`` is the canonical carrier
    of the decoding controls; the flat ``max_new``/``temperature``/
    ``top_k``/``seed`` fields remain as the legacy construction surface
    (and are kept in sync with ``params`` either way)."""

    rid: int
    tokens: np.ndarray            # (prompt_len,) int32
    max_new: int
    features: Optional[np.ndarray] = None   # frozen-encoder routing features
    extras: Dict[str, np.ndarray] = field(default_factory=dict)
    #                             # unbatched modality inputs: "patches"
    #                             # (vlm), "frames" (audio)
    temperature: float = 0.0      # 0 → greedy (the default: parity-exact)
    top_k: int = 0                # sample from the k highest-scoring tokens
    #                             # (0 → the full vocabulary)
    seed: int = 0                 # per-request sampling stream
    params: Optional[SamplingParams] = None
    out: List[int] = field(default_factory=list)
    truncated: bool = False       # retired at the context bound, not done
    finish_reason: Optional[str] = None     # set exactly once, at retirement
    t_submit: float = 0.0         # perf_counter at add_request
    t_admit: float = 0.0          # perf_counter at slot admission (PR 9:
    #                             # queued_s = t_admit - t_submit)
    t_first: float = 0.0          # perf_counter at the first emitted token
    t_done: float = 0.0           # perf_counter at retirement
    t_tok: List[float] = field(default_factory=list)   # per-token stamps
    emitted: int = 0              # tokens already streamed out via step()
    spec_req_steps: int = 0       # this request's speculative verify steps
    spec_req_accepted: int = 0    # draft tokens those steps accepted
    preemptions: int = 0          # times this request was parked/requeued
    resuming: bool = False        # parked by recompute: the next admission
    #                             # is a resume (re-prefill prompt + output)

    def __post_init__(self):
        if self.params is None:
            self.params = SamplingParams(
                max_new=self.max_new, temperature=self.temperature,
                top_k=self.top_k, seed=self.seed)
        else:                     # params is canonical: mirror to legacy
            self.max_new = self.params.max_new
            self.temperature = self.params.temperature
            self.top_k = self.params.top_k
            self.seed = self.params.seed

    @property
    def done(self) -> bool:
        return len(self.out) >= self.max_new

    @property
    def hit_stop(self) -> bool:
        """The LAST generated token is a stop/eos id (prompt tokens never
        trigger — only the output stream is inspected)."""
        s = self.params.stop_set
        return bool(s) and bool(self.out) and self.out[-1] in s

    def reason_now(self) -> Optional[str]:
        """Retirement reason after the latest emitted token, or None if
        the request should keep decoding. Capacity truncation is the
        caller's to detect (it is positional, not content, state)."""
        if self.hit_stop:
            return "stop"
        if self.done:
            return "length"
        return None

    def record(self, tok: int, t: Optional[float] = None) -> None:
        """Append one generated token with its latency stamp."""
        t = time.perf_counter() if t is None else t
        self.out.append(int(tok))
        self.t_tok.append(t)
        self.t_first = self.t_first or t

    @property
    def prefill_tokens(self) -> np.ndarray:
        """Token ids a (re-)prefill consumes. Normally the prompt; when a
        recompute-preempted request resumes, the prompt plus all but the
        last generated token — their KV was dropped at the park, and the
        last token is the decode input (its KV is written by the next
        decode step), exactly as after a fresh admission."""
        if self.resuming and len(self.out) > 1:
            return np.concatenate(
                [self.tokens, np.asarray(self.out[:-1], np.int32)])
        return self.tokens

    def batch(self, pad_to: int = 0) -> Dict[str, Array]:
        """Single-row prefill batch (tokens + modality extras). ``pad_to``
        right-pads the token row to that length (chunked prefill rounds the
        prompt up to a whole number of chunks; padded rows are masked)."""
        toks = self.prefill_tokens
        if pad_to > len(toks):
            toks = np.concatenate(
                [toks, np.zeros(pad_to - len(toks), np.int32)])
        b = {"tokens": jnp.asarray(toks[None, :]),
             "labels": jnp.zeros((1, len(toks)), jnp.int32)}
        for name, v in self.extras.items():
            b[name] = jnp.asarray(np.asarray(v)[None])
        return b


# _sample_tokens / sample_tokens moved to repro.serve.fused (so the fused
# dispatch, the stacked mixture core and the schedulers share one tracing)
# and re-exported above for back-compat.

_FEATURES_MSG = ("request {rid}: this engine routes on frozen-encoder "
                 "features — pass features= to add_request")


def _as_request(prompt, params: Optional[SamplingParams], extras,
                features, rid: int) -> Request:
    """The one place a submission becomes a ``Request``: pass a prebuilt
    ``Request`` through untouched, or wrap a token-id array with its
    ``SamplingParams`` (shared by the engines' ``add_request`` and the
    decentralized front end)."""
    if isinstance(prompt, Request):
        return prompt
    sp = params if params is not None else SamplingParams()
    return Request(rid, np.asarray(prompt, dtype=np.int32), sp.max_new,
                   features=features, extras=dict(extras or {}), params=sp)


def _raise_dropped(dropped: List[str], n_finished: int,
                   max_steps: int) -> None:
    """Exhausting the drive loop with unfinished requests is never a silent
    drop: log the count (with each request's progress — queued, decode
    position, or partial prefill position), then raise."""
    logger.error(
        "serve() exhausted max_steps=%d: dropping %d unfinished "
        "request(s) %s (%d finished)", max_steps, len(dropped), dropped,
        n_finished)
    raise RuntimeError(
        f"serve() exhausted max_steps={max_steps} with {len(dropped)} "
        f"request(s) {dropped} unfinished — raise max_steps or shrink "
        f"budgets")


class BlockAllocator:
    """Free-list allocator over a shared pool of KV cache blocks.

    Physical block 0 is reserved as the scratch block: inactive slots'
    lockstep decode writes land there (their block tables are zeroed), so
    the pool hands out blocks 1..n_blocks-1. ``alloc`` is all-or-nothing —
    a partially satisfiable request leaves the free list untouched.

    ``free`` guards against out-of-range ids and double frees with clear
    errors: once blocks are refcounted and shared (the prefix cache), a
    bookkeeping slip would otherwise hand the same physical block to two
    live requests and corrupt both silently.

    Every block also carries a generation counter, bumped when it is
    freed: a holder that stamped the generation at reservation can prove
    its ``(slot, block)`` reference is still live (``assert_live``) — a
    stale reference held across a free/realloc raises a use-after-free
    instead of silently aliasing the block's new owner (the failure shape
    of PR 4's refcount-0 eviction bug).
    """

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError(f"pool needs >= 2 blocks (one is the reserved "
                             f"scratch block), got {n_blocks}")
        self.n_blocks = n_blocks
        self._free = list(range(n_blocks - 1, 0, -1))   # pop() → low ids
        self._free_set = set(self._free)
        self.gen = [0] * n_blocks       # bumped at free() per block

    @property
    def n_free(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(out)
        return out

    def free(self, blocks: List[int]) -> None:
        if len(set(blocks)) != len(blocks):
            raise ValueError(f"double free within one call: {blocks}")
        for b in blocks:
            if not 0 < b < self.n_blocks:
                raise ValueError(
                    f"freeing block {b} outside the pool range "
                    f"1..{self.n_blocks - 1} (block 0 is the reserved "
                    f"scratch block)")
            if b in self._free_set:
                raise ValueError(
                    f"double free of block {b} — it is already on the free "
                    f"list; block refcount bookkeeping is corrupt")
        self._free.extend(blocks)
        self._free_set.update(blocks)
        for b in blocks:
            self.gen[b] += 1

    def assert_live(self, block: int, gen: int, *, owner: str = "") -> None:
        """Raise unless ``block`` is still in the allocation generation the
        holder stamped at reservation — i.e. it has NOT been freed (and
        possibly reissued) since. ``owner`` names the holder in the
        error."""
        cur = self.gen[block]
        if cur != gen:
            who = f" held by {owner}" if owner else ""
            raise ValueError(
                f"use-after-free: block {block}{who} was freed since its "
                f"reservation (generation {cur} != held {gen}) — the "
                "reference is stale and may alias the block's new owner")


class _SlotTable:
    """Slot bookkeeping + the continuous-admission drive loop shared by the
    single-engine and stacked-mixture servers. With ``block_size > 0`` it
    also owns the paged-cache block tables and allocator; with ``chunk > 0``
    it runs chunked-prefill continuous batching: admission only embeds the
    prompt and reserves its blocks, and each scheduler step co-schedules one
    prefill chunk (FCFS over mid-prefill slots) with the lockstep decode of
    every decoding slot in a single jitted dispatch, subject to
    ``token_budget`` (decode slots count 1 token each, the chunk counts
    ``chunk``; 0 → n_slots + chunk, so co-scheduling always fits)."""

    def __init__(self, n_slots: int, cache_len: int, *, block_size: int = 0,
                 n_blocks: int = 0, window: int = 0, chunk: int = 0,
                 token_budget: int = 0, prefix_cache: bool = False,
                 sanitize: bool = False, obs: Optional[EngineObs] = None,
                 qos: Optional[QoSConfig] = None, preemption: str = "off"):
        self.n_slots, self.cache_len = n_slots, cache_len
        # -- multi-tenant QoS (PR 10, repro.serve.qos) --------------------
        # policy objects; None/"off" keeps the legacy FCFS behavior (plus
        # the bounded admission skip-ahead, which is always on)
        self.qos = qos
        self.preemption = preemption
        quantum = (qos.quantum if qos is not None and qos.quantum > 0
                   else (chunk if chunk > 0 else 16))
        self._drr_admit = TenantScheduler(qos, quantum)
        self._drr_chunk = TenantScheduler(qos, quantum)
        self._parked: Dict[int, ParkedState] = {}   # rid -> parked state
        self._chunk_pick: Optional[int] = None      # this step's chunk slot
        self._step_ewma = 0.0        # EWMA step() wall time (TTFT model)
        self._step_kind = "none"     # this step's dispatch, for its span
        self._tenant_stats: Dict[str, Dict[str, int]] = {}
        # telemetry bundle (PR 9): the always-on per-engine registry plus
        # the (default no-op) span recorder. stats() and the n_aborted /
        # n_stopped / n_spec_* back-compat attributes are views over it.
        self.obs = obs if obs is not None else EngineObs()
        self.obs.name_tracks(n_slots, f"pod {self.obs.pod}")
        self.pos = np.zeros(n_slots, dtype=np.int32)      # next position
        self.slot_req: List[Optional[Request]] = [None] * n_slots
        self.last_tok = np.zeros(n_slots, dtype=np.int32)
        self.admit_retired: List[Request] = []  # retired without a slot
        self.waiting: List[Request] = []        # submitted, not yet admitted
        self._next_rid = 0                      # auto-assigned request ids
        self._needs_features = False            # mixture/top1 routing input
        self.chunk = chunk
        self.chunked = chunk > 0
        self.token_budget = token_budget if token_budget > 0 \
            else n_slots + chunk
        self.prefilling = [False] * n_slots
        self.prefill_pos = np.zeros(n_slots, dtype=np.int32)
        self.prefill_base = np.zeros(n_slots, dtype=np.int32)  # cached prefix
        self.prefill_width = np.zeros(n_slots, dtype=np.int32)
        self.prefill_x: List[Any] = [None] * n_slots   # per-chunk tensors
        self.prefill_carry: List[Any] = [None] * n_slots
        self.prefill_keys: List[Any] = [None] * n_slots  # full-block keys
        self.prefill_order: List[int] = []      # FCFS over mid-prefill slots
        self._seq_axis = 1         # sequence axis of the embedded prompt
        self._from_probs = False   # mixture scores are probabilities
        self.fused = False         # single-dispatch decode step (subclasses
        #                          # flip it on after building the fused fns)
        self._dstate = None        # persistent per-slot device state; None →
        #                          # rebuild from the host mirrors next step
        self._tables_dirty = False  # block tables grew but nothing else
        #                          # changed: patch st["tables"] only
        self._stop_width = 1       # stop-id matrix width (monotone, pow2 —
        #                          # each growth retraces the fused step once)
        self.speculative: Optional[str] = None  # set from EngineConfig by
        self.spec_len = 1          # _init_speculation (servers call it)
        self._can_spec = False     # armed: config asks AND the model can
        #                          # roll a span back (speculative_capable)
        self._step_span = 1        # decode-write span of the CURRENT step:
        #                          # 1 vanilla, spec_len speculating (the
        #                          # PoolSanitizer and _nb_live read it)
        self.block_size = block_size
        self.paged = block_size > 0
        if self.paged:
            s_kv = min(cache_len, window) if window > 0 else cache_len
            self.ring = window > 0
            if self.ring:
                if s_kv % block_size:
                    raise ValueError(
                        f"sliding-window ring length {s_kv} must be a "
                        f"multiple of page_block={block_size}")
                self.nb_slot = s_kv // block_size
            else:
                self.nb_slot = -(-cache_len // block_size)
            if n_blocks <= 0:       # default: full capacity + scratch
                n_blocks = n_slots * self.nb_slot + 1
            self.allocator = BlockAllocator(n_blocks)
            self.obs.pool_total_g.set(self.allocator.n_blocks)
            self.obs.pool_free_g.set(self.allocator.n_free)
            self.block_tables = np.zeros((n_slots, self.nb_slot), np.int32)
            self.n_alloc = np.zeros(n_slots, dtype=np.int32)
            # allocation generation of each mapped entry (use-after-free
            # detection: checked against allocator.gen at release and by
            # the PoolSanitizer's per-step scan)
            self.block_gens = np.zeros((n_slots, self.nb_slot), np.int64)
        self.prefix: Optional[PrefixCache] = None
        if prefix_cache:
            # flag combinations were vetted by EngineConfig.validate();
            # reaching here with prefix on means paged + chunked are too
            assert self.paged and self.chunked, (block_size, chunk)
            self.prefix = PrefixCache(self.allocator, block_size,
                                      registry=self.obs.registry)
        # debug-mode dynamic checker over the paged pool (EngineConfig.
        # sanitize / --sanitize): shadows every step with an ownership scan
        self.sanitizer: Optional[PoolSanitizer] = \
            PoolSanitizer(self) if sanitize and self.paged else None
        if not self.paged:
            # preemption parks/drops paged blocks; a family with no
            # pageable leaves (effective_page_block == 0) degrades to the
            # direct path and cannot be preempted
            self.preemption = "off"

    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    # lifetime counters, re-implemented as views over the registry (PR 9)
    # so exposition and stats() can never disagree
    @property
    def n_aborted(self) -> int:
        return self.obs.n_aborted

    @property
    def n_stopped(self) -> int:
        return self.obs.n_stopped

    @property
    def n_spec_steps(self) -> int:
        return self.obs.n_spec_steps

    @property
    def n_spec_tokens(self) -> int:
        return self.obs.n_spec_tokens

    @property
    def active(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is not None]

    @property
    def decoding(self) -> List[int]:
        """Slots in the lockstep decode (mid-prefill slots are excluded —
        their truth lives in the chunk carry, not the batched cache)."""
        return [i for i, r in enumerate(self.slot_req)
                if r is not None and not self.prefilling[i]]

    def admit(self, req: Request) -> bool:
        raise NotImplementedError

    def _decode_step(self) -> List[Request]:
        """One raw scheduler dispatch (lockstep decode, optionally fused
        with a prefill chunk). Returns the requests retired by it."""
        raise NotImplementedError

    # ------------------------------------------------------------------
    # The incremental request-lifecycle API (the primitive surface)
    # ------------------------------------------------------------------

    def add_request(self, prompt, params: Optional[SamplingParams] = None,
                    extras: Optional[Dict[str, np.ndarray]] = None, *,
                    features: Optional[np.ndarray] = None,
                    rid: Optional[int] = None) -> int:
        """Submit a prompt (token-id array) — or a prebuilt ``Request`` —
        to the waiting queue and return its rid. Admission into a slot
        happens inside ``step()``; submission never blocks and never
        dispatches device work. A request NO capacity could ever admit
        (prompt past the serving context, or a reservation bigger than
        the whole pool) is rejected here with a ValueError rather than
        poisoning the head of the queue."""
        req = _as_request(prompt, params, extras, features,
                          self._next_rid if rid is None else rid)
        if self._needs_features and req.features is None:
            raise ValueError(_FEATURES_MSG.format(rid=req.rid))
        self._reject_unservable(req)
        self._next_rid = max(self._next_rid, req.rid + 1)
        req.t_submit = req.t_submit or time.perf_counter()
        self.obs.submitted.inc()
        if self.qos is not None:
            why = self._admission_control(req)
            if why is not None:
                self._finish_rejected(req, why)
                return req.rid
        self.waiting.append(req)
        return req.rid

    def _admission_control(self, req: Request) -> Optional[str]:
        """SLO-aware load shedding at submission (``QoSConfig``): None →
        accept into the queue; otherwise the reason to reject. The
        predicted-TTFT model is first-order by design: every prompt token
        queued or still prefilling ahead of the arrival must flow through
        the per-step chunk budget at the observed (EWMA) step time."""
        q = self.qos
        if q.max_waiting and len(self.waiting) >= q.max_waiting:
            return (f"queue depth {len(self.waiting)} at the "
                    f"max_waiting={q.max_waiting} bound")
        if q.max_predicted_ttft_s > 0 and self.chunked \
                and self._step_ewma > 0:
            backlog = sum(self._prefill_width(r) for r in self.waiting)
            backlog += sum(
                int(self.prefill_width[s] - self.prefill_pos[s])
                for s in self.prefill_order)
            eta = predict_ttft(backlog + self._prefill_width(req),
                               self.chunk, self._step_ewma)
            if eta > q.max_predicted_ttft_s:
                return (f"predicted TTFT {eta:.3f}s over the "
                        f"max_predicted_ttft_s={q.max_predicted_ttft_s} "
                        f"SLO ({backlog} backlog tokens)")
        return None

    def _finish_rejected(self, req: Request, why: str) -> None:
        """Admission control refused the submission: retire it without a
        slot (``finish_reason="rejected"``, zero tokens) — the terminal
        ``RequestOutput`` streams from the next ``step()``, exactly like
        an admission retirement. Rejection is load shedding, not an
        error, so it logs rather than raises."""
        logger.info("reject request %d (tenant %s): %s", req.rid,
                    tenant_of(req), why)
        req.t_done = time.perf_counter()
        self._set_reason(req, "rejected")
        tenant = tenant_of(req)
        self._tenant(tenant)["rejections"] += 1
        self.obs.rejected(tenant).inc()
        self._obs_retired(None, req)
        self.admit_retired.append(req)

    def _tenant(self, tenant: str) -> Dict[str, int]:
        st = self._tenant_stats.get(tenant)
        if st is None:
            st = {"tokens": 0, "preemptions": 0, "resumes": 0,
                  "rejections": 0}
            self._tenant_stats[tenant] = st
        return st

    def _reject_unservable(self, req: Request) -> None:
        """Fail fast at submission on requests that can never be admitted,
        even by an idle server: the engine runs forever, so parking one at
        the queue head would wedge every later arrival behind it."""
        width = self._prefill_width(req)
        self._reject_overlong(req, width)
        # monolithic admission of a context-filling prompt retires at
        # admission without reserving; every other paged path reserves the
        # whole prompt — which needs `need` DISTINCT physical blocks
        # (prefix-shared blocks live in the same pool, so sharing can't
        # shrink the requirement below the table's span)
        if self.paged and (self.chunked or width < self.cache_len):
            need = self.nb_slot if self.ring else \
                max(min(-(-width // self.block_size), self.nb_slot), 1)
            usable = self.allocator.n_blocks - 1
            if need > usable:
                raise ValueError(
                    f"request {req.rid}: its prompt reservation needs "
                    f"{need} KV blocks but the pool has only {usable} "
                    f"usable (pool_blocks={self.allocator.n_blocks}, "
                    f"page_block={self.block_size}) — provision more "
                    f"pool_blocks or shorten the prompt")

    def step(self) -> List[RequestOutput]:
        """One engine step: admit from the waiting queue while slots (and,
        paged, pool blocks) allow, then run one co-scheduled prefill-chunk
        / lockstep-decode dispatch. Streams back a ``RequestOutput`` for
        every request that progressed — finished ones first (admission
        retirements, then this step's), then the live per-token deltas in
        slot order.

        Traced, the step is a ``step:<kind>`` span tiled by its phases:
        ``admit``, then (fused) ``schedule``, ``dispatch``, ``device_get``
        and ``advance``, then ``outputs``."""
        t_start = time.perf_counter()
        obs = self.obs
        self._chunk_pick = None      # this step's chunk pick, not yet made
        self._step_kind = "none"     # what the step dispatched, if anything
        with obs.span("step") as step_span:
            with obs.span("admit"):
                self._admit_waiting()
                finished = self._drain_admit_retired()
            if self.active:
                if self.sanitizer is not None:
                    self.sanitizer.begin_step()
                finished += self._decode_step()
                if self.sanitizer is not None:
                    self.sanitizer.check_step()
            with obs.span("outputs"):
                outs = [self._output(r) for r in finished]
                for req in (self.slot_req[s] for s in range(self.n_slots)):
                    if req is not None and req.emitted < len(req.out):
                        outs.append(self._output(req))
                self._obs_step()
                # EWMA step time feeds the admission-control TTFT
                # prediction
                dt = time.perf_counter() - t_start
                self._step_ewma = dt if self._step_ewma == 0.0 \
                    else 0.9 * self._step_ewma + 0.1 * dt
            step_span.kind(self._step_kind)
        return outs

    def abort(self, rid: int) -> Optional[RequestOutput]:
        """Cancel a request wherever it is in its life — still queued,
        mid-prefill, or mid-decode. Frees its slot, returns its pool
        blocks, and drops its prefix-cache references (shared cached
        blocks stay resident for other holders / the LRU list). Returns
        the terminal output (``finish_reason == "aborted"``); an unknown
        or already-finished rid is a no-op returning None."""
        for i, req in enumerate(self.waiting):
            if req.rid == rid:
                self.waiting.pop(i)
                parked = self._parked.pop(rid, None)
                if parked is not None:
                    # a parked victim holds pinned prefix refs (and, swap,
                    # a host payload): release them exactly
                    self._drop_parked(parked)
                    if self.sanitizer is not None:
                        self.sanitizer.check_pool()
                return self._finish_aborted(req)
        for slot, req in enumerate(self.slot_req):
            if req is None or req.rid != rid:
                continue
            if self.prefilling[slot]:
                self.prefill_order.remove(slot)
                self.prefilling[slot] = False
                self.prefill_x[slot] = None
                self.prefill_carry[slot] = None
                self.prefill_keys[slot] = None
                self.prefill_pos[slot] = 0
                self.prefill_base[slot] = 0
                self.prefill_width[slot] = 0
            self._release(slot)
            if self.sanitizer is not None:
                # an aborted request must leave zero leaked blocks behind
                self.sanitizer.check_pool()
            return self._finish_aborted(req)
        return None

    def has_unfinished(self) -> bool:
        """True while any request is waiting or holds a slot."""
        return bool(self.waiting) or bool(self.active)

    def _finish_aborted(self, req: Request) -> RequestOutput:
        req.finish_reason = "aborted"
        req.t_done = time.perf_counter()
        obs = self.obs
        obs.aborted.inc()
        self._account_retired(req)
        tr = obs.trace
        if tr.enabled:
            slot = getattr(req, "_obs_slot", None)
            tid = obs.slot_tid(slot) if slot is not None else ADMIT_TID
            t0 = getattr(req, "_obs_t_phase", 0.0)
            if t0:                  # close the phase the abort interrupted
                tr.complete(getattr(req, "_obs_phase", "decode"), t0,
                            req.t_done, tid, args={"rid": req.rid})
            elif req.t_admit == 0.0:   # aborted straight out of the queue
                tr.async_begin("queued", req.t_submit, req.rid)
                tr.async_end("queued", req.t_done, req.rid)
            tr.instant("abort", req.t_done, tid, args={"rid": req.rid})
        return self._output(req)

    def _admit_waiting(self) -> None:
        """Admission from the waiting queue. Without a QoSConfig this is
        FCFS with a bounded skip-ahead window (``DEFAULT_ADMIT_LOOKAHEAD``)
        past an unadmittable queue head — a pool-starved large prompt no
        longer head-of-line-blocks smaller admissible requests behind it.
        With a QoSConfig, deficit round robin arbitrates *between tenants*
        (weighted, charged in prompt tokens) while FCFS order is preserved
        *within* each tenant. Either way a request no idle server can
        admit would wait forever: raise instead."""
        if self.qos is None:
            self._admit_fcfs()
        else:
            self._admit_drr()
        if self.waiting and not self.active:
            req = self.waiting[0]
            # last resort on an otherwise idle server: parked requests'
            # pinned prefix blocks may be what is starving the pool —
            # release the pins (their contents stay reproducible: swap
            # payloads move host-side first, recompute re-prefills) and
            # retry the head once before declaring the pool too small
            if self._parked and self._unpin_parked() and \
                    self._admit_one(req):
                return
            raise RuntimeError(
                f"cannot admit request {req.rid} even on an "
                f"idle server — the KV block pool is too small for it")

    def _admit_one(self, req: Request) -> bool:
        """One admission attempt for a waiting request; on success it
        leaves the queue and its admission is recorded. Traced, the
        attempt is an ``admission`` span; a failed one writes no ring
        event."""
        with self.obs.span("admission", tid=ADMIT_TID) as span:
            t0 = time.perf_counter()
            if not self._try_admit(req):
                span.drop()
                return False
            self._dequeue(req)
            self._on_admitted(req, t0, span)
            return True

    def _dequeue(self, req: Request) -> None:
        # identity scan: the Request dataclass __eq__ compares ndarray
        # fields, so list.remove would die on ambiguous truth values
        i = next(i for i, r in enumerate(self.waiting) if r is req)
        self.waiting.pop(i)

    def _admit_fcfs(self) -> None:
        while self.waiting and self.free_slots():
            admitted = False
            for i in range(min(len(self.waiting),
                               DEFAULT_ADMIT_LOOKAHEAD)):
                if self._admit_one(self.waiting[i]):
                    admitted = True
                    break            # restart the scan from the head
            if not admitted:
                break                # wait for blocks to free up

    def _admit_drr(self) -> None:
        """DRR admission: each round offers every tenant's HEAD waiting
        request (within-tenant FCFS) to the tenant scheduler at a cost of
        its prefill width; a tenant whose head can't be admitted right
        now is refunded and stood aside for this step, so one starved
        tenant never blocks the others' admissions."""
        blocked: set = set()
        while self.waiting and self.free_slots():
            heads: Dict[str, Request] = {}
            for r in self.waiting:
                t = tenant_of(r)
                if t not in heads and t not in blocked:
                    heads[t] = r
            if not heads:
                break
            cand = {t: self._prefill_width(r) for t, r in heads.items()}
            pick = self._drr_admit.pick(cand)
            if not self._admit_one(heads[pick]):
                self._drr_admit.refund(pick, cand[pick])
                blocked.add(pick)

    # ------------------------------------------------------------------
    # Preemption: park / resume over the paged pool (repro.serve.qos)
    # ------------------------------------------------------------------

    def _try_admit(self, req: Request) -> bool:
        """One admission attempt with the QoS extensions: a swap-parked
        request resumes by swap-in (no prefill at all); anything else —
        including recompute-parked requests, which re-enter chunked
        prefill over prompt + generated tokens — goes through the
        subclass ``admit``. On pool-pressure failure, preemption (when
        enabled) evicts one strictly-lower-priority victim and retries
        until the request fits or no eligible victim remains."""
        parked = self._parked.get(req.rid)
        while True:
            if parked is not None and parked.mode == "swap":
                ok = self._admit_swapped(parked)
            else:
                ok = self.admit(req)
            if ok:
                if parked is not None and parked.mode == "recompute":
                    # the resume's prefix match re-acquired whatever it
                    # still shares; the park's pin is now redundant
                    self._parked.pop(req.rid, None)
                    if self.prefix is not None:
                        for b in parked.pinned:
                            self.prefix.release(b)
                    parked.pinned = ()
                return True
            if self.preemption == "off":
                return False
            victim = self._pick_victim(priority_of(req))
            if victim is None:
                return False
            self._preempt(victim)

    def _pick_victim(self, floor: int,
                     exclude: Tuple[Optional[int], ...] = ()
                     ) -> Optional[int]:
        """Slot of the best preemption victim with priority strictly
        below ``floor`` — lowest priority first, youngest admission first
        among equals (it has the least work to lose). Mid-prefill slots
        are eligible (they requeue cheaply); in recompute mode a decoding
        victim whose resume prefill could never fit the pool again is
        skipped (preempting it would strand it unadmittable forever)."""
        best, best_key = None, None
        usable = self.allocator.n_blocks - 1 if self.paged else 0
        for slot in self.active:
            if slot in exclude:
                continue
            req = self.slot_req[slot]
            p = priority_of(req)
            if p >= floor:
                continue
            if self.preemption == "recompute" and not self.prefilling[slot]:
                need = -(-int(self.pos[slot]) // self.block_size)
                if min(need, self.nb_slot) > usable:
                    continue
            key = (p, -req.t_admit)
            if best_key is None or key < best_key:
                best, best_key = slot, key
        return best

    def _can_park(self, slot: int) -> bool:
        """A decoding slot may be parked only if its resume could ever be
        admitted again: always true for swap (the payload re-enters any
        free blocks), but a recompute resume must re-prefill its whole
        position span through the pool."""
        if self.preemption != "recompute" or self.prefilling[slot]:
            return True
        need = -(-int(self.pos[slot]) // self.block_size)
        return min(need, self.nb_slot) <= self.allocator.n_blocks - 1

    def _preempt(self, slot: int) -> None:
        """Evict the request holding ``slot`` to relieve pool pressure.
        Mid-prefill victims simply requeue (their chunk state is cheap to
        rebuild); decoding victims park — ``swap`` carries their private
        block contents to the host, ``recompute`` drops them and replays
        the generated tokens through chunked prefill at resume. Either
        way the victim re-enters the waiting queue at the front, and its
        resumed output is token-for-token identical: sampling is seeded
        per token index, independent of the schedule."""
        req = self.slot_req[slot]
        mode = "requeue" if self.prefilling[slot] else self.preemption
        with self.obs.span("preempt", rid=req.rid, mode=mode):
            if mode == "requeue":
                self.prefill_order.remove(slot)
                self.prefilling[slot] = False
                self.prefill_x[slot] = None
                self.prefill_carry[slot] = None
                self.prefill_keys[slot] = None
                self.prefill_pos[slot] = 0
                self.prefill_base[slot] = 0
                self.prefill_width[slot] = 0
                self._release(slot)
            elif mode == "swap":
                self._park_swap(slot, req)
            else:
                self._park_recompute(slot, req)
            self._obs_preempted(slot, req, mode)
        req.preemptions += 1
        tenant = tenant_of(req)
        self._tenant(tenant)["preemptions"] += 1
        self.obs.preempted(tenant, mode).inc()
        self.waiting.insert(0, req)
        logger.info("preempt request %d (tenant %s, priority %d, mode %s)",
                    req.rid, tenant, priority_of(req), mode)

    def _park_recompute(self, slot: int, req: Request) -> None:
        """Drop the victim's blocks, keeping only pinned prefix-cache
        references; the resume replays ``prompt + out[:-1]`` through
        chunked prefill (largely hitting the cache when the pins held)."""
        n = int(self.n_alloc[slot])
        refs = self.prefix.refcounts if self.prefix is not None else {}
        pinned = tuple(
            b for b in (int(x) for x in self.block_tables[slot, :n])
            if b in refs)
        if pinned:
            self.prefix.acquire(list(pinned))    # pin across the park
        req.resuming = True
        self._parked[req.rid] = ParkedState(
            req=req, mode="recompute", pinned=pinned,
            pos=int(self.pos[slot]), last_tok=int(self.last_tok[slot]))
        self._release(slot)

    def _park_swap(self, slot: int, req: Request) -> None:
        """Copy the victim's private block rows (and its direct, non-
        paged cache leaves) to the host, then free them; cache-tracked
        rows stay resident in the pool under a pin. Resume scatters the
        payload into freshly allocated blocks — no recompute at all."""
        n = int(self.n_alloc[slot])
        blocks = [int(b) for b in self.block_tables[slot, :n]]
        refs = self.prefix.refcounts if self.prefix is not None else {}
        shared = tuple((i, b) for i, b in enumerate(blocks) if b in refs)
        private = tuple((i, b) for i, b in enumerate(blocks)
                        if b not in refs)
        payload = jax.device_get(self.spec.swap_out(
            self.cache, slot, [b for _, b in private]))
        pinned = tuple(b for _, b in shared)
        if pinned:
            self.prefix.acquire(list(pinned))    # pin across the park
        self._parked[req.rid] = ParkedState(
            req=req, mode="swap", pinned=pinned, shared=shared,
            private=private, payload=payload, pos=int(self.pos[slot]),
            last_tok=int(self.last_tok[slot]), n_alloc=n,
            extras=self._park_extras(slot))
        self._release(slot)

    def _admit_swapped(self, st: ParkedState) -> bool:
        """Resume a swap-parked request: allocate fresh physical blocks
        for its private rows, rebuild its block table (pinned shared rows
        map back in place — the parked pin transfers silently to the
        slot's table reference), scatter the host payload back, and
        re-occupy a slot with NO prefill: the decode cursor restarts
        exactly where the park left it."""
        free = self.free_slots()
        if not free:
            return False
        req = st.req
        slot = free[0]
        fresh: List[int] = []
        if st.private:
            got = self._alloc_blocks(len(st.private))
            if got is None:
                return False
            fresh = got
        for i, b in st.shared:
            self.block_tables[slot, i] = b
        for (i, _), b in zip(st.private, fresh):
            self.block_tables[slot, i] = b
        self.n_alloc[slot] = st.n_alloc
        self._stamp_gens(slot, 0, st.n_alloc)
        self._tables_dirty = True
        self.cache = self.spec.swap_in(self.cache, st.payload, slot,
                                       fresh)
        self.slot_req[slot] = req
        self.pos[slot] = st.pos
        self.last_tok[slot] = st.last_tok
        self._restore_extras(slot, st.extras)
        self._dstate = None
        self._parked.pop(req.rid, None)
        return True

    def _drop_parked(self, st: ParkedState) -> None:
        """Free a parked request's held resources exactly: the pinned
        prefix references go back to the cache's LRU accounting and the
        swap payload is dropped (host memory only — its private blocks
        returned to the pool at park time)."""
        if self.prefix is not None:
            for b in st.pinned:
                self.prefix.release(b)
        st.pinned = ()
        st.payload = None

    def _unpin_parked(self) -> bool:
        """Deadlock relief on an otherwise idle server: drop every parked
        request's pinned prefix references so the LRU can evict those
        blocks for the admission that is starving. Recompute parks lose
        nothing (resume re-prefills whatever was evicted); swap parks
        first fold the pinned rows' contents into their host payload and
        thereafter resume fully from host copies. True if any pin was
        released."""
        released = False
        for st in self._parked.values():
            if not st.pinned:
                continue
            if st.mode == "swap" and st.shared:
                extra = jax.device_get(self.spec.swap_out(
                    self.cache, 0, [b for _, b in st.shared]))
                st.payload = self._merge_payload(st.payload, extra)
                st.private = st.private + st.shared
                st.shared = ()
            for b in st.pinned:
                self.prefix.release(b)
            st.pinned = ()
            released = True
        return released

    def _merge_payload(self, a, b):
        """Append payload ``b``'s pool rows after ``a``'s. Direct leaves
        keep ``a``'s slot copy — ``b`` was gathered with a dummy slot and
        only its pool rows are meaningful."""
        def one(x, y, b_ax, s_ax):
            if s_ax < 0:
                return x
            return np.concatenate([np.asarray(x), np.asarray(y)],
                                  axis=b_ax)
        return jax.tree.map(one, a, b, self.spec.batch_axes,
                            self.spec.paged.seq_axes)

    def _park_extras(self, slot: int) -> Dict[str, Any]:
        """Subclass hook: extra per-slot host state a swap park must
        carry (the mixture server parks its router-weight row)."""
        return {}

    def _restore_extras(self, slot: int, extras: Dict[str, Any]) -> None:
        """Subclass hook: restore ``_park_extras`` state at swap resume."""
        return None

    def _obs_preempted(self, slot: int, req: Request, mode: str) -> None:
        """Close the victim's open phase span and mark the preemption as
        an instant on its slot track; the queued span re-opens from this
        stamp at resume (``_on_admitted``)."""
        t = time.perf_counter()
        req._obs_queued_from = t
        tr = self.obs.trace
        if tr.enabled:
            tid = self.obs.slot_tid(slot)
            t0 = getattr(req, "_obs_t_phase", 0.0)
            if t0:
                tr.complete(getattr(req, "_obs_phase", "decode"), t0, t,
                            tid, args={"rid": req.rid})
            tr.instant("preempt", t, tid,
                       args={"rid": req.rid, "mode": mode,
                             "tenant": tenant_of(req)})
        req._obs_t_phase = 0.0

    def _on_admitted(self, req: Request, t0: float, span) -> None:
        """Telemetry boundary for one successful admission: stamp
        ``t_admit`` (queue delay ends here), close the request's
        ``queued`` span, and open its slot-resident phase. Requests that
        retired inside ``admit()`` (context-filling prompts, max_new == 1)
        clamp the admission ``span`` to their ``t_done`` so a request's
        spans always sum to its end-to-end latency."""
        t1 = req.t_done if req.finish_reason is not None \
            else time.perf_counter()
        resumed_from = getattr(req, "_obs_queued_from", 0.0)
        if not req.t_admit:          # resumes keep their first admission
            req.t_admit = t0
        obs = self.obs
        obs.admitted.inc()
        # a resumed request's queue delay is measured from its preemption
        obs.queued_s.observe(t0 - (resumed_from or req.t_submit))
        slot = next((s for s, r in enumerate(self.slot_req) if r is req),
                    None)
        if req.finish_reason is None and slot is not None:
            # phase bookkeeping rides the Request (host-only attributes):
            # the retirement path closes the open phase span from these
            req._obs_slot = slot
            req._obs_phase = "prefill" if self.prefilling[slot] \
                else "decode"
            req._obs_t_phase = t1
        tr = obs.trace
        if tr.enabled:
            tr.async_begin("queued", resumed_from or req.t_submit, req.rid,
                           args={"rid": req.rid})
            tr.async_end("queued", t0, req.rid)
            span.stamp(t0, t1, obs.slot_tid(slot) if slot is not None
                       else ADMIT_TID)
            span.note(rid=req.rid)
        if resumed_from:
            tenant = tenant_of(req)
            self._tenant(tenant)["resumes"] += 1
            obs.resumed(tenant).inc()
            if tr.enabled and slot is not None:
                tr.instant("resume", t0, obs.slot_tid(slot),
                           args={"rid": req.rid, "tenant": tenant})
            req._obs_queued_from = 0.0

    def _obs_step(self) -> None:
        """Per-step telemetry epilogue: bump the step counter and refresh
        the occupancy/pool gauges (plus, tracing, one "C" counter sample
        that Perfetto renders as timeline graphs)."""
        obs = self.obs
        obs.steps.inc()
        n_act, n_wait = len(self.active), len(self.waiting)
        obs.active_g.set(n_act)
        obs.waiting_g.set(n_wait)
        if self.paged:
            obs.pool_free_g.set(self.allocator.n_free)
        tr = obs.trace
        if tr.enabled:
            vals = {"active": n_act, "waiting": n_wait}
            if self.paged:
                vals["pool_free_blocks"] = self.allocator.n_free
            tr.counter("engine", time.perf_counter(), vals)

    def _output(self, req: Request) -> RequestOutput:
        """Build the streaming update for ``req`` (tokens newly decoded
        since its last update) and advance its emission cursor."""
        new = req.out[req.emitted:]
        stamps = req.t_tok[req.emitted:]
        deltas = [TokenDelta(tok, req.emitted + i, t)
                  for i, (tok, t) in enumerate(zip(new, stamps))]
        req.emitted = len(req.out)
        return RequestOutput(
            rid=req.rid, deltas=deltas, token_ids=list(req.out),
            finished=req.finish_reason is not None,
            finish_reason=req.finish_reason, t_submit=req.t_submit,
            t_first=req.t_first, t_done=req.t_done, t_admit=req.t_admit)

    def _prefill_width(self, req: Request) -> int:
        """Decoder positions a request's prefill consumes (so admission can
        reserve blocks before paying for the prefill). Subclasses set
        ``self.model`` before admitting. A resuming (recompute-preempted)
        request re-prefills its generated tokens too."""
        w = len(req.prefill_tokens)
        if self.model.cfg.family == "vlm":
            w += self.model.cfg.n_patches          # image prefix
        return w

    def _reject_overlong(self, req: Request, width: int) -> None:
        """A prompt that exceeds the serving context is malformed and
        rejected loudly — the cache cannot even hold its prefill."""
        if width > self.cache_len:
            raise ValueError(
                f"request {req.rid}: prompt needs {width} positions but the "
                f"serving context is cache_len={self.cache_len} — reject "
                f"the request or raise cache_len")

    def _admission_precheck(self, req: Request, slot: int,
                            width: int) -> bool:
        """Runs BEFORE the prefill is paid for. False → can't admit right
        now (pool has no blocks free: the request stays pending)."""
        self._reject_overlong(req, width)
        if self.paged and width < self.cache_len and \
                not self._reserve(slot, width):
            return False
        return True

    def _admit_prefilled(self, slot: int, req: Request, first: int,
                         width: int, row_cache) -> None:
        """Insert an admitted request's prefill state (paged or contiguous)
        and occupy its slot. A request whose whole budget is the prefill
        token (max_new == 1) retires immediately — the slot must not decode
        a token past its budget."""
        if self.paged:
            blocks = jnp.asarray(
                self.block_tables[slot, :int(self.n_alloc[slot])])
            self.cache = self.spec.insert_paged(self.cache, row_cache, slot,
                                                blocks)
        else:
            self.cache = self.spec.insert(self.cache, row_cache, slot)
        self._occupy(slot, req, first, width)
        reason = req.reason_now()        # max_new == 1, or first tok stops
        if reason:
            self._retire_from_slot(slot, req, reason)
            self.admit_retired.append(req)

    # ------------------------------------------------------------------
    # Paged-cache bookkeeping
    # ------------------------------------------------------------------

    def _alloc_blocks(self, n: int) -> Optional[List[int]]:
        """Pool allocation with prefix-cache pressure relief: when the free
        list can't satisfy, evict LRU unreferenced cached blocks back to it
        and retry — cached-but-idle prefixes never block admission."""
        blocks = self.allocator.alloc(n)
        if blocks is None and self.prefix is not None:
            self.prefix.evict(n - self.allocator.n_free)
            blocks = self.allocator.alloc(n)
        return blocks

    def _reserve(self, slot: int, upto: int,
                 shared: Optional[List[int]] = None) -> bool:
        """Grow ``slot``'s block reservation to cover logical positions
        [0, upto). Ring (sliding-window) slots reserve their whole bounded
        span at once. All-or-nothing; False when the pool can't satisfy.

        ``shared`` (admission only, table empty) maps prefix-cache hit
        blocks read-only into the table's leading entries; only the
        remainder is allocated fresh. The matched run is PINNED (acquired)
        before that allocation runs — ``_alloc_blocks`` relieves pool
        pressure by evicting LRU refcount-0 blocks, which is exactly what
        the matched run still is until it is pinned — and un-pinned again
        if the allocation fails, so a failed admission retry leaves the
        cache as it found it."""
        need = self.nb_slot if self.ring else \
            min(-(-upto // self.block_size), self.nb_slot)
        need = max(need, 1)
        have = int(self.n_alloc[slot])
        if need <= have:
            return True
        if shared:
            assert have == 0, (slot, have)
            self.prefix.acquire(shared)
            blocks = self._alloc_blocks(need - len(shared))
            if blocks is None:
                for b in shared:
                    self.prefix.release(b)
                return False
            self.block_tables[slot, :len(shared)] = shared
            self.block_tables[slot, len(shared):need] = blocks
            self.n_alloc[slot] = need
            self._stamp_gens(slot, 0, need)
            self._tables_dirty = True    # only the table changed
            return True
        blocks = self._alloc_blocks(need - have)
        if blocks is None:
            return False
        self.block_tables[slot, have:need] = blocks
        self.n_alloc[slot] = need
        self._stamp_gens(slot, have, need)
        # growth changes the table and NOTHING else — patch st["tables"]
        # instead of tearing down the whole device state (mid-decode growth
        # fires every page_block steps; a full rebuild there costs more
        # than the dispatch it feeds)
        self._tables_dirty = True
        return True

    def _stamp_gens(self, slot: int, lo: int, hi: int) -> None:
        """Record the allocation generation of newly mapped table entries
        [lo, hi) — the use-after-free witness ``_release`` (and the
        PoolSanitizer) check against ``allocator.gen``."""
        gen = self.allocator.gen
        for i in range(lo, hi):
            self.block_gens[slot, i] = gen[int(self.block_tables[slot, i])]

    def _grow_active(self) -> None:
        """Before a lockstep decode step: make sure every decoding slot
        owns the block its next write position lands in."""
        if not self.paged or self.ring:
            return
        # vectorized fast path: positions only cross a block boundary every
        # block_size steps, so most steps no slot needs growth — one numpy
        # compare instead of a python _reserve call per slot
        need = np.minimum(-(-(self.pos + 1) // self.block_size),
                          self.nb_slot)
        # n_alloc == 0 masks out free slots (a decoding slot always holds
        # at least its admission block)
        if not np.any((need > self.n_alloc) & (self.n_alloc > 0)):
            return
        for slot in self.decoding:
            if self.slot_req[slot] is None:
                continue             # preempted as a victim in this loop
            while not self._reserve(slot, int(self.pos[slot]) + 1):
                if self.preemption != "off":
                    # preempt a strictly-lower-priority victim to keep
                    # this slot decoding; never the growing slot itself,
                    # nor this step's already-scheduled chunk slot
                    p = priority_of(self.slot_req[slot])
                    victim = self._pick_victim(
                        p, exclude=(slot, self._chunk_pick))
                    if victim is None:
                        # last resort: an equal-priority victim (youngest
                        # first, never a higher one). The grower's reserve
                        # succeeds right after the park, so every eviction
                        # funds immediate decode progress — two requests
                        # too big for the pool together hand it back and
                        # forth but can never livelock
                        victim = self._pick_victim(
                            p + 1, exclude=(slot, self._chunk_pick))
                    if victim is not None:
                        self._preempt(victim)
                        continue
                    # every other active slot outranks the grower: park
                    # the growing request itself rather than crash (the
                    # higher-priority slots keep progressing and free
                    # blocks for its resume). A slot that cannot grow
                    # even alone is a genuinely too-small pool and still
                    # raises below.
                    if len(self.active) > 1 and self._can_park(slot):
                        self._preempt(slot)
                        break
                    # parked requests' pinned prefix blocks may be what
                    # is starving the pool: release the pins (contents
                    # stay reproducible) and retry the reservation
                    if self._parked and self._unpin_parked():
                        continue
                req = self.slot_req[slot]
                raise RuntimeError(
                    f"KV block pool exhausted growing slot {slot} (request "
                    f"{req.rid}): {self.allocator.n_free} free of "
                    f"{self.allocator.n_blocks} blocks — provision more "
                    f"pool_blocks or fewer slots")

    def _grow_active_span(self, span: int) -> bool:
        """Span variant of ``_grow_active``: make sure every decoding slot
        owns blocks for ALL ``span`` positions a speculative step may
        write. False → the pool can't cover the whole span right now; the
        caller degrades to the vanilla one-token step instead of raising
        (speculation is a latency lever, never a liveness requirement).
        Slots reserved before the failing one keep their blocks — they
        would need them within ``span`` vanilla steps anyway, and
        retirement returns them. Only reached non-ring (sliding-window
        models are not ``speculative_capable``)."""
        need = np.minimum(-(-(self.pos + span) // self.block_size),
                          self.nb_slot)
        if not np.any((need > self.n_alloc) & (self.n_alloc > 0)):
            return True
        for slot in self.decoding:
            if not self._reserve(slot, int(self.pos[slot]) + span):
                return False
        return True

    def _init_speculation(self, config: EngineConfig, model,
                          build) -> None:
        """Arm speculative decoding when the config asks for it AND the
        engine shape supports it: fused paged decode on a model that can
        roll a span back (``speculative_capable`` — recurrent and
        sliding-window families can't, and silently degrade to vanilla
        decode, where parity is trivial). ``build()`` returns the jitted
        verify step, deferred so ineligible servers never trace it."""
        self.speculative = config.speculative
        self.spec_len = config.spec_len
        self._can_spec = (config.speculative is not None
                          and config.spec_len > 1 and self.fused
                          and self.paged and model.speculative_capable)
        if not self._can_spec:
            return
        self._vstep = build()
        self._ngram = NGramProposer(self.spec_len) \
            if config.speculative == "ngram" else None

    def _release(self, slot: int) -> None:
        self.slot_req[slot] = None
        self.pos[slot] = 0           # free slots write the scratch block
        self.last_tok[slot] = 0
        self._dstate = None          # retirement/abort: rebuild device state
        if self.paged:
            n = int(self.n_alloc[slot])
            if n:
                blocks = self.block_tables[slot, :n].tolist()
                # use-after-free check: every block this slot is about to
                # return must still be in the generation it reserved — a
                # mismatch means something freed (and possibly reissued)
                # it behind the table's back
                for i, b in enumerate(blocks):
                    self.allocator.assert_live(
                        b, int(self.block_gens[slot, i]),
                        owner=f"slot {slot} entry {i}")
                if self.prefix is not None:
                    # cache-tracked blocks stay resident (shared or LRU-
                    # evictable); only untracked ones return to the free
                    # list here
                    blocks = [b for b in blocks
                              if not self.prefix.release(b)]
                if blocks:
                    self.allocator.free(blocks)
            self.block_tables[slot, :] = 0
            self.block_gens[slot, :] = 0
            self.n_alloc[slot] = 0

    def _retire_at_admission(self, req: Request, first_tok: int) -> None:
        """The prompt already fills the context bound: the request keeps its
        single prefill token and retires without ever holding a slot."""
        req.record(first_tok)
        req.t_done = time.perf_counter()
        self._set_reason(req, req.reason_now() or "truncated")
        self._obs_retired(None, req)
        self.admit_retired.append(req)

    def _set_reason(self, req: Request, reason: str) -> None:
        """Stamp the terminal ``finish_reason`` (keeping the legacy
        ``truncated`` flag in sync) and bump the per-reason counters."""
        req.finish_reason = reason
        req.truncated = reason == "truncated"
        self.obs.retired(reason).inc()
        self._account_retired(req)

    def _account_retired(self, req: Request) -> None:
        """Fold a terminal request into its tenant's token accounting
        (the per-tenant breakdown ``stats()`` reports and the
        ``serve_tenant_tokens_total`` series)."""
        tenant = tenant_of(req)
        self._tenant(tenant)["tokens"] += len(req.out)
        self.obs.tenant_tokens(tenant).inc(len(req.out))

    def _obs_retired(self, slot: Optional[int], req: Request) -> None:
        """Telemetry boundary for one retirement (``t_done`` already
        stamped): latency histograms, the per-request speculative accept
        rate, and — tracing — the close of the open phase span plus a
        ``retire`` instant carrying the finish reason."""
        obs = self.obs
        obs.e2e_s.observe(req.t_done - req.t_submit)
        if req.t_first > 0:
            obs.ttft_s.observe(req.t_first - req.t_submit)
        if req.spec_req_steps and self.spec_len > 1:
            obs.req_accept_rate.observe(
                req.spec_req_accepted
                / (req.spec_req_steps * (self.spec_len - 1)))
        tr = obs.trace
        if tr.enabled:
            tid = obs.slot_tid(slot) if slot is not None else ADMIT_TID
            t0 = getattr(req, "_obs_t_phase", 0.0)
            if t0:
                tr.complete(getattr(req, "_obs_phase", "decode"), t0,
                            req.t_done, tid, args={"rid": req.rid})
            tr.instant("retire", req.t_done, tid,
                       args={"rid": req.rid,
                             "finish_reason": req.finish_reason})

    def _drain_admit_retired(self) -> List[Request]:
        out, self.admit_retired = self.admit_retired, []
        return out

    # ------------------------------------------------------------------
    # Lockstep advance / drive loop
    # ------------------------------------------------------------------

    def _occupy(self, slot: int, req: Request, first_tok: int,
                prompt_len: int) -> None:
        if req.resuming:
            # resumed recompute prefill: the "first token" pick merely
            # re-predicted the last already-recorded token (and a sampled
            # pick used a fresh count-0 fold, so it need not even match) —
            # discard it and put the decode cursor exactly back where the
            # park left it: pos = resume width = park-time pos, last_tok =
            # the last recorded token
            req.resuming = False
            self.slot_req[slot] = req
            self.pos[slot] = prompt_len
            self.last_tok[slot] = int(req.out[-1])
            self._dstate = None
            return
        req.record(first_tok)
        self.slot_req[slot] = req
        self.pos[slot] = prompt_len
        self.last_tok[slot] = first_tok
        self._dstate = None          # admission: rebuild device state

    def _advance(self, next_tok: np.ndarray) -> List[Request]:
        """Record one decoded token per decoding slot; retire finished
        requests — budget exhausted (``length``), a generated stop/eos id
        (``stop``), or the capacity bound (``truncated``; capacity-exact:
        position cache_len - 1 is decodable).
        next_tok: (n_slots,) int32 (inactive/prefilling rows ignored)."""
        retired = []
        t = time.perf_counter()
        for slot in self.decoding:
            req = self.slot_req[slot]
            req.record(int(next_tok[slot]), t)
            self.pos[slot] += 1
            self.last_tok[slot] = next_tok[slot]
            reason = req.reason_now() or \
                ("truncated" if self.pos[slot] >= self.cache_len else None)
            if reason:
                self._retire_from_slot(slot, req, reason)
                retired.append(req)
        return retired

    def _retire_from_slot(self, slot: int, req: Request,
                          reason: str) -> None:
        """Finalize a request that currently holds ``slot``: stamp the
        finish reason, release the slot (and its blocks)."""
        self._set_reason(req, reason)
        req.t_done = time.perf_counter()
        self._obs_retired(slot, req)
        self._release(slot)

    # ------------------------------------------------------------------
    # Fused single-dispatch decode step (repro.serve.fused)
    # ------------------------------------------------------------------

    def _device_state(self) -> Dict[str, Array]:
        """The per-slot device-state dict the fused dispatch consumes:
        tok/pos plus every sampling/stop/budget control, as persistent
        device arrays. Rebuilt from the host mirrors ONLY when admission,
        retirement/abort or block-table growth invalidated it
        (``self._dstate = None``); between those events the dict returned
        by the previous fused dispatch is passed straight back in — the
        steady-state step uploads nothing. Pure block-table growth
        (``_tables_dirty``) patches ``st["tables"]`` alone: one small
        upload instead of a dozen."""
        if self._dstate is not None:
            if self.paged:
                nbl = self._nb_live()
                # growth marks the table dirty; the width check is a
                # belt-and-braces guard for any horizon move without one
                if self._tables_dirty or \
                        self._dstate["tables"].shape[1] != nbl:
                    self._dstate = dict(
                        self._dstate,
                        tables=jnp.asarray(self._decode_tables()[:, :nbl]))
                    self._tables_dirty = False
            return self._dstate
        self._tables_dirty = False
        n = self.n_slots
        temps = np.zeros(n, np.float32)
        top_ks = np.zeros(n, np.int32)
        seeds = np.zeros(n, np.uint32)
        counts = np.zeros(n, np.int32)
        max_new = np.full(n, np.iinfo(np.int32).max, np.int32)
        active = np.zeros(n, np.bool_)
        dec = self.decoding
        for s in dec:
            need = len(self.slot_req[s].params.stop_set)
            while need > self._stop_width:   # monotone pow2: bounded retraces
                self._stop_width *= 2
        stops = np.full((n, self._stop_width), -1, np.int32)
        for s in dec:
            r = self.slot_req[s]
            active[s] = True
            temps[s], top_ks[s] = r.temperature, r.top_k
            # & wraps negative seeds into uint32 range (NumPy 2.x raises
            # on out-of-bounds assignment instead of wrapping)
            seeds[s], counts[s] = r.seed & 0xFFFFFFFF, len(r.out)
            max_new[s] = r.max_new
            stops[s] = stop_id_row(r.params, self._stop_width)
        st = {"tok": jnp.asarray(self.last_tok),
              "pos": jnp.asarray(self.pos),
              "active": jnp.asarray(active),
              "temps": jnp.asarray(temps), "top_ks": jnp.asarray(top_ks),
              "seeds": jnp.asarray(seeds), "counts": jnp.asarray(counts),
              "max_new": jnp.asarray(max_new),
              "stop_ids": jnp.asarray(stops)}
        if self.paged:
            st["tables"] = jnp.asarray(
                self._decode_tables()[:, :self._nb_live()])
        self._dstate = self._state_extras(st)
        return self._dstate

    def _state_extras(self, st: Dict[str, Array]) -> Dict[str, Array]:
        """Subclass hook: extra per-slot device state the fused dispatch
        needs (the mixture server adds its router weights)."""
        return st

    def _pick_args(self, req: Request):
        """The (temp, top_k, seed) device rows for a fused first-token
        pick (count is 0 by construction — the pick IS token 0)."""
        return (jnp.asarray([req.temperature], jnp.float32),
                jnp.asarray([req.top_k], jnp.int32),
                jnp.asarray([req.seed & 0xFFFFFFFF], jnp.uint32))

    def _advance_fused(self, dec: List[int], nxt: np.ndarray,
                       done: np.ndarray) -> List[Request]:
        """Host half of the fused step: record each decoding slot's token
        and retire the slots the device-side ``done`` bitmap flagged — no
        per-slot token inspection, the reason is already decided."""
        retired = []
        t = time.perf_counter()
        for slot in dec:
            req = self.slot_req[slot]
            req.record(int(nxt[slot]), t)
            self.pos[slot] += 1
            self.last_tok[slot] = nxt[slot]
            d = int(done[slot])
            if d:
                reason = DONE_REASONS[d]
                # the device bitmap replaces reason_now(): they must agree
                assert reason == (req.reason_now() or "truncated"), \
                    (slot, reason, req.reason_now())
                self._retire_from_slot(slot, req, reason)
                retired.append(req)
        return retired

    def _run_fused(self, st):
        """Dispatch one fused decode step; returns device (nxt, done) and
        stores the new cache/state on self."""
        raise NotImplementedError

    def _run_fused_chunk(self, st, slot, xc, start, length, cbt, pick):
        """Fused decode + one prefill chunk (+ device-side first-token
        pick); returns device (nxt, done, first)."""
        raise NotImplementedError

    def _run_chunk_only(self, slot, xc, start, length, cbt, pick):
        """One prefill chunk + device-side first-token pick (nothing
        decoding); returns the device (1,) first token."""
        raise NotImplementedError

    def _decode_step_fused(self) -> List[Request]:
        """One scheduler step as ONE jitted device dispatch: model forward
        (+ optional co-scheduled prefill chunk), Eq. 27 mixing where
        applicable, seeded sampling, stop/budget/context checks and the
        position advance all run on device; the host reads back only the
        (next_tok, done) pair — and the chunk's first token on a prefill's
        final chunk."""
        obs = self.obs
        with obs.span("schedule"):
            dec = self.decoding
            self._step_span = 1      # chunk/vanilla steps write one position
            do_chunk = self.chunked and self._schedule_chunk()
            if do_chunk:
                slot, xc, start, length, cbt = self._chunk_args()
                pick = self._pick_args(self.slot_req[slot])
        if not dec and not do_chunk:
            return []
        if do_chunk and not dec:
            self._step_kind = "chunk"
            first = self._dispatch(self._run_chunk_only, slot, xc, start,
                                   length, cbt, pick,
                                   inner=self._chunk_span(slot, start))
            first_h = None
            if int(self.prefill_pos[slot]) + length >= \
                    int(self.prefill_width[slot]):
                first_h = self._read_back(first)    # the final chunk only
            with obs.span("advance"):
                return self._after_chunk_tok(slot, length,
                                             lambda: int(first_h[0]))
        if not do_chunk and self._can_spec:
            retired = self._decode_step_spec(dec)
            if retired is not None:
                return retired
            # pool can't cover the span this step: vanilla single token
        with obs.span("schedule"):
            self._grow_active()
            dec = self.decoding      # growth may have preempted a victim
            st = self._device_state()
        if do_chunk:
            self._step_kind = "decode+chunk"
            nxt, done, first = self._dispatch(
                self._run_fused_chunk, st, slot, xc, start, length, cbt,
                pick, inner=self._chunk_span(slot, start))
            nxt_h, done_h, first_h = self._read_back((nxt, done, first))
            with obs.span("advance"):
                retired = self._advance_fused(dec, nxt_h, done_h)
                retired += self._after_chunk_tok(slot, length,
                                                 lambda: int(first_h[0]))
            return retired
        self._step_kind = "decode"
        nxt, done = self._dispatch(self._run_fused, st)
        nxt_h, done_h = self._read_back((nxt, done))
        with obs.span("advance"):
            return self._advance_fused(dec, nxt_h, done_h)

    def _dispatch(self, run, *args, inner=NULL_SPAN):
        """``run(*args)``, the step's one jitted dispatch, in a
        ``dispatch`` span (around ``inner``, a chunk's own span); the
        always-on histogram times the call alone.

        Every step program consumes (donates) the cache it is given, so
        XLA updates the pool in place. A donation XLA cannot use only
        warns and brings back a whole copy of the pool, so the pool arrays
        passed in are checked afterwards: consumed counts
        ``serve_pool_inplace_total{outcome="kept"}``, still alive
        ``{outcome="copied"}``."""
        pool = self._pool_leaves()
        with self.obs.span("dispatch"), inner:
            t0 = time.perf_counter()
            out = run(*args)
            t1 = time.perf_counter()
        self.obs.dispatch_s.observe(t1 - t0)
        if pool:
            kept = all(a.is_deleted() for a in pool)
            (self.obs.pool_kept if kept else self.obs.pool_copied).inc()
        return out

    def _pool_leaves(self) -> List[Array]:
        """The cache's paged pool arrays (none without a paged layout)."""
        if not self.paged:
            return []
        return [a for a, s in zip(jax.tree.leaves(self.cache),
                                  jax.tree.leaves(self.spec.paged.seq_axes))
                if s >= 0]

    def _read_back(self, arrays):
        """The step's one ``jax.device_get``, in a ``device_get`` span and
        timed by the always-on histogram."""
        with self.obs.span("device_get"):
            t0 = time.perf_counter()
            host = jax.device_get(arrays)
            t1 = time.perf_counter()
        self.obs.readback_s.observe(t1 - t0)
        return host

    def _chunk_span(self, slot: int, start: int):
        """The ``prefill_chunk[i]`` span of the chunk dispatched for
        ``slot``, on the slot's track with its ``rid`` and ``start``."""
        obs = self.obs
        if not obs.trace.enabled:
            return NULL_SPAN
        return obs.span(f"prefill_chunk[{start // self.chunk}]",
                        obs.slot_tid(slot), rid=self.slot_req[slot].rid,
                        start=start)

    def _obs_phase_flip(self, slot: int, req: Request) -> None:
        """Prefill → decode transition: close the request's ``prefill``
        span and open its ``decode`` phase at the same stamp (phases share
        boundaries, so a request's spans tile its latency exactly)."""
        t = time.perf_counter()
        t0 = getattr(req, "_obs_t_phase", 0.0)
        tr = self.obs.trace
        if tr.enabled and t0:
            tr.complete("prefill", t0, t, self.obs.slot_tid(slot),
                        args={"rid": req.rid})
        req._obs_phase = "decode"
        req._obs_t_phase = t

    # ------------------------------------------------------------------
    # Speculative decoding: draft + multi-token verify (repro.serve.
    # speculate / Model.verify_step_paged / fused.verify_epilogue)
    # ------------------------------------------------------------------

    def _decode_step_spec(self, dec: List[int]) -> Optional[List[Request]]:
        """One speculative step, still a single dispatch + single
        ``device_get``: reserve every decoding slot's span blocks, build
        the drafts (host n-gram lookup, or None for on-device expert
        drafting), run the fused verify and advance each slot by its
        accepted run. None → the pool can't cover the span; the caller
        falls back to the vanilla one-token step (the output trajectory
        is identical either way — only the step size changes)."""
        span = self.spec_len
        with self.obs.span("schedule"):
            if not self._grow_active_span(span):
                return None
            self._step_span = span   # sanitizer plan + _nb_live horizon
            st = self._device_state()
            drafts = self._draft_tokens(dec) if self._ngram is not None \
                else None
        self._step_kind = "spec_verify"
        toks, n_emit, done = self._dispatch(self._run_verify, st, drafts)
        toks_h, n_h, done_h = self._read_back((toks, n_emit, done))
        with self.obs.span("advance"):
            return self._advance_span(dec, toks_h, n_h, done_h)

    def _draft_tokens(self, dec: List[int]) -> Array:
        """Host-side n-gram drafts, one row per slot. Idle / mid-prefill
        rows stay zero: their verify writes land in the scratch block
        (tables masked / zeroed) and the epilogue masks their outputs."""
        drafts = np.zeros((self.n_slots, self.spec_len - 1), np.int32)
        for s in dec:
            r = self.slot_req[s]
            drafts[s] = self._ngram.propose(
                np.concatenate([r.tokens, np.asarray(r.out, np.int32)]))
        return jnp.asarray(drafts)

    def _run_verify(self, st, drafts):
        """Dispatch one fused verify step; returns device
        ``(toks, n_emit, done)`` and stores the new cache/state on self.
        ``drafts`` is None when the verify fn drafts on device."""
        raise NotImplementedError

    def _advance_span(self, dec: List[int], toks: np.ndarray,
                      n_emit: np.ndarray, done: np.ndarray
                      ) -> List[Request]:
        """Host half of the speculative step: record each decoding slot's
        ACCEPTED run (1..spec_len tokens — forward progress is >= the
        vanilla step by construction) and retire the slots the device
        ``done`` bitmap flagged. The device already truncated each span
        at its first stop/budget/context halt, so a request finishing
        mid-span records nothing past its terminal token and retires
        exactly once — ``stats()['stopped']`` counts it once too."""
        retired = []
        t = time.perf_counter()
        obs = self.obs
        accepted = 0
        for slot in dec:
            req = self.slot_req[slot]
            n = int(n_emit[slot])
            for j in range(n):
                req.record(int(toks[slot, j]), t)
            self.pos[slot] += n
            if n:
                self.last_tok[slot] = toks[slot, n - 1]
            obs.spec_steps.inc()
            obs.spec_tokens.inc(n)
            obs.accept_len.observe(n)
            # per-request diagnostics: n - 1 of the step's spec_len - 1
            # drafts were accepted (the first token is the committed one)
            req.spec_req_steps += 1
            req.spec_req_accepted += max(n - 1, 0)
            accepted += max(n - 1, 0)
            d = int(done[slot])
            if d:
                reason = DONE_REASONS[d]
                # the device bitmap replaces reason_now(): they must agree
                assert reason == (req.reason_now() or "truncated"), \
                    (slot, reason, req.reason_now())
                self._retire_from_slot(slot, req, reason)
                retired.append(req)
        if dec and self.spec_len > 1:
            src = self.speculative or "ngram"
            obs.drafts(src, "proposed").inc(len(dec) * (self.spec_len - 1))
            obs.drafts(src, "accepted").inc(accepted)
        return retired

    # ------------------------------------------------------------------
    # Token selection: greedy fast path / per-request seeded sampling
    # ------------------------------------------------------------------

    def _pick_first(self, req: Request, row, *,
                    from_probs: bool = False) -> int:
        """First token from a prefill's last-position scores ((V,) row).
        Greedy unless the request asked for sampling; token index 0 of the
        request's seeded stream either way. One jitted dispatch for BOTH
        paths (greedy rows take the argmax inside ``sample_tokens``) — the
        eager ``jnp.argmax`` this replaces cost a separate device sync per
        admitted request. The chunked path avoids even this dispatch: its
        pick is fused into the final chunk's step (``pick_first``).
        Probability rows route through ``sample_tokens_probs`` so the
        floor + log transform rides the same dispatch — the eager
        ``jnp.log`` it replaces was a host-path dispatch repro-lint
        flags."""
        fn = sample_tokens_probs if from_probs else sample_tokens
        return int(fn(
            row[None], jnp.asarray([req.temperature], jnp.float32),
            jnp.asarray([req.top_k], jnp.int32),
            jnp.asarray([req.seed & 0xFFFFFFFF], jnp.uint32),
            jnp.asarray([len(req.out)], jnp.int32))[0])

    def _next_tokens(self, scores, *, from_probs: bool = False) -> np.ndarray:
        """Next token per slot from the lockstep dispatch's (n_slots, V)
        scores. All-greedy steps take the jitted argmax fast path
        (``argmax_tokens`` — the eager ``jnp.argmax`` it replaces was an
        un-fused dispatch + implicit sync per step, the PR 6 incident
        repro-lint's host-sync rule now catches); any sampled slot routes
        the whole step through the jitted seeded sampler (greedy rows
        still take their argmax inside it, probability rows fold the
        floor + log into the same dispatch)."""
        dec = self.decoding
        if all(self.slot_req[s].temperature <= 0 for s in dec):
            return np.asarray(argmax_tokens(scores), dtype=np.int32)
        fn = sample_tokens_probs if from_probs else sample_tokens
        temps = np.zeros(self.n_slots, np.float32)
        top_ks = np.zeros(self.n_slots, np.int32)
        seeds = np.zeros(self.n_slots, np.uint32)
        counts = np.zeros(self.n_slots, np.int32)
        for s in dec:
            r = self.slot_req[s]
            temps[s], top_ks[s] = r.temperature, r.top_k
            # & wraps negative seeds into uint32 range (NumPy 2.x raises
            # on out-of-bounds assignment instead of wrapping)
            seeds[s], counts[s] = r.seed & 0xFFFFFFFF, len(r.out)
        return np.asarray(fn(
            scores, jnp.asarray(temps), jnp.asarray(top_ks),
            jnp.asarray(seeds), jnp.asarray(counts)), dtype=np.int32)

    # ------------------------------------------------------------------
    # Observability
    # ------------------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        """Serving stats: active slots, waiting depth, aborted/stopped
        counters, pool free blocks, prefix-cache hit rate — the numbers
        the serve log and ``occupancy()`` surface. Since PR 9 this is a
        *view* over the engine's metrics registry (``self.metrics``) —
        same keys and values as ever, one source of truth underneath.
        The aborted/stopped counters are per-``serve()``-run (each drain
        loop starts by ``reset_stats()``); driving ``step()`` directly
        accumulates them until ``reset_stats()`` is called."""
        out: Dict[str, Any] = {"active": len(self.active),
                               "waiting": len(self.waiting),
                               "aborted": self.n_aborted,
                               "stopped": self.n_stopped}
        if self.paged:
            out["pool_free_blocks"] = self.allocator.n_free
            out["pool_blocks"] = self.allocator.n_blocks
        if self.speculative is not None:
            out["spec_steps"] = self.n_spec_steps
            out["spec_tokens"] = self.n_spec_tokens
            out["spec_tokens_per_step"] = (
                self.n_spec_tokens / self.n_spec_steps
                if self.n_spec_steps else 0.0)
        if self.prefix is not None:
            out.update(self.prefix.stats())
        if self.sanitizer is not None:
            out.update(self.sanitizer.stats())
        if self.qos is not None or self.preemption != "off":
            out["parked"] = len(self._parked)
            out["tenants"] = self._tenant_breakdown()
        return out

    def _tenant_breakdown(self) -> Dict[str, Dict[str, int]]:
        """Per-tenant view: cumulative counters (tokens at retirement,
        preemptions, resumes, rejections) plus the live picture — active
        slots, pool blocks held by those slots, blocks pinned by parked
        requests, and tokens emitted by still-running requests."""
        def zero() -> Dict[str, int]:
            return {"tokens": 0, "preemptions": 0, "resumes": 0,
                    "rejections": 0, "active_slots": 0, "pool_blocks": 0,
                    "parked": 0, "pinned_blocks": 0, "tokens_live": 0}
        tenants: Dict[str, Dict[str, int]] = {}
        for t, st in self._tenant_stats.items():
            tenants[t] = dict(zero(), **st)
        for slot in self.active:
            req = self.slot_req[slot]
            d = tenants.setdefault(tenant_of(req), zero())
            d["active_slots"] += 1
            d["pool_blocks"] += int(self.n_alloc[slot])
            d["tokens_live"] += len(req.out)
        for st in self._parked.values():
            d = tenants.setdefault(tenant_of(st.req), zero())
            d["parked"] += 1
            d["pinned_blocks"] += len(st.pinned)
        return tenants

    @property
    def metrics(self) -> _obs_metrics.MetricsRegistry:
        """The engine's private metrics registry (always live; published
        to ``repro.obs.default_registry()`` when the config set
        ``metrics=True``)."""
        return self.obs.registry

    def reset_stats(self) -> None:
        """Documented per-run counter hygiene: zero the request-lifecycle
        counters (``aborted`` and the per-reason retirement counters
        behind ``stopped``) so back-to-back ``serve()`` runs on one
        engine never report a previous run's terminal counts. Cumulative
        telemetry — latency histograms, speculative and prefix-cache
        totals — is untouched; zero *everything* with the registry-wide
        ``engine.metrics.reset()``."""
        self.obs.reset_run_counters()

    def export_trace(self, path: Optional[str] = None) -> dict:
        """The recorded span trace as a Chrome/Perfetto ``trace_event``
        JSON object (empty unless the engine was built with
        ``EngineConfig(trace=True)``). Load the written file directly in
        ``ui.perfetto.dev`` or ``chrome://tracing``."""
        doc = self.obs.trace.to_chrome()
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc

    def export_metrics(self, path: Optional[str] = None) -> dict:
        """JSON snapshot of the engine's metrics registry (optionally
        written to ``path``). Prometheus text is ``prometheus_metrics``."""
        doc = self.obs.registry.to_dict()
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f, indent=1)
        return doc

    def prometheus_metrics(self) -> str:
        """Prometheus text exposition of this engine's registry."""
        return self.obs.registry.to_prometheus()

    # ------------------------------------------------------------------
    # Chunked prefill: admission, chunk scheduling, decode transition
    # ------------------------------------------------------------------

    def _admit_chunked(self, req: Request, slot: int, width: int,
                       prep) -> bool:
        """Shared chunked admission: validate, match the prompt against the
        prefix cache (hit blocks are mapped read-only into the table and
        their positions skipped), reserve the remaining blocks (the WHOLE
        width up front, so a chunk can never strand mid-prompt on an
        exhausted pool), embed the prompt + build the carry via
        ``prep(batch)``, slice off the cached prefix, pre-split the suffix
        into per-chunk tensors, and park the slot mid-prefill at the first
        uncached position. False → pool can't reserve right now; the
        request stays pending (the match re-runs on retry, so a prefix
        evicted meanwhile is simply re-prefilled)."""
        self._reject_overlong(req, width)
        toks = req.prefill_tokens    # resume: prompt + generated tokens
        base, shared, keys = 0, [], None
        if self.prefix is not None:
            # memoized per request: a pool-blocked admission retries every
            # step, and the keys (incl. the extras digest) are immutable —
            # but a resume's token span differs from the original prompt,
            # so the memo is keyed by span length too
            cached = getattr(req, "_prefix_keys", None)
            memo_key = (self.block_size, len(toks))
            if cached is None or cached[0] != memo_key:
                keys = block_keys(toks, req.extras, self.block_size,
                                  width // self.block_size,
                                  n_prefix=width - len(toks))
                req._prefix_keys = (memo_key, keys)
            else:
                keys = cached[1]
            with self.obs.span("prefix_match", self.obs.slot_tid(slot),
                               rid=req.rid) as span:
                shared = self.prefix.match(keys, width)
                span.note(hit_blocks=len(shared))
            base = len(shared) * self.block_size
        if self.paged and not self._reserve(slot, width, shared=shared):
            return False
        if self.prefix is not None:
            self.prefix.record(width, base)
        pad = -(width - base) % self.chunk
        b = req.batch(pad_to=len(toks) + pad)
        x, carry = prep(b)
        if base:
            x = jax.lax.slice_in_dim(x, base, x.shape[self._seq_axis],
                                     axis=self._seq_axis)
        chunks = tuple(jnp.split(x, x.shape[self._seq_axis] // self.chunk,
                                 axis=self._seq_axis))
        self._occupy_prefilling(slot, req, width, chunks, carry,
                                base=base, keys=keys)
        return True

    def _occupy_prefilling(self, slot: int, req: Request, width: int,
                           x, carry, *, base: int = 0, keys=None) -> None:
        """Hold a slot in the mid-prefill state: the embedded prompt suffix
        (as a tuple of per-chunk tensors) and the chunk carry are per-slot
        host state, the slot's block table already covers the prompt
        (leading entries may be shared cached blocks — the prefill starts
        at ``base``, the first uncached position), and its decode-side rows
        stay inert (pos 0, table masked to scratch) until the transition."""
        self.slot_req[slot] = req
        self.prefilling[slot] = True
        self.prefill_pos[slot] = base
        self.prefill_base[slot] = base
        self.prefill_width[slot] = width
        self.prefill_x[slot] = x
        self.prefill_carry[slot] = carry
        self.prefill_keys[slot] = keys
        self.prefill_order.append(slot)
        self.pos[slot] = 0
        self.last_tok[slot] = 0
        self._dstate = None          # table masking changed for this slot

    def _decode_tables(self) -> np.ndarray:
        """Block tables as the decode dispatch must see them: mid-prefill
        slots are masked to the scratch block so the lockstep decode's
        writes for those rows can never touch the blocks their chunks are
        filling."""
        if not self.prefill_order:
            return self.block_tables
        bt = self.block_tables.copy()
        bt[self.prefill_order] = 0
        return bt

    def _nb_live(self) -> int:
        """Logical-block horizon of the decode dispatch: columns past
        ``max(pos) // block + 1`` hold no key any slot can attend (the
        position mask zeroes them), so the tables are truncated to this
        width before upload — the gather AND the attention span shrink to
        the live region, the jnp analogue of the kernel's pos-derived
        block skip. Ring (sliding-window) layouts address the full
        logical span and are never truncated. The dispatch retraces once
        per distinct width — at most ``nb_slot`` shapes, all warmed by
        the first request that decodes to full depth."""
        if self.ring:
            return self.nb_slot
        # a speculative step writes (and attends) up to _step_span - 1
        # positions past pos, so the horizon covers the whole span
        mx = int(self.pos.max(initial=0)) + self._step_span - 1
        return min(mx // self.block_size + 1, self.nb_slot)

    def _schedule_chunk(self) -> bool:
        """Token-budget admission of one prefill chunk into this step:
        decoding slots count one token each and always run (starvation
        freedom for decodes); the chunk rides along when it fits the budget,
        and runs alone when nothing is decoding."""
        if not self.prefill_order:
            return False
        n_dec = len(self.decoding)
        return n_dec == 0 or n_dec + self.chunk <= self.token_budget

    def _pick_chunk_slot(self) -> int:
        """This step's prefill-chunk slot. FCFS (``prefill_order`` head)
        without QoS; with a QoSConfig, deficit round robin across the
        tenants that have a mid-prefill slot (one chunk = one charge),
        FCFS within a tenant. Cached per step so the sanitizer's shadow
        replay and the dispatch see the same pick without double-charging
        the DRR."""
        pick = self._chunk_pick
        if pick is not None and self.prefilling[pick]:
            return pick
        pick = self.prefill_order[0]
        if self.qos is not None and len(self.prefill_order) > 1:
            heads: Dict[str, int] = {}
            for s in self.prefill_order:
                t = tenant_of(self.slot_req[s])
                if t not in heads:
                    heads[t] = s
            if len(heads) > 1:
                chosen = self._drr_chunk.pick(
                    {t: self.chunk for t in heads})
                pick = heads[chosen]
        self._chunk_pick = pick
        return pick

    def _chunk_args(self):
        """(slot, x_chunk, start, length, block_table) for this step's
        mid-prefill slot (``_pick_chunk_slot``). The prompt was pre-split
        into chunk tensors at admission, so picking this step's chunk
        costs no dispatch; ``length`` masks the final chunk's padding."""
        slot = self._pick_chunk_slot()
        start = int(self.prefill_pos[slot])
        length = min(self.chunk, int(self.prefill_width[slot]) - start)
        xc = self.prefill_x[slot][
            (start - int(self.prefill_base[slot])) // self.chunk]
        cbt = jnp.asarray(self.block_tables[slot]) if self.paged \
            else jnp.zeros((1,), jnp.int32)
        return slot, xc, start, length, cbt

    def _after_chunk(self, slot: int, length: int, c_out) -> List[Request]:
        """Unfused wrapper over ``_after_chunk_tok``: the first token is
        picked eagerly from the chunk's output scores."""
        req = self.slot_req[slot]
        return self._after_chunk_tok(
            slot, length,
            lambda: self._pick_first(req, c_out[0],
                                     from_probs=self._from_probs))

    def _after_chunk_tok(self, slot: int, length: int,
                         first_fn) -> List[Request]:
        """Advance a slot's prefill by one chunk; on the final chunk take
        the first token from ``first_fn`` (unfused: an eager pick from the
        chunk scores; fused: materializing the device-side pick that rode
        the chunk dispatch — intermediate chunks never call it, keeping
        their zero-sync property), register the prompt's full blocks with
        the prefix cache, splice the carry's direct-leaf state into the
        batched cache, and transition the slot to decode (or retire, for
        context-filling prompts and max_new == 1)."""
        self.prefill_pos[slot] += length
        if int(self.prefill_pos[slot]) < int(self.prefill_width[slot]):
            return []
        req = self.slot_req[slot]
        first = int(first_fn())
        width = int(self.prefill_width[slot])
        self.prefill_order.remove(slot)
        self.prefilling[slot] = False
        self.prefill_x[slot] = None
        carry, self.prefill_carry[slot] = self.prefill_carry[slot], None
        if self.prefix is not None:
            # the prompt's full blocks are now whole and immutable (decode
            # writes land past the prompt): make them shareable — BEFORE
            # any retirement below releases them to the LRU list
            n_full = width // self.block_size
            self.prefix.insert(self.prefill_keys[slot] or [],
                               self.block_tables[slot, :n_full])
        self.prefill_keys[slot] = None
        self.prefill_base[slot] = 0
        if width >= self.cache_len:      # prompt fills the context bound
            req.record(first)
            self._retire_from_slot(slot, req,
                                   req.reason_now() or "truncated")
            return [req]
        self.cache = self.spec.insert_direct(self.cache, carry, slot)
        self._obs_phase_flip(slot, req)
        self._occupy(slot, req, first, width)
        reason = req.reason_now()        # max_new == 1, or first tok stops
        if reason:
            self._retire_from_slot(slot, req, reason)
            return [req]
        return []

    def _drop_details(self) -> List[str]:
        """Progress annotation for every request still holding a slot — a
        mid-prefill request reports its partial position (it is neither
        queued nor decoding, and used to fall through drop accounting)."""
        out = []
        for slot, r in enumerate(self.slot_req):
            if r is None:
                continue
            if self.prefilling[slot]:
                out.append(f"{r.rid} (prefill {int(self.prefill_pos[slot])}"
                           f"/{int(self.prefill_width[slot])})")
            else:
                out.append(f"{r.rid} (decode pos {int(self.pos[slot])})")
        return out

    def serve(self, queue: List[Request], *, max_steps: int = 10_000
              ) -> Dict[int, List[int]]:
        """Drive a queue to completion — a thin drain loop over the
        incremental API (``add_request`` everything, ``step`` until
        nothing is unfinished, collect the finished outputs).

        Each run starts with ``reset_stats()``: the ``aborted``/
        ``stopped`` counts ``stats()`` reports afterwards are THIS run's,
        never stale totals accumulated across earlier ``serve()`` calls
        on the same engine.

        Admission can fail transiently on a paged server (not enough free
        KV blocks yet) — the request stays pending until retirements free
        blocks. Exhausting ``max_steps`` with unfinished requests raises
        (never a silent drop); every unfinished request is reported with its
        progress, including mid-prefill requests with their partial
        position.
        """
        self.reset_stats()
        for req in queue:
            self.add_request(req)
        finished: Dict[int, List[int]] = {}
        reasons: Dict[int, str] = {}
        for _ in range(max_steps):
            for out in self.step():
                if out.finished:
                    finished[out.rid] = out.token_ids
                    reasons[out.rid] = out.finish_reason
            if not self.has_unfinished():
                break
        dropped = [f"{r.rid} (queued)" for r in self.waiting] + \
            self._drop_details()
        if dropped:
            _raise_dropped(dropped, len(finished), max_steps)
        logger.info("serve: %d finished (finish_reasons %s), stats %s",
                    len(finished), reasons, self.stats())
        return finished


def _legacy_config(n_slots: int, cache_len: int, *, page_block: int,
                   pool_blocks: int, chunk: int, token_budget: int,
                   prefix_cache: bool, use_kernel: bool,
                   fused_step: bool = True,
                   strategy: str = "top1") -> EngineConfig:
    """Map the pre-redesign constructor kwargs onto an ``EngineConfig`` so
    every entry point funnels through one ``validate()``."""
    return EngineConfig(
        n_slots=n_slots, cache_len=cache_len, paged=page_block > 0,
        page_block=page_block if page_block > 0 else 16,
        pool_blocks=pool_blocks, chunked_prefill=chunk > 0,
        chunk=chunk if chunk > 0 else 16, token_budget=token_budget,
        prefix_cache=prefix_cache, fused_step=fused_step,
        use_kernel=use_kernel, strategy=strategy)


def make_chunk_fns(model: Model, cache_len: int, chunk: int, *,
                   use_kernel: bool = False, paged: bool = False):
    """The jitted chunked-prefill function family one SlotServer runs on
    (shared across the pods of a top-1 DecentralizedSlotServer, like
    ``make_serve_fns``): admission prep (embed the padded prompt and build
    the carry in one dispatch — admission then slices off any cached
    prefix and pre-splits the suffix into per-chunk tensors, so a chunk
    STEP still issues no eager slicing), the FUSED step — decode every
    decoding slot AND consume one prefill chunk in a single dispatch —
    and the chunk-only step for a server with nothing decoding. ``prep``
    retraces once per distinct padded prompt width (widths are rounded to
    whole chunks, so the bucket count stays small).

    The fusion is safe with zero ordering constraints because the two
    halves touch disjoint state: decode writes land in the decoding slots'
    own physical blocks (the chunk slot's table row is masked to scratch),
    the chunk writes land in its own reserved blocks, and the chunk's
    recurrent state flows through its carry — the lockstep decode's
    garbage updates to the mid-prefill slot's cache rows are overwritten by
    ``insert_direct`` at the transition. Both steps consume (donate) their
    cache argument, like every step program (``make_fused_fns``)."""
    def top1_prep(p, b):
        x = model.embed_prompt(p, b)                    # (1, W, D)
        return x, model.init_chunk_carry(p, b, cache_len)

    def top1_chunk_logits(p, c, carry, xc, start, ln, cbt):
        return model.prefill_chunk(p, c, carry, xc, start, ln, cbt,
                                   use_kernel=use_kernel)

    if paged:
        def top1_decode_chunk_logits(p, c, toks, pos, dbt, carry, xc,
                                     start, ln, cbt):
            d_logits, c = model.decode_step_paged(p, c, toks, pos, dbt,
                                                  use_kernel=use_kernel)
            c_logits, carry, c = model.prefill_chunk(
                p, c, carry, xc, start, ln, cbt, use_kernel=use_kernel)
            return d_logits, c_logits, carry, c
    else:
        def top1_decode_chunk_logits(p, c, toks, pos, carry, xc, start, ln,
                                     cbt):
            d_logits, c = model.decode_step(p, c, toks, pos,
                                            use_kernel=use_kernel)
            c_logits, carry, c = model.prefill_chunk(
                p, c, carry, xc, start, ln, cbt, use_kernel=use_kernel)
            return d_logits, c_logits, carry, c
    return (jax.jit(top1_prep),
            jax.jit(top1_decode_chunk_logits, donate_argnums=(1,)),
            jax.jit(top1_chunk_logits, donate_argnums=(1,)))


def make_serve_fns(model: Model, cache_len: int, *, use_kernel: bool = False,
                   paged: bool = False):
    """The jitted (prefill, decode) pair one SlotServer runs on. Params are
    an explicit argument, so pods serving different experts of the same
    model SHARE one pair (one trace/compile instead of K). With ``paged``
    the decode fn takes the per-slot block tables as its last argument.
    The decode consumes (donates) its cache argument."""
    def top1_prefill(p, b):
        return model.prefill(p, b, cache_len, use_kernel=use_kernel)

    if paged:
        def top1_decode_logits(p, c, t, pos, bt):
            return model.decode_step_paged(p, c, t, pos, bt,
                                           use_kernel=use_kernel)
    else:
        def top1_decode_logits(p, c, t, pos):
            return model.decode_step(p, c, t, pos, use_kernel=use_kernel)
    return (jax.jit(top1_prefill),
            jax.jit(top1_decode_logits, donate_argnums=(1,)))


def make_fused_fns(model: Model, cache_len: int, chunk: int = 0, *,
                   use_kernel: bool = False, paged: bool = False):
    """The jitted fused-step function family one SlotServer runs on
    (shared across the pods of a top-1 DecentralizedSlotServer, like
    ``make_serve_fns``). Returns ``(top1_fused_decode,
    top1_fused_decode_chunk, top1_chunk_only)``, the names the device
    trace shows them by:

    * ``top1_fused_decode(params, cache, state)`` → ``(cache, state,
      next_tok, done)`` — the WHOLE decode token (forward + sampling +
      stop/budget/context checks + position advance) in one dispatch
      (``Model.fused_decode_step``);
    * ``top1_fused_decode_chunk(params, cache, state, carry, xc, start,
      length, cbt, temp, top_k, seed)`` — the same with one co-scheduled
      prefill chunk and its device-side first-token pick fused in;
    * ``top1_chunk_only(params, cache, carry, xc, start, length, cbt, temp,
      top_k, seed)`` → ``(first, carry, cache)`` — a chunk with nothing
      decoding. The last two are None when ``chunk == 0``.

    Each consumes (donates) its cache argument: with the pool riding the
    layer loop's carry and written in place (``Model._paged_layers``), the
    step then touches only the rows it writes. Callers rebind the cache
    they get back and never read the one they passed.
    """
    def top1_fused_decode(p, c, st):
        return model.fused_decode_step(p, c, st, cache_len=cache_len,
                                       use_kernel=use_kernel, paged=paged)

    if chunk <= 0:
        return jax.jit(top1_fused_decode, donate_argnums=(1,)), None, None

    def top1_fused_decode_chunk(p, c, st, carry, xc, start, ln, cbt, temp,
                                top_k, seed):
        c, st, nxt, done = model.fused_decode_step(
            p, c, st, cache_len=cache_len, use_kernel=use_kernel,
            paged=paged)
        c_out, carry, c = model.prefill_chunk(p, c, carry, xc, start, ln,
                                              cbt, use_kernel=use_kernel)
        first = pick_first(c_out, temp, top_k, seed)
        return c, st, nxt, done, first, carry

    def top1_chunk_only(p, c, carry, xc, start, ln, cbt, temp, top_k,
                        seed):
        c_out, carry, c = model.prefill_chunk(p, c, carry, xc, start, ln,
                                              cbt, use_kernel=use_kernel)
        return pick_first(c_out, temp, top_k, seed), carry, c

    return (jax.jit(top1_fused_decode, donate_argnums=(1,)),
            jax.jit(top1_fused_decode_chunk, donate_argnums=(1,)),
            jax.jit(top1_chunk_only, donate_argnums=(1,)))


def make_verify_fns(model: Model, cache_len: int, *,
                    use_kernel: bool = False):
    """The jitted speculative verify step one SlotServer runs on (shared
    across the pods of a top-1 DecentralizedSlotServer, like
    ``make_fused_fns``): ``verify(params, cache, state, drafts)`` →
    ``(cache, state, toks, n_emit, done)`` — the span forward over
    ``[committed token, drafts]`` plus the accept/reject epilogue in one
    dispatch (``Model.fused_verify_step``). Traces once per drafts width,
    which is fixed at ``spec_len - 1`` for an engine's lifetime. Consumes
    (donates) its cache argument."""
    def top1_fused_verify(p, c, st, drafts):
        return model.fused_verify_step(p, c, st, drafts,
                                       cache_len=cache_len,
                                       use_kernel=use_kernel)
    return jax.jit(top1_fused_verify, donate_argnums=(1,))


class SlotServer(_SlotTable):
    """Continuous batching over ONE expert / model (greedy decoding).

    ``page_block > 0`` switches the attention KV leaves to the paged cache:
    ``pool_blocks`` physical blocks of ``page_block`` positions shared by
    all slots (0 → sized for full capacity, i.e. no admission blocking).

    ``chunk > 0`` switches admission to chunked prefill: the prompt is
    consumed ``chunk`` positions at a time, written straight into the paged
    pool, and each chunk rides the same jitted dispatch as the lockstep
    decode — no more stop-the-world prefill. ``token_budget`` bounds the
    per-step token work (decoding slots + chunk).

    ``prefix_cache=True`` (needs paging + chunked prefill) makes the pool
    blocks content-addressed and shareable: admissions whose prompts share
    a cached prefix map the shared blocks read-only and start chunked
    prefill at the first uncached position. Families whose decode state
    accumulates outside the pool (ssm, hybrid — see
    ``Model.prefix_cacheable``) degrade to the uncached path.
    """

    def __init__(self, model: Model, params, n_slots: int = 0,
                 cache_len: int = 0, *, use_kernel: bool = False,
                 serve_fns=None, page_block: int = 0, pool_blocks: int = 0,
                 chunk: int = 0, token_budget: int = 0, chunk_fns=None,
                 prefix_cache: bool = False, fused_step: bool = True,
                 fused_fns=None, verify_fns=None,
                 config: Optional[EngineConfig] = None, pod: int = 0):
        if config is None:
            config = _legacy_config(
                n_slots, cache_len, page_block=page_block,
                pool_blocks=pool_blocks, chunk=chunk,
                token_budget=token_budget, prefix_cache=prefix_cache,
                fused_step=fused_step, use_kernel=use_kernel)
        config.validate(model)
        self.config = config
        n_slots, cache_len = config.n_slots, config.cache_len
        use_kernel = config.use_kernel
        page_block = effective_page_block(
            model, config.page_block if config.paged else 0)
        chunk = config.chunk if config.chunked_prefill else 0
        super().__init__(n_slots, cache_len, block_size=page_block,
                         n_blocks=config.pool_blocks,
                         window=model.cfg.sliding_window, chunk=chunk,
                         token_budget=config.token_budget,
                         prefix_cache=config.prefix_cache
                         and model.prefix_cacheable,
                         sanitize=config.sanitize,
                         qos=config.qos, preemption=config.preemption,
                         obs=EngineObs(pod=pod, trace=config.trace,
                                       trace_ring=config.trace_ring,
                                       publish=config.metrics,
                                       annotate=jax.profiler.TraceAnnotation))
        self.model, self.params = model, params
        self.use_kernel = use_kernel
        if self.paged:
            self.cache = model.init_paged_cache(
                n_slots, self.allocator.n_blocks, page_block, cache_len)
            self.spec = model.cache_spec(page_block)
        else:
            self.cache = model.init_cache(n_slots, cache_len)
            self.spec = model.cache_spec()
        self._prefill, self._decode = serve_fns or make_serve_fns(
            model, cache_len, use_kernel=use_kernel, paged=self.paged)
        if self.chunked:
            self._prep, self._fused, self._chunk_only = \
                chunk_fns or make_chunk_fns(model, cache_len, chunk,
                                            use_kernel=use_kernel,
                                            paged=self.paged)
        self.fused = config.fused_step
        if self.fused:
            self._fstep, self._fstep_chunk, self._fchunk_only = \
                fused_fns or make_fused_fns(model, cache_len, chunk,
                                            use_kernel=use_kernel,
                                            paged=self.paged)
        self._init_speculation(
            config, model,
            lambda: verify_fns or make_verify_fns(model, cache_len,
                                                  use_kernel=use_kernel))

    def admit(self, req: Request) -> bool:
        """Admit a request into a free slot. Monolithic: prefill it alone
        and insert its decode state. Chunked: embed the prompt, reserve its
        blocks, and park the slot mid-prefill — the step loop consumes the
        prompt chunk by chunk. False when no slot — or, paged, not enough
        free blocks."""
        free = self.free_slots()
        if not free:
            return False
        slot = free[0]
        width = self._prefill_width(req)
        if self.chunked:
            return self._admit_chunked(
                req, slot, width, lambda b: self._prep(self.params, b))
        if not self._admission_precheck(req, slot, width):
            return False
        logits, row_cache = self._prefill(self.params, req.batch())
        # first token from the prompt's last position (greedy / sampled)
        first = self._pick_first(req, logits[0, -1])
        # logits width = positions consumed (incl. any image prefix)
        assert logits.shape[1] == width, (logits.shape, width)
        if width == self.cache_len:
            self._retire_at_admission(req, first)
            return True
        self._admit_prefilled(slot, req, first, width, row_cache)
        return True

    def _run_fused(self, st):
        self.cache, self._dstate, nxt, done = self._fstep(
            self.params, self.cache, st)
        return nxt, done

    def _run_verify(self, st, drafts):
        self.cache, self._dstate, toks, n_emit, done = self._vstep(
            self.params, self.cache, st, drafts)
        return toks, n_emit, done

    def _run_fused_chunk(self, st, slot, xc, start, length, cbt, pick):
        (self.cache, self._dstate, nxt, done, first,
         self.prefill_carry[slot]) = self._fstep_chunk(
            self.params, self.cache, st, self.prefill_carry[slot], xc,
            start, length, cbt, *pick)
        return nxt, done, first

    def _run_chunk_only(self, slot, xc, start, length, cbt, pick):
        first, self.prefill_carry[slot], self.cache = self._fchunk_only(
            self.params, self.cache, self.prefill_carry[slot], xc, start,
            length, cbt, *pick)
        return first

    def _decode_step(self) -> List[Request]:
        """One raw scheduler dispatch. Monolithic: lockstep decode over
        every active slot. Chunked: co-schedule the lockstep decode with
        one prefill chunk under the token budget, in a single jitted
        dispatch. Fused (the default): the host epilogue rides the same
        dispatch too — see ``_decode_step_fused``. Returns requests
        retired this step."""
        if self.fused:
            return self._decode_step_fused()
        dec = self.decoding
        do_chunk = self.chunked and self._schedule_chunk()
        if not dec and not do_chunk:
            return []
        self._step_kind = "unfused"
        run = self._dispatch
        if do_chunk:
            slot, xc, start, length, cbt = self._chunk_args()
            if not dec:
                c_out, carry, self.cache = run(
                    self._chunk_only, self.params, self.cache,
                    self.prefill_carry[slot], xc, start, length, cbt)
                self.prefill_carry[slot] = carry
                return self._after_chunk(slot, length, c_out)
            self._grow_active()
            # the decode's block tables, on a paged cache
            tables = (jnp.asarray(
                self._decode_tables()[:, :self._nb_live()]),) \
                if self.paged else ()
            d_logits, c_out, carry, self.cache = run(
                self._fused, self.params, self.cache,
                jnp.asarray(self.last_tok), jnp.asarray(self.pos), *tables,
                self.prefill_carry[slot], xc, start, length, cbt)
            self.prefill_carry[slot] = carry
            nxt = self._next_tokens(d_logits)
            retired = self._advance(nxt)
            retired += self._after_chunk(slot, length, c_out)
            return retired
        if self.paged:
            self._grow_active()
            logits, self.cache = run(
                self._decode, self.params, self.cache,
                jnp.asarray(self.last_tok), jnp.asarray(self.pos),
                jnp.asarray(self._decode_tables()[:, :self._nb_live()]))
        else:
            logits, self.cache = run(
                self._decode, self.params, self.cache,
                jnp.asarray(self.last_tok), jnp.asarray(self.pos))
        return self._advance(self._next_tokens(logits))


class MixtureSlotServer(_SlotTable):
    """Continuous batching over the STACKED expert ensemble: one cache
    carrying the expert (K) dim, one jitted vmapped decode step with the
    Eq. 27 mixture fused in, per-slot router weights fixed at admission.
    In the paged layout the block pool carries the K dim too, and all K
    experts of a slot share ONE block table."""

    def __init__(self, model: Model, expert_params: List[Any], router,
                 n_slots: int = 0, cache_len: int = 0, *,
                 use_kernel: bool = False, page_block: int = 0,
                 pool_blocks: int = 0, chunk: int = 0,
                 token_budget: int = 0, prefix_cache: bool = False,
                 fused_step: bool = True,
                 config: Optional[EngineConfig] = None, pod: int = 0):
        if config is None:
            config = _legacy_config(
                n_slots, cache_len, page_block=page_block,
                pool_blocks=pool_blocks, chunk=chunk,
                token_budget=token_budget, prefix_cache=prefix_cache,
                fused_step=fused_step, use_kernel=use_kernel,
                strategy="mixture")
        config.validate(model)
        self.config = config
        n_slots, cache_len = config.n_slots, config.cache_len
        use_kernel = config.use_kernel
        page_block = effective_page_block(
            model, config.page_block if config.paged else 0)
        chunk = config.chunk if config.chunked_prefill else 0
        super().__init__(n_slots, cache_len, block_size=page_block,
                         n_blocks=config.pool_blocks,
                         window=model.cfg.sliding_window, chunk=chunk,
                         token_budget=config.token_budget,
                         prefix_cache=config.prefix_cache
                         and model.prefix_cacheable,
                         sanitize=config.sanitize,
                         qos=config.qos, preemption=config.preemption,
                         obs=EngineObs(pod=pod, trace=config.trace,
                                       trace_ring=config.trace_ring,
                                       publish=config.metrics,
                                       annotate=jax.profiler.TraceAnnotation))
        self._seq_axis = 2      # embedded prompts carry K at axis 0
        self._from_probs = True  # the mixed scores are Eq. 27 probabilities
        self._needs_features = True   # admission routes on features
        self.model, self.router = model, router
        self.K = len(expert_params)
        self.use_kernel = use_kernel
        self.stacked, param_axes, self._prefill_all, self._mix_decode = \
            make_stacked_serving(model, expert_params, cache_len,
                                 use_kernel=use_kernel, paged=self.paged)
        chunk_all = None
        if self.chunked:
            self._prep_all, chunk_all = \
                make_stacked_chunk_fns(model, self.stacked, param_axes,
                                       cache_len, chunk,
                                       use_kernel=use_kernel)
            mix_decode = self._mix_decode
            if self.paged:
                def mixture_decode_chunk_probs(sp, c, toks, pos, w, dbt,
                                               carry, xc, start, ln, cbt,
                                               w_row):
                    probs, c = mix_decode(sp, c, toks, pos, w, dbt)
                    c_probs, carry, c = chunk_all(sp, c, carry, xc, start,
                                                  ln, cbt, w_row)
                    return probs, c_probs, carry, c
            else:
                def mixture_decode_chunk_probs(sp, c, toks, pos, w, carry,
                                               xc, start, ln, cbt, w_row):
                    probs, c = mix_decode(sp, c, toks, pos, w)
                    c_probs, carry, c = chunk_all(sp, c, carry, xc, start,
                                                  ln, cbt, w_row)
                    return probs, c_probs, carry, c
            self._fused_mix = jax.jit(mixture_decode_chunk_probs,
                                      donate_argnums=(1,))
            self._chunk_only_mix = jax.jit(chunk_all, donate_argnums=(1,))
        self.fused = config.fused_step
        if self.fused:
            self._fstep, self._fstep_chunk, self._fchunk_only = \
                make_stacked_fused(model, param_axes, cache_len,
                                   chunk_all=chunk_all,
                                   use_kernel=use_kernel, paged=self.paged)
        self._init_speculation(
            config, model,
            lambda: make_stacked_verify(
                model, param_axes, cache_len, config.spec_len,
                use_kernel=use_kernel,
                expert_draft=config.speculative == "expert"))
        # expert (K) dim where the vmapped step consumes it without a
        # transpose (``stacked_cache_axes``): leading each paged pool
        # leaf, after the scan dim of every leaf the layer loop scans
        shapes = model.paged_cache_shapes(
            n_slots, self.allocator.n_blocks, page_block, cache_len) \
            if self.paged else model.cache_shapes(n_slots, cache_len)
        self.cache = jax.tree.map(
            lambda s, ax: jnp.zeros(s.shape[:ax] + (self.K,) + s.shape[ax:],
                                    s.dtype),
            shapes, stacked_cache_axes(model, self.paged))
        # batch/seq axes move by 1 under the K dim (it lies before them)
        self.spec = model.cache_spec(page_block).shifted(1)
        self.weights = np.zeros((n_slots, self.K), dtype=np.float32)
        self._mix = jax.jit(mix_expert_logits)

        def mixture_route(features):
            return router.route(features)
        self._route = jax.jit(mixture_route)

    def admit(self, req: Request) -> bool:
        free = self.free_slots()
        if not free:
            return False
        if req.features is None:
            raise ValueError("mixture admission routes on request features")
        slot = free[0]
        width = self._prefill_width(req)
        if self.chunked:
            if not self._admit_chunked(
                    req, slot, width,
                    lambda b: self._prep_all(self.stacked, b)):
                return False
            self.weights[slot] = self._route_weights(req.features)[0]
            return True
        if not self._admission_precheck(req, slot, width):
            return False
        # route only once admission is paying for the prefill — a request
        # blocked on free KV blocks must not re-run the router every retry
        w = self._route_weights(req.features)                     # (1, K)
        logits, row_cache = self._prefill_all(self.stacked, req.batch())
        probs = self._mix(logits[:, :, -1], w)                    # (1, V)
        first = self._pick_first(req, probs[0], from_probs=True)
        assert logits.shape[2] == width, (logits.shape, width)
        if width == self.cache_len:
            self._retire_at_admission(req, first)
            return True
        self.weights[slot] = w[0]
        self._admit_prefilled(slot, req, first, width, row_cache)
        return True

    def _route_weights(self, features: np.ndarray) -> np.ndarray:
        """The Eq. 28 router's (1, K) weights for one request, in a
        ``route`` span and timed into ``serve_router_seconds``. The
        ``device_get`` is the explicit sync for the host weights mirror —
        ``np.asarray`` of the device row was an implicit one (repro-lint
        host-sync)."""
        obs = self.obs
        with obs.span("route"):
            t0 = time.perf_counter()
            w = jax.device_get(self._route(jnp.asarray(features[None])))
            t1 = time.perf_counter()
        obs.router_s.observe(t1 - t0)
        return w

    def _state_extras(self, st):
        st["weights"] = jnp.asarray(self.weights)
        return st

    def _park_extras(self, slot: int) -> Dict[str, Any]:
        # the router-weight row is per-slot host state a swap resume
        # cannot rebuild (recompute resumes re-route on the features)
        return {"weights": self.weights[slot].copy()}

    def _restore_extras(self, slot: int, extras: Dict[str, Any]) -> None:
        if "weights" in extras:
            self.weights[slot] = extras["weights"]

    def _run_fused(self, st):
        self.cache, self._dstate, nxt, done = self._fstep(
            self.stacked, self.cache, st)
        return nxt, done

    def _run_verify(self, st, drafts):
        # drafts is None when expert 0 drafts on device (speculative=
        # "expert"); the n-gram variant takes the host drafts argument
        out = self._vstep(self.stacked, self.cache, st) if drafts is None \
            else self._vstep(self.stacked, self.cache, st, drafts)
        self.cache, self._dstate, toks, n_emit, done = out
        return toks, n_emit, done

    def _run_fused_chunk(self, st, slot, xc, start, length, cbt, pick):
        w_row = jnp.asarray(self.weights[slot:slot + 1])
        (self.cache, self._dstate, nxt, done, first,
         self.prefill_carry[slot]) = self._fstep_chunk(
            self.stacked, self.cache, st, self.prefill_carry[slot], xc,
            start, length, cbt, w_row, *pick)
        return nxt, done, first

    def _run_chunk_only(self, slot, xc, start, length, cbt, pick):
        w_row = jnp.asarray(self.weights[slot:slot + 1])
        first, self.prefill_carry[slot], self.cache = self._fchunk_only(
            self.stacked, self.cache, self.prefill_carry[slot], xc, start,
            length, cbt, w_row, *pick)
        return first

    def _decode_step(self) -> List[Request]:
        if self.fused:
            return self._decode_step_fused()
        dec = self.decoding
        do_chunk = self.chunked and self._schedule_chunk()
        if not dec and not do_chunk:
            return []
        self._step_kind = "unfused"
        run = self._dispatch
        if do_chunk:
            slot, xc, start, length, cbt = self._chunk_args()
            w_row = jnp.asarray(self.weights[slot:slot + 1])
            if not dec:
                c_out, carry, self.cache = run(
                    self._chunk_only_mix, self.stacked, self.cache,
                    self.prefill_carry[slot], xc, start, length, cbt, w_row)
                self.prefill_carry[slot] = carry
                return self._after_chunk(slot, length, c_out)
            self._grow_active()
            # the decode's block tables, on a paged cache
            tables = (jnp.asarray(
                self._decode_tables()[:, :self._nb_live()]),) \
                if self.paged else ()
            probs, c_out, carry, self.cache = run(
                self._fused_mix, self.stacked, self.cache,
                jnp.asarray(self.last_tok), jnp.asarray(self.pos),
                jnp.asarray(self.weights), *tables,
                self.prefill_carry[slot], xc, start, length, cbt, w_row)
            self.prefill_carry[slot] = carry
            retired = self._advance(self._next_tokens(probs,
                                                      from_probs=True))
            retired += self._after_chunk(slot, length, c_out)
            return retired
        if self.paged:
            self._grow_active()
            probs, self.cache = run(
                self._mix_decode, self.stacked, self.cache,
                jnp.asarray(self.last_tok), jnp.asarray(self.pos),
                jnp.asarray(self.weights),
                jnp.asarray(self._decode_tables()[:, :self._nb_live()]))
        else:
            probs, self.cache = run(
                self._mix_decode, self.stacked, self.cache,
                jnp.asarray(self.last_tok), jnp.asarray(self.pos),
                jnp.asarray(self.weights))
        return self._advance(self._next_tokens(probs, from_probs=True))


class DecentralizedSlotServer:
    """Front-end centroid router over continuously-batched expert pods.

    strategy="top1"    — grouped top-1 (compute-matched): one ``SlotServer``
                         per expert pod; each request decodes on exactly the
                         expert the router assigns it.
    strategy="mixture" — general top-k: the stacked-expert mixture core.

    ``page_block > 0`` switches every pod (or the mixture core) to the
    paged KV cache; ``pool_blocks`` is per pod. ``prefix_cache=True``
    gives every pod its own radix prefix cache (the mixture core shares
    one across all K stacked experts — the pool carries the ``dexpert``
    dim, so a shared prefix block is shared for all K at once); the
    per-expert routing concentrates similar requests on the same pods,
    which is exactly what makes the per-pod caches hit.
    """

    def __init__(self, model: Model, expert_params: List[Any], router,
                 n_slots: int = 0, cache_len: int = 0, *,
                 strategy: str = "top1", use_kernel: bool = False,
                 page_block: int = 0, pool_blocks: int = 0, chunk: int = 0,
                 token_budget: int = 0, prefix_cache: bool = False,
                 fused_step: bool = True,
                 config: Optional[EngineConfig] = None):
        if config is None:
            config = _legacy_config(
                n_slots, cache_len, page_block=page_block,
                pool_blocks=pool_blocks, chunk=chunk,
                token_budget=token_budget, prefix_cache=prefix_cache,
                fused_step=fused_step, use_kernel=use_kernel,
                strategy=strategy)
        config.validate(model)
        self.config = config
        self.model, self.router = model, router
        self.K = len(expert_params)
        self.strategy = config.strategy
        self._next_rid = 0
        if self.strategy == "top1":
            eff_block = effective_page_block(
                model, config.page_block if config.paged else 0)
            cache_len, chunk = config.cache_len, \
                config.chunk if config.chunked_prefill else 0
            fns = make_serve_fns(model, cache_len,
                                 use_kernel=config.use_kernel,
                                 paged=eff_block > 0)
            cfns = make_chunk_fns(model, cache_len, chunk,
                                  use_kernel=config.use_kernel,
                                  paged=eff_block > 0) if chunk > 0 \
                else None
            ffns = make_fused_fns(model, cache_len, chunk,
                                  use_kernel=config.use_kernel,
                                  paged=eff_block > 0) \
                if config.fused_step else None
            vfns = make_verify_fns(model, cache_len,
                                   use_kernel=config.use_kernel) \
                if (config.speculative is not None and config.spec_len > 1
                    and config.fused_step and eff_block > 0
                    and model.speculative_capable) else None
            # pod=k labels each pod's registry/trace track (pid=k in the
            # merged Perfetto export) so per-expert load is attributable
            self.pods = [SlotServer(model, p, config=config,
                                    serve_fns=fns, chunk_fns=cfns,
                                    fused_fns=ffns, verify_fns=vfns,
                                    pod=k)
                         for k, p in enumerate(expert_params)]

            def top1_route(features):
                return router.top1(features)
            self._route = jax.jit(top1_route)
        else:
            self.core = MixtureSlotServer(model, expert_params, router,
                                          config=config, pod=0)

    def route(self, queue: List[Request]) -> np.ndarray:
        feats = np.stack([r.features for r in queue])
        return np.asarray(self.router.top1(jnp.asarray(feats)))

    # ------------------------------------------------------------------
    # Incremental API: the front-end router runs at submission time
    # ------------------------------------------------------------------

    def add_request(self, prompt, params: Optional[SamplingParams] = None,
                    extras: Optional[Dict[str, np.ndarray]] = None, *,
                    features: Optional[np.ndarray] = None,
                    rid: Optional[int] = None) -> int:
        """Submit a request: the Eq. 28 centroid router assigns it at the
        front end — to its top-1 expert's pod, or (mixture) straight into
        the stacked core's queue."""
        if self.strategy == "mixture":
            rid = self.core.add_request(prompt, params, extras,
                                        features=features, rid=rid)
            self._next_rid = self.core._next_rid
            return rid
        req = _as_request(prompt, params, extras, features,
                          self._next_rid if rid is None else rid)
        if req.features is None:
            raise ValueError(_FEATURES_MSG.format(rid=req.rid))
        self._next_rid = max(self._next_rid, req.rid + 1)
        # submission is now, not when the pod sees the request — the
        # front-end routing dispatch must count toward TTFT
        req.t_submit = req.t_submit or time.perf_counter()
        return self.pods[self._route_one(req.features)].add_request(req)

    def _route_one(self, features) -> int:
        """The pod (top-1 expert) one request's features route to, timed
        into that pod's ``serve_router_seconds``. Traced, a ``route`` span
        naming the ``expert``; it is written with pod 0's telemetry, as
        the pod is chosen inside it."""
        with self.pods[0].obs.span("route") as span:
            t0 = time.perf_counter()
            k = int(jax.device_get(self._route(
                jnp.asarray(np.asarray(features)[None])))[0])
            t1 = time.perf_counter()
            span.note(expert=k)
        self.pods[k].obs.router_s.observe(t1 - t0)
        return k

    def step(self) -> List[RequestOutput]:
        """One step of every pod (in pod order — admission then the fused
        dispatch, exactly the legacy drive loop's schedule), concatenating
        their streamed outputs."""
        if self.strategy == "mixture":
            return self.core.step()
        outs: List[RequestOutput] = []
        for pod in self.pods:
            outs += pod.step()
        return outs

    def abort(self, rid: int) -> Optional[RequestOutput]:
        """Cancel a request on whichever pod holds it (no-op → None)."""
        if self.strategy == "mixture":
            return self.core.abort(rid)
        for pod in self.pods:
            out = pod.abort(rid)
            if out is not None:
                return out
        return None

    def has_unfinished(self) -> bool:
        if self.strategy == "mixture":
            return self.core.has_unfinished()
        return any(pod.has_unfinished() for pod in self.pods)

    def serve(self, queue: List[Request], *, max_steps: int = 10_000
              ) -> Dict[int, List[int]]:
        """Drain loop over the incremental API (see ``_SlotTable.serve``);
        requests are routed to their pods at submission."""
        if not queue:
            return {}
        if self.strategy == "mixture":
            return self.core.serve(queue, max_steps=max_steps)
        self.reset_stats()
        for req in queue:
            self.add_request(req)
        finished: Dict[int, List[int]] = {}
        reasons: Dict[int, str] = {}
        for _ in range(max_steps):
            for out in self.step():
                if out.finished:
                    finished[out.rid] = out.token_ids
                    reasons[out.rid] = out.finish_reason
            if not self.has_unfinished():
                break
        dropped = [f"{r.rid} (queued)"
                   for pod in self.pods for r in pod.waiting] + \
            [d for pod in self.pods for d in pod._drop_details()]
        if dropped:
            _raise_dropped(dropped, len(finished), max_steps)
        logger.info("serve: %d finished (finish_reasons %s), pods %s",
                    len(finished), reasons, self.occupancy())
        return finished

    def occupancy(self) -> List[Dict[str, Any]]:
        """Per-pod serving stats (one dict per top-1 pod, or one for the
        mixture core): ``active`` slots, and — when paged —
        ``pool_free_blocks`` / ``pool_blocks``, plus the prefix-cache
        counters (``prefix_hit_rate``, ``prefix_skipped_tokens``, …) when
        the cache is on."""
        pods = [self.core] if self.strategy == "mixture" else self.pods
        return [p.stats() for p in pods]

    # ------------------------------------------------------------------
    # Observability (see docs/observability.md)
    # ------------------------------------------------------------------

    def _engines(self) -> List[_SlotTable]:
        return [self.core] if self.strategy == "mixture" else self.pods

    def reset_stats(self) -> None:
        """Per-run counter hygiene across every pod (see
        ``_SlotTable.reset_stats``); ``serve()`` calls this at entry."""
        for p in self._engines():
            p.reset_stats()

    def export_trace(self, path: Optional[str] = None) -> Dict[str, Any]:
        """Merged Chrome/Perfetto trace over every pod — each pod keeps
        its own ``pid``, so ui.perfetto.dev shows one process group per
        expert pod. Written to ``path`` when given."""
        doc = merge_chrome([p.obs.trace for p in self._engines()])
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc

    def export_metrics(self, path: Optional[str] = None) -> Dict[str, Any]:
        """Merged metrics snapshot over every pod's registry (series stay
        distinguished by their ``pod`` label)."""
        doc = _obs_metrics.snapshot([p.obs.registry
                                     for p in self._engines()])
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f, indent=2)
        return doc

    def prometheus_metrics(self) -> str:
        """Prometheus text exposition over every pod's registry."""
        return _obs_metrics.prometheus([p.obs.registry
                                        for p in self._engines()])


def make_engine(model: Model, params: Any = None, *,
                experts: Optional[List[Any]] = None, router=None,
                config: Optional[EngineConfig] = None):
    """Build the serving engine a deployment needs from ONE validated
    ``EngineConfig`` — replacing the three hand-wired constructors.

    * ``make_engine(model, params, config=cfg)`` — a single-model
      ``SlotServer``.
    * ``make_engine(model, experts=[...], router=r, config=cfg)`` — the
      decentralized deployment (paper §5.2): ``cfg.strategy == "top1"``
      builds one pod per expert behind the Eq. 28 front-end router
      (sharing the jitted serve/chunk fns across pods);
      ``"mixture"`` builds the stacked-expert Eq. 27 core.

    Every engine returned speaks the same incremental API:
    ``add_request`` / ``step`` / ``abort`` / ``has_unfinished`` (plus the
    legacy ``serve(queue)`` drain wrapper).
    """
    config = config if config is not None else EngineConfig()
    config.validate(model)
    if experts is not None:
        if router is None:
            raise ValueError(
                "decentralized serving routes on the centroid router — "
                "pass router= alongside experts=")
        return DecentralizedSlotServer(model, experts, router,
                                       config=config)
    if params is None:
        raise ValueError(
            "single-model serving needs the model's params (or pass "
            "experts= and router= for the decentralized deployment)")
    return SlotServer(model, params, config=config)
