"""Continuous-batching scheduler over the jitted prefill/decode entry points.

Two pool layouts serve the same masked decode step (DESIGN.md §7):

  dense (default) — one preallocated slot-pool KV cache (``Model.init_cache``
  layout, batch dim = ``num_slots``): every slot owns ``cache_len`` rows of
  every leaf regardless of how many tokens it actually holds.

  paged (``paged=True``) — fixed-size blocks in per-leaf arenas
  ``[layers, num_blocks + 1, block, ...]`` plus a per-slot block table;
  admission reserves ``ceil((prompt + max_new) / block)`` blocks from a
  refcounted free list (``serving.paging.BlockAllocator``), so admission is
  *by memory, not slot count*, a 16-token request holds one block where a
  4096-token request holds 64, and requests whose prompt prefix hashes to
  already-resident blocks share them copy-on-write and skip the covered
  prefill compute entirely (``prefill_resume``).

Lifecycle of a request:

  submit() ─→ queue ─→ admission (free slot + free blocks): bucketed
  single-request jitted prefill (or suffix-only resume prefill on a prefix
  hit) + a donated splice/scatter into the pool ─→ masked decode steps
  until EOS or the token budget ─→ retirement frees the slot and decrefs
  its blocks (published prefix blocks stay cached until evicted LRU).

The first generated token comes from the prefill logits (same contract as
``engine.generate``).  Sampling parameters ride on the ``Request``
(``temperature``, ``top_k``); each sampled request draws from its own PRNG
stream (``fold_in(base_key, uid)``), split once per *sampled* token —
greedy requests never consume randomness, so temperature=0 results are
key-independent.

Failure paths thread through the same lifecycle (DESIGN.md §11):

  deadlines — ``Request.deadline_s`` (TTL from submit) retires overdue
  work at the next ``step()`` with ``finish_reason="deadline"`` (partial
  tokens included) and frees its slot/blocks; ``cancel(uid)`` does the
  same on demand with ``finish_reason="cancelled"``.

  preemption — when the best queued request outranks the least important
  active slot (``Request.priority`` first, then submit order), the victim
  is evicted: its full blocks are published to the prefix registry, its
  blocks decrefed, and its partial state requeued for recompute; on
  re-admission the resume prompt (prompt + generated so far) reacquires
  the published blocks, so only the tail is recomputed.  Preemption is
  strictly rank-decreasing (never an equal-or-better victim), so the
  highest-ranked request in the system always runs to completion — no
  livelock.

  live resize — ``resize(num_slots=…, num_blocks=…)`` grows pools
  immediately; shrinks fence the excess and defer until the draining
  slots/blocks empty, never dropping in-flight requests.

  snapshot/restore — ``snapshot()`` captures scheduler + allocator +
  request + pool state host-side; ``Scheduler.from_snapshot`` resumes
  mid-stream with bit-identical surviving token streams (the serving twin
  of ``training/fault.py``).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import sharding as shd
from repro.models.model import Model
from repro.models.transformer import block_cache_kinds
from .paging import BlockAllocator, chain_hashes, logical_blocks

NEG_INF = -1e30


@dataclasses.dataclass
class Request:
    """One generation request.  ``inputs`` are the per-request model inputs
    with leading batch dim 1 (at minimum ``tokens [1, S]``; multimodal
    frontends add their embedding arrays).  ``temperature``/``top_k`` are
    per-request sampling parameters: temperature 0 is greedy (consumes no
    PRNG), top_k 0 disables the top-k filter.  ``priority`` orders
    admission and preemption (higher wins; ties go to the older request);
    ``deadline_s`` is a TTL from submit after which the request is retired
    with ``finish_reason="deadline"``.  ``on_token`` (optional callable
    ``(uid, index, token, logprob)``) streams each generated token as it is
    picked — the async serving front-end's hook; it is host-side state and
    is dropped from snapshots/journals (reconnecting clients replay from
    the server's buffers instead)."""
    uid: int
    inputs: dict
    max_new_tokens: int
    key: jax.Array | None = None          # per-request sampling stream
    temperature: float = 0.0
    top_k: int = 0
    priority: int = 0
    deadline_s: float | None = None
    on_token: object | None = dataclasses.field(default=None, repr=False,
                                                compare=False)


@dataclasses.dataclass
class FinishedRequest:
    uid: int
    tokens: np.ndarray                    # [n_generated] int32
    logprobs: np.ndarray                  # [n_generated] float32
    finish_reason: str          # "eos" | "length" | "deadline" | "cancelled"
    prompt_len: int
    submit_time: float                    # perf_counter at submit()
    finish_time: float                    # perf_counter at retirement
    first_token_time: float | None = None  # perf_counter at first token


@dataclasses.dataclass
class _Resume:
    """Partial generation state of a preempted request: everything needed
    to continue its token stream bit-identically after re-admission."""
    tokens: list[int]
    logprobs: list[float]
    key: jax.Array | None                 # PRNG stream state at preemption
    last_tok: int
    first_token_time: float | None = None


@dataclasses.dataclass
class _Queued:
    req: Request
    prompt_len: int
    submit_time: float
    deadline: float | None = None         # absolute (scheduler clock)
    resume: _Resume | None = None         # set on preempted re-queues


@dataclasses.dataclass
class _Slot:
    uid: int
    req: Request                          # original request (preemption
    max_new: int                          # rebuilds the queue entry)
    key: jax.Array | None
    prompt_len: int
    submit_time: float
    temperature: float = 0.0
    top_k: int = 0
    priority: int = 0
    deadline: float | None = None
    tokens: list[int] = dataclasses.field(default_factory=list)
    logprobs: list[float] = dataclasses.field(default_factory=list)
    last_tok: int = 0
    first_token_time: float | None = None
    admit_time: float | None = None       # clock at the last admission
    # chunked-prefill state machine: ``prefill_pos`` is None once the
    # prompt is fully prefilled (slot is decoding); while prefilling it
    # counts prompt tokens already processed.  ``prefill_toks`` is the
    # effective prompt (original + resume tokens) and ``prefill_table``
    # the slot's sentinel-padded block-table row (paged pools).
    prefill_pos: int | None = None
    prefill_toks: np.ndarray | None = dataclasses.field(
        default=None, repr=False)
    prefill_table: np.ndarray | None = dataclasses.field(
        default=None, repr=False)


class _Phase:
    """One host phase of ``Scheduler.step`` (DESIGN.md §16): a profiler
    span ``sched.<name>``, recorded only while a trace is active, and the
    phase's time on the scheduler's clock, summed into ``host_s[name]``
    with the longest single occurrence in ``host_max_s[name]``.  Phases
    nest: every wait for the device's tokens is a ``sync`` inside the
    phase that waits, so ``host_s["step"] - host_s["sync"]`` is the
    host's own time."""
    __slots__ = ("sched", "name", "span", "t0")

    def __init__(self, sched: "Scheduler", name: str, span=None):
        self.sched, self.name = sched, name
        self.span = span or jax.profiler.TraceAnnotation(f"sched.{name}")

    def __enter__(self):
        self.span.__enter__()
        self.t0 = self.sched._now()

    def __exit__(self, *exc):
        s = self.sched
        dt = s._now() - self.t0
        self.span.__exit__(*exc)
        s.host_s[self.name] = s.host_s.get(self.name, 0.0) + dt
        if dt > s.host_max_s.get(self.name, 0.0):
            s.host_max_s[self.name] = dt


class Scheduler:
    """Continuous-batching loop: ``submit()`` any time, ``step()`` advances
    every active slot by one token and admits queued requests into freed
    slots, ``run()`` drains."""

    def __init__(self, model: Model, params, num_slots: int, cache_len: int,
                 *, eos_id: int | None = None, key: jax.Array | None = None,
                 paged: bool = False, block_size: int = 64,
                 num_blocks: int | None = None, prefix_cache: bool = True,
                 bucket_prompts: bool = True, preempt: bool = True,
                 clock=None, mesh=None, chunk_prefill: bool = False,
                 chunk_size: int = 64, prefill_budget: int | None = None):
        if num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        # Chunked prefill: prompts are processed ``chunk_size`` tokens at a
        # time INSIDE the fused decode step (one traced program per
        # (lanes, chunk) shape) instead of a monolithic admission prefill.
        # ``prefill_budget`` caps prefill tokens per step: the step runs
        # floor(budget / chunk_size) chunk lanes alongside the B decode
        # rows, trading TTFT of admitting requests against inter-token
        # latency of running ones.
        self.chunk_prefill = bool(chunk_prefill)
        self.chunk_size = int(chunk_size)
        budget = self.chunk_size if prefill_budget is None else int(prefill_budget)
        self.prefill_budget = budget
        self.chunk_lanes = max(1, budget // max(1, self.chunk_size))
        self.prefill_chunks = 0           # chunk lanes executed
        if self.chunk_prefill:
            if self.chunk_size < 1:
                raise ValueError("chunk_size must be >= 1")
            if not model.supports_chunked_prefill:
                raise ValueError(
                    "model does not support chunked prefill (encoder-decoder "
                    "and frontend models prefill monolithically)")
        self.model = model
        self.mesh = mesh
        if mesh is not None:
            # one placement decision, made here: params land sharded per
            # DESIGN.md §14 and every jitted entry point (prefill, masked
            # decode, splice, resume) is partitioned by GSPMD from its
            # operands — the traced programs are unchanged, so one decode
            # step stays one executable, collectives compiled in.
            params = jax.device_put(params, shd.serve_param_shardings(
                model.param_specs(), params, mesh))
        self.params = params
        self.preempt = preempt
        # injectable clock (deadlines, latency stamps): tests and the
        # fault harness drive a virtual clock for determinism
        self._now = clock if clock is not None else time.perf_counter
        # Touch the model's PlanBook up front: every TT layer's execution
        # plan is resolved (or confirmed resolved) here, outside any jit
        # trace, so admission prefills and the masked decode step perform
        # ZERO plan resolutions — asserted by tests via
        # kernels.plan.plan_resolutions() and the serve.py CI smoke.
        model.plan_book
        self.num_slots = num_slots
        self.eos_id = eos_id
        self.base_key = key
        self.paged = paged
        self.bucket_prompts = bucket_prompts
        if paged:
            self.block = block_size
            self.max_blocks = logical_blocks(cache_len, block_size)
            # the pool's logical length is block-aligned so prefilled rows
            # scatter into whole blocks
            self.cache_len = self.max_blocks * block_size
            self.num_blocks = (num_blocks if num_blocks is not None
                               else num_slots * self.max_blocks)
            self.allocator = BlockAllocator(self.num_blocks, block_size)
            self.prefix_cache = prefix_cache and model.supports_prefix_reuse
            self._slot_blocks: list[list[int] | None] = [None] * num_slots
            self.block_hwm = 0                # live blocks high-water mark
            self.prefix_hit_tokens = 0        # prompt tokens found resident
            self.prefix_prompt_tokens = 0     # prompt tokens seen (paged)
            self.prefill_tokens_skipped = 0   # prefill compute avoided
        else:
            self.cache_len = cache_len
        self.queue: deque[_Queued] = deque()
        self.slots: list[_Slot | None] = [None] * num_slots
        self.cache = None                 # pool; built from first prefill
        self.finished: list[FinishedRequest] = []
        self.steps_run = 0                # decode steps executed
        self.tokens_out = 0               # total generated tokens
        self.preemptions = 0              # slots evicted + requeued
        self.cancelled = 0                # requests cancelled via cancel()
        self.expired = 0                  # requests retired past deadline
        self._target_slots: int | None = None   # pending slot shrink
        self.hold_admissions = False      # fault/SLO gate: skip admission
        # host phases of step() (``_Phase``) and the first-token split
        self.host_s: dict[str, float] = {}      # summed seconds per phase
        self.host_max_s: dict[str, float] = {}  # longest single occurrence
        self.host_steps = 0               # step() calls
        self.first_tokens = 0             # first tokens emitted
        self.ttft_queue_s = 0.0           # submit -> admission, summed
        self.ttft_prefill_s = 0.0         # admission -> first token, summed
        self.decode_kv_tokens = 0         # positions decode rows attend over
        # shared across Scheduler instances of the same model: a server
        # creating one Scheduler per batch must not recompile the pick
        self._pick = model._jit_get("pick", self._build_pick)

    # ------------------------------------------------------------- interface
    def submit(self, req: Request, submit_time: float | None = None) -> None:
        """Queue a request.  Raises ValueError *here* — not by hanging the
        drain loop forever — when the request could never be admitted:
        its lifetime reservation must fit the pool even when every other
        request has retired."""
        S = int(req.inputs["tokens"].shape[1])
        if self.model.cfg.frontend == "vit":
            S += int(req.inputs["image_embeds"].shape[1])
        if req.max_new_tokens < 0:
            raise ValueError("max_new_tokens must be >= 0")
        if S + req.max_new_tokens > self.cache_len:
            raise ValueError(
                f"request uid={req.uid}: prompt ({S}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds cache_len={self.cache_len}")
        if self.paged:
            need = logical_blocks(S + req.max_new_tokens, self.block)
            cap = self.allocator.capacity      # pending-shrink aware
            if need > cap:
                raise ValueError(
                    f"request uid={req.uid} can never be admitted: prompt "
                    f"({S}) + max_new_tokens ({req.max_new_tokens}) needs "
                    f"{need} blocks of {self.block} tokens but the pool "
                    f"has only {cap}")
        t = self._now() if submit_time is None else submit_time
        self.queue.append(_Queued(
            req, S, t,
            deadline=None if req.deadline_s is None else t + req.deadline_s))

    def cancel(self, uid: int) -> bool:
        """Explicitly cancel a request, queued or in flight.  Retires it
        with ``finish_reason="cancelled"`` (partial tokens included) and
        frees its slot/blocks.  Returns False for an unknown uid."""
        for qi, q in enumerate(self.queue):
            if q.req.uid == uid:
                del self.queue[qi]
                self.finished.append(self._finish_queued(q, "cancelled"))
                self.cancelled += 1
                return True
        for i, s in enumerate(self.slots):
            if s is not None and s.uid == uid:
                self.finished.append(self._evict(i, "cancelled"))
                self.cancelled += 1
                return True
        return False

    def drop(self, uid: int) -> bool:
        """Remove a request — queued or in flight — WITHOUT recording a
        result: the slot/blocks are freed and nothing lands in
        ``finished``.  Journal replay uses this when a retire record is
        authoritative (the journaled tokens were already acknowledged to
        the client; the restored live copy must simply vanish).  Returns
        False for an unknown uid."""
        for qi, q in enumerate(self.queue):
            if q.req.uid == uid:
                del self.queue[qi]
                return True
        for i, s in enumerate(self.slots):
            if s is not None and s.uid == uid:
                if self.paged:
                    self._release_blocks(i)
                self.slots[i] = None
                return True
        return False

    @property
    def num_active(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def idle(self) -> bool:
        return not self.queue and self.num_active == 0

    def stats(self) -> dict:
        """Pool/paging counters for reporting (serve.py, bench_serve_tt)."""
        out = {"tokens_out": self.tokens_out, "steps_run": self.steps_run,
               "kv_pool_bytes": self.kv_pool_bytes(),
               "preemptions": self.preemptions,
               "cancelled": self.cancelled, "expired": self.expired,
               "host_s": dict(self.host_s),
               "host_max_s": dict(self.host_max_s),
               "host_steps": self.host_steps,
               "first_tokens": self.first_tokens,
               "ttft_queue_s": self.ttft_queue_s,
               "ttft_prefill_s": self.ttft_prefill_s,
               "decode_kv_tokens": self.decode_kv_tokens}
        if self.chunk_prefill:
            out.update(chunk_size=self.chunk_size,
                       prefill_budget=self.prefill_budget,
                       chunk_lanes=self.chunk_lanes,
                       prefill_chunks=self.prefill_chunks)
        if self.paged:
            out.update(
                block_size=self.block, num_blocks=self.num_blocks,
                blocks_in_use=self.allocator.in_use,
                block_high_water=self.block_hwm,
                prefix_hit_tokens=self.prefix_hit_tokens,
                prefix_prompt_tokens=self.prefix_prompt_tokens,
                prefill_tokens_skipped=self.prefill_tokens_skipped,
                prefix_hit_rate=(
                    self.prefix_hit_tokens / self.prefix_prompt_tokens
                    if self.prefix_prompt_tokens else 0.0))
        return out

    def kv_pool_bytes(self) -> int:
        if self.cache is None:
            return 0
        return sum(leaf.nbytes for leaf in jax.tree.leaves(self.cache))

    def reset_stats(self) -> None:
        """Zero the reporting counters (after a warm-up request, so compile
        effects stay out of steady-state numbers).  Owned here so every
        counter added to :meth:`stats` gets excluded by construction."""
        self.finished.clear()
        self.tokens_out = self.steps_run = 0
        self.preemptions = self.cancelled = self.expired = 0
        self.prefill_chunks = 0
        self.host_s, self.host_max_s = {}, {}
        self.host_steps = self.first_tokens = 0
        self.ttft_queue_s = self.ttft_prefill_s = 0.0
        self.decode_kv_tokens = 0
        if self.paged:
            self.block_hwm = self.allocator.in_use
            self.prefix_hit_tokens = self.prefix_prompt_tokens = 0
            self.prefill_tokens_skipped = 0

    def step(self) -> list[FinishedRequest]:
        """One scheduler tick: expire overdue work, land any drained
        resize, admit into free slots best-rank-first (paged mode
        additionally requires the block reservation to fit — admission by
        memory; preemption may evict lower-ranked slots), then run one
        masked decode step.  Returns the requests retired during this
        call.

        With a mesh the step runs with it in context (``jax.set_mesh``):
        the TT layers find it there and run their Pallas kernels once per
        device (``kernels.ops._on_each_device``)."""
        if self.mesh is None:
            return self._step()
        with jax.set_mesh(self.mesh):
            return self._step()

    def _step(self) -> list[FinishedRequest]:
        done: list[FinishedRequest] = []
        self.host_steps += 1
        with _Phase(self, "step", jax.profiler.StepTraceAnnotation(
                "sched.step", step_num=self.host_steps)):
            with _Phase(self, "admit"):
                self._expire(self._now(), done)
                self._apply_pending_resize()
                if not self.hold_admissions:
                    self._admit_phase(done)
            if self.num_active:
                if self.chunk_prefill and any(
                        s is not None and s.prefill_pos is not None
                        for s in self.slots):
                    self._mixed_once(done)
                else:
                    self._decode_once(done)
            # retirements this step may have been the last thing a
            # deferred shrink was waiting on — land it now, not one step
            # later
            self._apply_pending_resize()
            self.finished.extend(done)
        return done

    def run(self) -> dict[int, FinishedRequest]:
        """Drain queue + active slots; returns {uid: FinishedRequest}.

        Guards against silent hangs: a step that makes no progress at all
        (nothing admitted, decoded, retired or expired) while requests are
        still queued raises RuntimeError with the pool ledger instead of
        spinning forever."""
        out = {}
        while not self.idle:
            before = (len(self.queue), self.num_active, self.steps_run,
                      len(self.finished))
            for f in self.step():
                out[f.uid] = f
            after = (len(self.queue), self.num_active, self.steps_run,
                     len(self.finished))
            if before == after and after[1] == 0:
                q = self.queue[0]
                detail = ""
                if self.paged:
                    need = logical_blocks(
                        q.prompt_len + q.req.max_new_tokens, self.block)
                    detail = (f" (head uid={q.req.uid} needs {need} blocks, "
                              f"{self.allocator.available} available)")
                raise RuntimeError(
                    f"scheduler stalled: {len(self.queue)} queued requests, "
                    f"no active slots, and a step made no progress" + detail)
        return out

    # ----------------------------------------------------- deadlines/cancels
    def _finish_queued(self, q: _Queued, reason: str) -> FinishedRequest:
        """Retire a request straight out of the queue (cancel/deadline);
        a preempted re-queue keeps its partial tokens."""
        r = q.resume
        return FinishedRequest(
            uid=q.req.uid,
            tokens=np.asarray(r.tokens if r else [], np.int32),
            logprobs=np.asarray(r.logprobs if r else [], np.float32),
            finish_reason=reason, prompt_len=q.prompt_len,
            submit_time=q.submit_time, finish_time=self._now(),
            first_token_time=r.first_token_time if r else None)

    def _evict(self, i: int, reason: str) -> FinishedRequest:
        """Retire active slot ``i`` early (cancel/deadline): emit its
        partial tokens and free the slot + blocks."""
        f = self._retire(self.slots[i], reason)
        if self.paged:
            self._release_blocks(i)
        self.slots[i] = None
        return f

    def _expire(self, now: float, done: list[FinishedRequest]) -> None:
        """Retire everything past its deadline — queued requests before
        they ever reach a prefill, active slots with their partial tokens."""
        if any(q.deadline is not None and now >= q.deadline
               for q in self.queue):
            keep: deque[_Queued] = deque()
            for q in self.queue:
                if q.deadline is not None and now >= q.deadline:
                    done.append(self._finish_queued(q, "deadline"))
                    self.expired += 1
                else:
                    keep.append(q)
            self.queue = keep
        for i, s in enumerate(self.slots):
            if s is not None and s.deadline is not None \
                    and now >= s.deadline:
                done.append(self._evict(i, "deadline"))
                self.expired += 1

    # ------------------------------------------------------------ preemption
    @staticmethod
    def _rank(priority: int, submit_time: float) -> tuple:
        """Admission/preemption order: smaller sorts first (better).
        Higher priority wins; ties go to the older request."""
        return (-priority, submit_time)

    def _qrank(self, q: _Queued) -> tuple:
        return self._rank(q.req.priority, q.submit_time)

    def _srank(self, s: _Slot) -> tuple:
        return self._rank(s.priority, s.submit_time)

    def _slot_limit(self) -> int:
        """Admissible slot range: a pending shrink stops filling the
        draining tail."""
        return (self._target_slots if self._target_slots is not None
                else self.num_slots)

    def _admit_phase(self, done: list[FinishedRequest]) -> None:
        """Admit queued requests best-rank-first.  When the best queued
        request cannot start (no free slot, or its block reservation does
        not fit), preemption may evict a strictly lower-ranked active slot
        — rank order is static, so a preemptor can never itself be
        preempted by its victim and the top-ranked request in the system
        always runs to completion (anti-livelock).  If the best request
        still cannot start, admission stops: lower-ranked requests never
        jump over it."""
        while self.queue:
            qi = min(range(len(self.queue)),
                     key=lambda j: self._qrank(self.queue[j]))
            q = self.queue[qi]
            limit = self._slot_limit()
            free = next((i for i in range(limit) if self.slots[i] is None),
                        None)
            if free is None:
                if not self._preempt_for(q):
                    break
                continue                  # a slot was freed: retry
            if self._try_admit(q, free, done):
                del self.queue[qi]
                continue
            if not self._preempt_for(q):  # paged: blocks unavailable
                break

    def _preempt_for(self, q: _Queued) -> bool:
        """Evict the worst-ranked active slot if it ranks strictly below
        ``q``.  Returns True iff a victim was preempted."""
        if not self.preempt:
            return False
        cand = [(self._srank(s), i)
                for i, s in enumerate(self.slots) if s is not None]
        if not cand:
            return False
        rank, victim = max(cand)
        if rank <= self._qrank(q):        # never an equal-or-better victim
            return False
        self._preempt(victim)
        return True

    def _resume_tokens(self, s: _Slot) -> np.ndarray:
        """Token sequence of the resume prompt: original prompt followed
        by everything generated so far."""
        orig = np.asarray(s.req.inputs["tokens"]).reshape(-1)
        return np.concatenate([orig, np.asarray(s.tokens, orig.dtype)])

    def _preempt(self, i: int) -> None:
        """Evict slot ``i`` and requeue it for recompute.  Full blocks of
        already-computed KV are published to the prefix registry first, so
        re-admission reacquires them (refcount-0 evictable blocks survive
        unless the preemptor itself needs them) and recomputes only the
        tail.  The partial token/logprob/PRNG state rides along on the
        queue entry — the resumed stream is the same stream."""
        s = self.slots[i]
        if self.paged:
            blocks = self._slot_blocks[i]
            if self.prefix_cache and blocks:
                # KV rows exist for the prompt + all generated tokens except
                # last_tok (still pending as the next decode input); a
                # mid-prefill victim has valid KV only up to its chunk
                # cursor
                toks = self._resume_tokens(s)
                n_valid = (s.prefill_pos if s.prefill_pos is not None
                           else s.prompt_len + len(s.tokens) - 1)
                n_pub = min(n_valid // self.block, len(blocks))
                if n_pub > 0:
                    hashes = chain_hashes(toks[:n_pub * self.block],
                                          self.block)
                    for bid, h in zip(blocks[:n_pub], hashes):
                        self.allocator.publish(bid, h)
            self._release_blocks(i)
        self.slots[i] = None
        self.queue.append(_Queued(
            req=s.req, prompt_len=s.prompt_len, submit_time=s.submit_time,
            deadline=s.deadline,
            resume=_Resume(list(s.tokens), list(s.logprobs), s.key,
                           s.last_tok, s.first_token_time)))
        self.preemptions += 1

    # -------------------------------------------------------------- sampling
    def _build_pick(self):
        def pick(logits, keys, temps, topk):
            """logits [B,V]; keys [B,2] uint32 (ignored for greedy rows);
            temps [B] float32; topk [B] int32 (0 = no filter) →
            (tokens [B] int32, logprobs [B] float32).  One compiled pick
            serves every mix of per-request sampling params."""
            V = logits.shape[-1]
            lp = jax.nn.log_softmax(logits, -1)
            greedy = jnp.argmax(logits, -1)
            srt = jnp.sort(logits, axis=-1)[:, ::-1]          # descending
            kth = jnp.take_along_axis(
                srt, jnp.clip(topk - 1, 0, V - 1)[:, None], 1)[:, 0]
            keep = (topk[:, None] <= 0) | (logits >= kth[:, None])
            safe_t = jnp.where(temps > 0, temps, 1.0)[:, None]
            scaled = jnp.where(keep, logits, NEG_INF) / safe_t
            sampled = jax.vmap(jax.random.categorical)(keys, scaled)
            tok = jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)
            return tok, jnp.take_along_axis(lp, tok[:, None], -1)[:, 0]

        return jax.jit(pick)

    def _gather_logits(self, logits: jax.Array) -> jax.Array:
        """Collapse tensor-parallel logits to replicated before the pick.

        With a sharded LM head the decode step emits logits partitioned on
        the vocab axis; feeding them to ``_pick`` as-is would compile the
        top-k sort into a distributed sort (~40 collectives per step on a
        2-device mesh, measured — the rendezvous cost dwarfs the math at
        decode shapes).  One explicit all-gather of [B, V] instead keeps
        the pick executable collective-free and mesh-agnostic."""
        if self.mesh is None:
            return logits
        return jax.device_put(
            logits, jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec()))

    def _req_key(self, req: Request) -> jax.Array | None:
        if req.temperature <= 0.0:
            return None                   # greedy: no randomness consumed
        if req.key is not None:
            return req.key
        base = (self.base_key if self.base_key is not None
                else jax.random.PRNGKey(0))
        # uids may be negative (warm-up requests); fold_in wants uint32
        return jax.random.fold_in(base, req.uid & 0xFFFFFFFF)

    def _next_key(self, slot: _Slot) -> jax.Array:
        slot.key, sub = jax.random.split(slot.key)
        return sub

    def _pick_one(self, logits_row, slot: _Slot) -> tuple[int, float]:
        """Pick for a single request (admission path): same jitted pick as
        the batched decode, batch dim 1."""
        if slot.temperature > 0.0:
            keys = self._next_key(slot)[None]
        else:
            keys = jnp.zeros((1, 2), jnp.uint32)
        tok, lp = self._pick(
            self._gather_logits(logits_row[None]), keys,
            jnp.asarray([slot.temperature], jnp.float32),
            jnp.asarray([slot.top_k], jnp.int32))
        with _Phase(self, "sync"):
            return int(tok[0]), float(lp[0])

    # ------------------------------------------------------------ pool build
    def _ensure_pool(self, row_cache: dict) -> None:
        """Allocate the pool from the first prefilled row's cache tree
        (guarantees dtype/shape agreement with what prefill produces)."""
        if self.cache is not None:
            return
        B = self.num_slots
        if not self.paged:
            def expand(leaf):
                return jnp.zeros(leaf.shape[:1] + (B,) + leaf.shape[2:],
                                 leaf.dtype)

            self.cache = {"pos": jnp.zeros((B,), jnp.int32)}
            for k, v in row_cache.items():
                if k != "pos":
                    self.cache[k] = jax.tree.map(expand, v)
            return
        nb1 = self.num_blocks + 1         # + write-sentinel block
        cache: dict = {
            "pos": jnp.zeros((B,), jnp.int32),
            "block_tables": jnp.full((B, self.max_blocks), self.num_blocks,
                                     jnp.int32)}
        for gi, (period, _count) in enumerate(self.model.groups):
            g = {}
            for i, bd in enumerate(period):
                kinds = block_cache_kinds(bd)
                b = {}
                for name, row in row_cache[f"g{gi}"][f"b{i}"].items():
                    if kinds[name] == "slot":
                        b[name] = jnp.zeros(
                            row.shape[:1] + (B,) + row.shape[2:], row.dtype)
                    else:                 # row [layers, 1, T, ...] → arena
                        b[name] = jnp.zeros(
                            (row.shape[0], nb1, self.block) + row.shape[3:],
                            row.dtype)
                g[f"b{i}"] = b
            cache[f"g{gi}"] = g
        self.cache = cache
        self._constrain_pool()

    def _constrain_pool(self) -> None:
        """Re-assert the pool's device placement (no-op without a mesh, or
        for leaves already laid out correctly).  Called wherever the pool
        is (re)built from host data or eager reshapes — pool build, resize
        remaps, snapshot restore — so the decode executable always sees
        the same input sharding and never recompiles mid-stream."""
        if self.mesh is not None and self.cache is not None:
            self.cache = jax.device_put(
                self.cache,
                shd.serve_cache_shardings(
                    self.cache, self.mesh,
                    batch=None if self.paged else self.num_slots))

    # -------------------------------------------------------------- admission
    def _try_admit(self, q: _Queued, slot_idx: int,
                   done: list[FinishedRequest]) -> bool:
        """Admit the queue head into ``slot_idx``.  Returns False when the
        paged pool cannot reserve the request's blocks yet (the request
        stays queued; retirements will free blocks)."""
        req = q.req
        if req.max_new_tokens == 0:       # nothing to generate: no prefill
            done.append(FinishedRequest(
                uid=req.uid, tokens=np.zeros((0,), np.int32),
                logprobs=np.zeros((0,), np.float32), finish_reason="length",
                prompt_len=q.prompt_len, submit_time=q.submit_time,
                finish_time=self._now()))
            return True
        if self.chunk_prefill:
            return self._admit_chunked(q, slot_idx)
        if self.paged:
            return self._admit_paged(q, slot_idx, done)
        self._admit_dense(q, slot_idx, done)
        return True

    def _admit_inputs(self, q: _Queued) -> tuple[dict, int]:
        """Model inputs + effective prompt length for an admission.  A
        preempted re-queue resumes with prompt = original prompt + tokens
        generated so far: the prefill (or resume prefill on a prefix hit)
        rebuilds the KV state and its last-position logits pick the next
        token — exactly the pick the interrupted decode step would have
        made."""
        if q.resume is None:
            return q.req.inputs, q.prompt_len
        orig = np.asarray(q.req.inputs["tokens"])
        toks = np.concatenate(
            [orig, np.asarray([q.resume.tokens], orig.dtype)], axis=1)
        inputs = dict(q.req.inputs, tokens=jnp.asarray(toks))
        return inputs, q.prompt_len + len(q.resume.tokens)

    def _row_prefill(self, inputs):
        if self.bucket_prompts:
            fn = self.model.jitted_prefill_bucketed(self.cache_len)
            return fn(self.params, inputs)
        return self.model.jitted_prefill(
            self.cache_len,
            shape_key=int(inputs["tokens"].shape[1]))(self.params, inputs)

    def _start_slot(self, q: _Queued) -> _Slot:
        req = q.req
        s = _Slot(uid=req.uid, req=req, max_new=req.max_new_tokens,
                  key=self._req_key(req), prompt_len=q.prompt_len,
                  submit_time=q.submit_time,
                  temperature=float(req.temperature),
                  top_k=int(req.top_k), priority=int(req.priority),
                  deadline=q.deadline, admit_time=self._now())
        if q.resume is not None:          # continue the interrupted stream
            s.tokens = list(q.resume.tokens)
            s.logprobs = list(q.resume.logprobs)
            s.key = q.resume.key          # PRNG state, not a fresh fold_in
            s.last_tok = q.resume.last_tok
            s.first_token_time = q.resume.first_token_time
        return s

    def _emit(self, slot: _Slot, tok: int, lp: float) -> None:
        """Append one generated token to a slot: TTFT stamp on the first,
        streaming callback on every one.  The single funnel for token
        emission — admission first-tokens, chunk-completion first-tokens
        and decode steps all come through here."""
        slot.tokens.append(tok)
        slot.logprobs.append(lp)
        slot.last_tok = tok
        self.tokens_out += 1
        if slot.first_token_time is None:
            now = self._now()
            slot.first_token_time = now
            self.first_tokens += 1
            self.ttft_queue_s += slot.admit_time - slot.submit_time
            self.ttft_prefill_s += now - slot.admit_time
        cb = slot.req.on_token
        if cb is not None:
            cb(slot.uid, len(slot.tokens) - 1, tok, lp)

    def _admit_dense(self, q: _Queued, slot_idx: int,
                     done: list[FinishedRequest]) -> None:
        inputs, _ = self._admit_inputs(q)
        slot = self._start_slot(q)
        logits, row_cache = self._row_prefill(inputs)
        tok, lp = self._pick_one(logits[0, -1], slot)
        self._emit(slot, tok, lp)
        if self._finished_reason(slot):
            done.append(self._retire(slot))
            return                        # never occupied a decode slot
        self._ensure_pool(row_cache)
        self.cache = self.model.jitted_splice()(
            self.cache, row_cache, jnp.asarray(slot_idx, jnp.int32))
        self.slots[slot_idx] = slot

    def _admit_paged(self, q: _Queued, slot_idx: int,
                     done: list[FinishedRequest]) -> bool:
        req = q.req
        inputs, S = self._admit_inputs(q)
        blk = self.block
        alloc = self.allocator
        # lifetime reservation — invariant under preemption/resume:
        # original prompt + already-generated + remaining budget
        need = logical_blocks(min(q.prompt_len + req.max_new_tokens,
                                  self.cache_len), blk)
        # ---- prefix lookup: acquire the longest chain of resident blocks
        hashes: list[bytes] = []
        shared: list[int] = []
        if self.prefix_cache:
            hashes = chain_hashes(np.asarray(inputs["tokens"]), blk)
            for h in hashes:
                bid = alloc.acquire(h)
                if bid is None:
                    break
                shared.append(bid)
        matched = len(shared)
        covered = matched * blk
        full_cover = matched > 0 and covered >= S
        # resume must compute >= 1 token for logits: full coverage COWs the
        # last matched block and recomputes only its final token
        start = S - 1 if full_cover else covered
        fresh_needed = need - matched + (1 if full_cover else 0)
        # if we are the COW source's only owner, the COW's decref returns
        # it to the pool mid-admission — credit it, or an idle pool could
        # refuse a request that actually fits (admission livelock)
        credit = (1 if full_cover and alloc.refcount(shared[-1]) == 1
                  else 0)
        if fresh_needed > alloc.available + credit:
            for bid in shared:            # rollback: request stays queued
                alloc.decref(bid)
            return False
        # ---- build source/destination tables (dst != src ⇒ COW block)
        src = list(shared)
        dst = list(shared)
        if full_cover:
            dst[-1] = alloc.cow(shared[-1])
        fresh = [alloc.alloc() for _ in range(need - len(dst))]
        src += fresh
        dst += fresh
        sentinel = self.num_blocks
        src_t = np.full(self.max_blocks, sentinel, np.int32)
        dst_t = np.full(self.max_blocks, sentinel, np.int32)
        src_t[:len(src)] = src
        dst_t[:len(dst)] = dst
        # ---- prefill: full prompt (splice) or suffix only (resume)
        slot = self._start_slot(q)
        if start == 0:
            logits, row_cache = self._row_prefill(inputs)
            self._ensure_pool(row_cache)
            self.cache = self.model.jitted_splice_paged()(
                self.cache, row_cache, jnp.asarray(slot_idx, jnp.int32),
                jnp.asarray(dst_t))
        else:
            suffix = {k: (v[:, start:] if k == "tokens" else v)
                      for k, v in inputs.items()}
            logits, self.cache = self.model.jitted_prefill_resume(
                self.cache_len)(self.params, suffix, self.cache, slot_idx,
                                src_t, dst_t, start, S - start)
            self.prefill_tokens_skipped += start
        # ---- publish full prompt blocks for future sharing
        if self.prefix_cache:
            for i in range(min(len(hashes), len(dst))):
                alloc.publish(dst[i], hashes[i])
        self._slot_blocks[slot_idx] = dst
        self.prefix_prompt_tokens += S
        self.prefix_hit_tokens += min(covered, S)
        self.block_hwm = max(self.block_hwm, alloc.in_use)
        # ---- first token
        tok, lp = self._pick_one(logits[0, -1], slot)
        self._emit(slot, tok, lp)
        if self._finished_reason(slot):
            done.append(self._retire(slot))
            self._release_blocks(slot_idx)
            return True                   # never occupied a decode slot
        self.slots[slot_idx] = slot
        return True

    def _ensure_pool_chunked(self) -> None:
        """Chunked admission performs no monolithic prefill, so the pool
        cannot be built "from the first prefilled row"; bootstrap it from
        a zeroed single-row cache with the same shapes and dtypes."""
        if self.cache is None:
            self._ensure_pool(self.model.init_cache(
                1, self.cache_len, dtype=self.model.param_dtype))

    def _admit_chunked(self, q: _Queued, slot_idx: int) -> bool:
        """Admit under chunked prefill: reserve memory and arm the chunk
        state machine — NO prefill compute happens at admission.  The
        mixed step streams the prompt through chunk lanes and the first
        token is picked at chunk completion.  Paged reservation/prefix
        logic mirrors :meth:`_admit_paged` exactly (same lifetime need,
        same COW-credit trick), so admission-by-memory and preemption
        behave identically in both modes.  Returns False when the block
        reservation cannot fit yet."""
        inputs, S = self._admit_inputs(q)
        toks_np = np.asarray(inputs["tokens"]).reshape(-1).astype(np.int32)
        self._ensure_pool_chunked()
        if not self.paged:
            slot = self._start_slot(q)
            slot.prefill_pos = 0
            slot.prefill_toks = toks_np
            self.slots[slot_idx] = slot
            return True
        blk = self.block
        alloc = self.allocator
        need = logical_blocks(min(q.prompt_len + q.req.max_new_tokens,
                                  self.cache_len), blk)
        shared: list[int] = []
        if self.prefix_cache:
            for h in chain_hashes(np.asarray(inputs["tokens"]), blk):
                bid = alloc.acquire(h)
                if bid is None:
                    break
                shared.append(bid)
        matched = len(shared)
        covered = matched * blk
        full_cover = matched > 0 and covered >= S
        # full coverage still computes >= 1 chunk token for logits
        start = S - 1 if full_cover else covered
        fresh_needed = need - matched + (1 if full_cover else 0)
        credit = (1 if full_cover and alloc.refcount(shared[-1]) == 1
                  else 0)
        if fresh_needed > alloc.available + credit:
            for bid in shared:            # rollback: request stays queued
                alloc.decref(bid)
            return False
        dst = list(shared)
        if full_cover:
            dst[-1] = alloc.cow(shared[-1])
            if dst[-1] != shared[-1]:
                # chunk passes read AND write through the slot's own
                # table: materialize the to-be-partially-overwritten tail
                # block eagerly (the monolithic resume path instead keeps
                # src/dst tables apart inside one prefill call)
                self.cache = self.model.jitted_copy_blocks()(
                    self.cache, jnp.asarray(shared[-1], jnp.int32),
                    jnp.asarray(dst[-1], jnp.int32))
        dst += [alloc.alloc() for _ in range(need - len(dst))]
        dst_t = np.full(self.max_blocks, self.num_blocks, np.int32)
        dst_t[:len(dst)] = dst
        self._slot_blocks[slot_idx] = dst
        self.prefix_prompt_tokens += S
        self.prefix_hit_tokens += min(covered, S)
        self.prefill_tokens_skipped += start
        self.block_hwm = max(self.block_hwm, alloc.in_use)
        slot = self._start_slot(q)
        slot.prefill_pos = int(start)
        slot.prefill_toks = toks_np
        slot.prefill_table = dst_t
        self.slots[slot_idx] = slot
        return True

    def _release_blocks(self, slot_idx: int) -> None:
        blocks = self._slot_blocks[slot_idx]
        if blocks is not None:
            for bid in blocks:
                self.allocator.decref(bid)
            self._slot_blocks[slot_idx] = None

    # ----------------------------------------------------------------- resize
    def resize(self, num_slots: int | None = None,
               num_blocks: int | None = None) -> dict:
        """Live pool resize — the knob an autoscaler turns (ROADMAP 4).
        Growth applies immediately (slot rows / arena blocks are padded
        in place, new block ids join the free list).  A shrink never
        drops in-flight requests: the slot tail stops admitting and the
        block fence stops re-issuing high ids, and the actual array
        slicing lands at a later ``step()`` once the tail has drained.
        Returns the current/pending geometry."""
        if num_slots is not None:
            if num_slots < 1:
                raise ValueError("num_slots must be >= 1")
            if num_slots >= self.num_slots:
                if num_slots > self.num_slots:
                    self._grow_slots(num_slots)
                self._target_slots = None
            else:
                self._target_slots = num_slots
                self._apply_slot_shrink()
        if num_blocks is not None:
            if not self.paged:
                raise ValueError("num_blocks resize requires paged=True")
            old = self.num_blocks
            if self.allocator.resize(num_blocks):
                if num_blocks != old:
                    self._remap_arenas(old, num_blocks)
                    self.num_blocks = num_blocks
            # else: fenced — _apply_pending_resize lands it when drained
        return {"num_slots": self.num_slots,
                "num_blocks": self.num_blocks if self.paged else None,
                "pending_slots": self._target_slots,
                "pending_blocks": (self.allocator.pending_target
                                   if self.paged else None)}

    def _apply_pending_resize(self) -> None:
        self._apply_slot_shrink()
        if self.paged and self.allocator.shrink_ready:
            old, new = self.num_blocks, self.allocator.pending_target
            self.allocator.finalize_shrink()
            self._remap_arenas(old, new)
            self.num_blocks = new

    def _grow_slots(self, n: int) -> None:
        old = self.num_slots
        self.slots.extend([None] * (n - old))
        if self.paged:
            self._slot_blocks.extend([None] * (n - old))
        if self.cache is not None:
            self.cache = self._reshape_slots(self.cache, n)
        self.num_slots = n
        self._constrain_pool()

    def _apply_slot_shrink(self) -> bool:
        """Land a pending slot shrink once the tail slots have drained."""
        t = self._target_slots
        if t is None:
            return True
        if any(self.slots[i] is not None
               for i in range(t, self.num_slots)):
            return False                  # defer: tail still busy
        self.slots = self.slots[:t]
        if self.paged:
            self._slot_blocks = self._slot_blocks[:t]
        if self.cache is not None:
            self.cache = self._reshape_slots(self.cache, t)
        self.num_slots = t
        self._constrain_pool()
        self._target_slots = None
        return True

    @staticmethod
    def _axis_resize(leaf, n: int, axis: int):
        cur = leaf.shape[axis]
        if n == cur:
            return leaf
        if n < cur:
            return jax.lax.slice_in_dim(leaf, 0, n, axis=axis)
        pad = jnp.zeros(leaf.shape[:axis] + (n - cur,) + leaf.shape[axis + 1:],
                        leaf.dtype)
        return jnp.concatenate([leaf, pad], axis=axis)

    def _reshape_slots(self, cache: dict, n: int) -> dict:
        """Pad (grow) or slice (drained shrink) every slot-dimensioned
        leaf to ``n`` slots; arenas are slot-independent and untouched."""
        out = {"pos": self._axis_resize(cache["pos"], n, 0)}
        if not self.paged:
            for k, v in cache.items():
                if k != "pos":
                    out[k] = jax.tree.map(
                        lambda leaf: self._axis_resize(leaf, n, 1), v)
            return out
        bt = cache["block_tables"]
        if n < bt.shape[0]:
            out["block_tables"] = bt[:n]
        else:                             # fresh rows point at the sentinel
            pad = jnp.full((n - bt.shape[0], bt.shape[1]),
                           self.num_blocks, bt.dtype)
            out["block_tables"] = jnp.concatenate([bt, pad], axis=0)
        for gi, (period, _count) in enumerate(self.model.groups):
            g = {}
            for i, bd in enumerate(period):
                kinds = block_cache_kinds(bd)
                g[f"b{i}"] = {
                    name: (self._axis_resize(leaf, n, 1)
                           if kinds[name] == "slot" else leaf)
                    for name, leaf in cache[f"g{gi}"][f"b{i}"].items()}
            out[f"g{gi}"] = g
        return out

    def _remap_arenas(self, old_nb: int, new_nb: int) -> None:
        """Reshape every arena leaf ``[layers, old_nb+1, block, …]`` to the
        new block count and move the write sentinel to its new index.  Any
        table entry at or above ``min(old, new)`` is a sentinel reference
        or a stale retired-slot id — both collapse onto the new sentinel
        (live ids are below the fence by construction)."""
        if self.cache is None:
            return
        cache = dict(self.cache)
        bt = cache["block_tables"]
        cache["block_tables"] = jnp.where(
            bt >= min(old_nb, new_nb), jnp.asarray(new_nb, bt.dtype), bt)
        for gi, (period, _count) in enumerate(self.model.groups):
            g = {}
            for i, bd in enumerate(period):
                kinds = block_cache_kinds(bd)
                b = {}
                for name, leaf in cache[f"g{gi}"][f"b{i}"].items():
                    if kinds[name] == "slot":
                        b[name] = leaf
                    elif new_nb > old_nb:
                        # grow: the old sentinel slab becomes data block
                        # ``old_nb`` (free-listed, content meaningless)
                        b[name] = self._axis_resize(leaf, new_nb + 1, 1)
                    else:
                        # shrink: drained tail sliced off; zero the slab
                        # that becomes the new sentinel
                        b[name] = leaf[:, :new_nb + 1].at[:, new_nb].set(0)
                g[f"b{i}"] = b
            cache[f"g{gi}"] = g
        self.cache = cache
        self._constrain_pool()

    # --------------------------------------------------------------- snapshot
    SNAPSHOT_VERSION = 1

    def snapshot(self) -> dict:
        """Host-side snapshot of the complete serving state: queue, slots
        (partial tokens + per-request PRNG stream state), allocator
        ledger, pool cache contents and counters.  Everything is numpy /
        plain python — ``serving.faults.save_snapshot`` persists it, and
        :meth:`from_snapshot` resumes mid-stream with surviving token
        streams bit-identical to an uninterrupted run (the serving twin
        of ``training/fault.py``'s checkpoint/restart contract)."""
        def arr(x):
            return None if x is None else np.asarray(x)

        def enc_req(req: Request) -> dict:
            return {"uid": req.uid,
                    "inputs": {k: np.asarray(v)
                               for k, v in req.inputs.items()},
                    "max_new_tokens": req.max_new_tokens,
                    "key": arr(req.key), "temperature": req.temperature,
                    "top_k": req.top_k, "priority": req.priority,
                    "deadline_s": req.deadline_s}

        def enc_resume(r: _Resume | None):
            return None if r is None else {
                "tokens": list(r.tokens), "logprobs": list(r.logprobs),
                "key": arr(r.key), "last_tok": r.last_tok,
                "first_token_time": r.first_token_time}

        snap = {
            "version": self.SNAPSHOT_VERSION,
            "now": self._now(),
            "config": {
                "num_slots": self.num_slots, "cache_len": self.cache_len,
                "eos_id": self.eos_id, "paged": self.paged,
                "block_size": self.block if self.paged else None,
                "num_blocks": self.num_blocks if self.paged else None,
                "prefix_cache": (self.prefix_cache if self.paged else True),
                "bucket_prompts": self.bucket_prompts,
                "preempt": self.preempt,
                "chunk_prefill": self.chunk_prefill,
                "chunk_size": self.chunk_size,
                "prefill_budget": self.prefill_budget},
            "base_key": arr(self.base_key),
            "queue": [{"req": enc_req(q.req), "prompt_len": q.prompt_len,
                       "submit_time": q.submit_time, "deadline": q.deadline,
                       "resume": enc_resume(q.resume)} for q in self.queue],
            "slots": [None if s is None else
                      {"req": enc_req(s.req), "prompt_len": s.prompt_len,
                       "submit_time": s.submit_time, "deadline": s.deadline,
                       "temperature": s.temperature, "top_k": s.top_k,
                       "priority": s.priority, "tokens": list(s.tokens),
                       "logprobs": list(s.logprobs), "last_tok": s.last_tok,
                       "key": arr(s.key),
                       "first_token_time": s.first_token_time,
                       "admit_time": s.admit_time,
                       "prefill_pos": s.prefill_pos} for s in self.slots],
            "finished": [{"uid": f.uid, "tokens": np.asarray(f.tokens),
                          "logprobs": np.asarray(f.logprobs),
                          "finish_reason": f.finish_reason,
                          "prompt_len": f.prompt_len,
                          "submit_time": f.submit_time,
                          "finish_time": f.finish_time,
                          "first_token_time": f.first_token_time}
                         for f in self.finished],
            "target_slots": self._target_slots,
            "counters": {"steps_run": self.steps_run,
                         "tokens_out": self.tokens_out,
                         "preemptions": self.preemptions,
                         "cancelled": self.cancelled,
                         "expired": self.expired,
                         "prefill_chunks": self.prefill_chunks},
            "cache": (None if self.cache is None
                      else jax.tree.map(np.asarray, self.cache)),
        }
        if self.paged:
            snap["slot_blocks"] = [None if b is None else list(b)
                                   for b in self._slot_blocks]
            snap["allocator"] = self.allocator.state()
            snap["counters"].update(
                block_hwm=self.block_hwm,
                prefix_hit_tokens=self.prefix_hit_tokens,
                prefix_prompt_tokens=self.prefix_prompt_tokens,
                prefill_tokens_skipped=self.prefill_tokens_skipped)
        return snap

    @classmethod
    def from_snapshot(cls, model: Model, params, snap: dict, *,
                      clock=None, rebase_clock: bool = False,
                      mesh=None) -> "Scheduler":
        """Rebuild a scheduler mid-stream from :meth:`snapshot`.  Pass
        ``rebase_clock=True`` when restoring in a *new process* (the
        monotonic clock rebased): pending submit times and deadlines are
        shifted so in-flight TTLs keep their remaining budget.

        Snapshots are mesh-agnostic (host-side numpy, gathered at capture
        time): pass ``mesh`` to restore onto any device topology — the
        pool is re-partitioned per DESIGN.md §14 on load, so a snapshot
        taken on one device restores onto four and vice versa."""
        if int(snap.get("version", -1)) != cls.SNAPSHOT_VERSION:
            raise ValueError(
                f"snapshot version {snap.get('version')!r} != "
                f"{cls.SNAPSHOT_VERSION}")
        cfg = snap["config"]
        base_key = snap.get("base_key")
        sched = cls(
            model, params, num_slots=int(cfg["num_slots"]),
            cache_len=int(cfg["cache_len"]),
            eos_id=None if cfg["eos_id"] is None else int(cfg["eos_id"]),
            key=None if base_key is None else jnp.asarray(base_key),
            paged=bool(cfg["paged"]),
            block_size=int(cfg["block_size"] or 64),
            num_blocks=(None if cfg["num_blocks"] is None
                        else int(cfg["num_blocks"])),
            prefix_cache=bool(cfg["prefix_cache"]),
            bucket_prompts=bool(cfg["bucket_prompts"]),
            preempt=bool(cfg["preempt"]), clock=clock, mesh=mesh,
            chunk_prefill=bool(cfg.get("chunk_prefill", False)),
            chunk_size=int(cfg.get("chunk_size") or 64),
            prefill_budget=(None if cfg.get("prefill_budget") is None
                            else int(cfg["prefill_budget"])))
        shift = (sched._now() - float(snap["now"])) if rebase_clock else 0.0

        def t_of(v):
            return None if v is None else float(v) + shift

        def dec_key(k):
            return None if k is None else jnp.asarray(k)

        def dec_req(d: dict) -> Request:
            return Request(
                uid=int(d["uid"]),
                inputs={k: jnp.asarray(v) for k, v in d["inputs"].items()},
                max_new_tokens=int(d["max_new_tokens"]),
                key=dec_key(d["key"]), temperature=float(d["temperature"]),
                top_k=int(d["top_k"]), priority=int(d["priority"]),
                deadline_s=(None if d["deadline_s"] is None
                            else float(d["deadline_s"])))

        def dec_resume(d):
            return None if d is None else _Resume(
                tokens=[int(t) for t in d["tokens"]],
                logprobs=[float(x) for x in d["logprobs"]],
                key=dec_key(d["key"]), last_tok=int(d["last_tok"]),
                first_token_time=t_of(d.get("first_token_time")))

        sched.queue = deque(
            _Queued(req=dec_req(d["req"]), prompt_len=int(d["prompt_len"]),
                    submit_time=float(d["submit_time"]) + shift,
                    deadline=t_of(d["deadline"]),
                    resume=dec_resume(d["resume"]))
            for d in snap["queue"])
        slots: list[_Slot | None] = []
        for d in snap["slots"]:
            if d is None:
                slots.append(None)
                continue
            req = dec_req(d["req"])
            slots.append(_Slot(
                uid=req.uid, req=req, max_new=req.max_new_tokens,
                key=dec_key(d["key"]), prompt_len=int(d["prompt_len"]),
                submit_time=float(d["submit_time"]) + shift,
                temperature=float(d["temperature"]), top_k=int(d["top_k"]),
                priority=int(d["priority"]), deadline=t_of(d["deadline"]),
                tokens=[int(t) for t in d["tokens"]],
                logprobs=[float(x) for x in d["logprobs"]],
                last_tok=int(d["last_tok"]),
                first_token_time=t_of(d.get("first_token_time")),
                admit_time=t_of(d.get("admit_time", d["submit_time"])),
                prefill_pos=(None if d.get("prefill_pos") is None
                             else int(d["prefill_pos"]))))
        sched.slots = slots
        # mid-prefill slots rebuild their host-side chunk inputs (the
        # effective prompt is derivable: original prompt + resume tokens)
        for s in sched.slots:
            if s is not None and s.prefill_pos is not None:
                s.prefill_toks = sched._resume_tokens(s).astype(np.int32)
        sched.finished = [FinishedRequest(
            uid=int(f["uid"]), tokens=np.asarray(f["tokens"], np.int32),
            logprobs=np.asarray(f["logprobs"], np.float32),
            finish_reason=str(f["finish_reason"]),
            prompt_len=int(f["prompt_len"]),
            submit_time=float(f["submit_time"]),
            finish_time=float(f["finish_time"]),
            first_token_time=(None if f.get("first_token_time") is None
                              else float(f["first_token_time"])))
            for f in snap["finished"]]
        c = snap["counters"]
        sched.steps_run = int(c["steps_run"])
        sched.tokens_out = int(c["tokens_out"])
        sched.preemptions = int(c["preemptions"])
        sched.cancelled = int(c["cancelled"])
        sched.expired = int(c["expired"])
        sched.prefill_chunks = int(c.get("prefill_chunks", 0))
        sched._target_slots = (None if snap["target_slots"] is None
                               else int(snap["target_slots"]))
        if snap["cache"] is not None:
            sched.cache = jax.tree.map(jnp.asarray, snap["cache"])
            sched._constrain_pool()
        if sched.paged:
            sched.allocator = BlockAllocator.from_state(snap["allocator"])
            sched._slot_blocks = [
                None if b is None else [int(x) for x in b]
                for b in snap["slot_blocks"]]
            sched.block_hwm = int(c["block_hwm"])
            sched.prefix_hit_tokens = int(c["prefix_hit_tokens"])
            sched.prefix_prompt_tokens = int(c["prefix_prompt_tokens"])
            sched.prefill_tokens_skipped = int(c["prefill_tokens_skipped"])
            for i, s in enumerate(sched.slots):
                if s is not None and s.prefill_pos is not None \
                        and sched._slot_blocks[i] is not None:
                    t = np.full(sched.max_blocks, sched.num_blocks,
                                np.int32)
                    blocks = sched._slot_blocks[i]
                    t[:len(blocks)] = blocks
                    s.prefill_table = t
        return sched

    # ---------------------------------------------------------------- decode
    def _decode_arrays(self):
        """Host-side inputs of the masked decode pass.  Mid-prefill slots
        are NOT decode-active: the decode pass's per-slot writes are
        masked off for them, leaving their partially-built rows alone.
        Counts the positions the active rows attend over (``pos + 1``:
        the prompt and every token generated, the last one written this
        pass) into ``decode_kv_tokens``."""
        B = self.num_slots
        toks = np.zeros((B, 1), np.int32)
        active = np.zeros((B,), bool)
        temps = np.zeros((B,), np.float32)
        topk = np.zeros((B,), np.int32)
        for i, s in enumerate(self.slots):
            if s is not None and s.prefill_pos is None:
                toks[i, 0] = s.last_tok
                active[i] = True
                temps[i] = s.temperature
                topk[i] = s.top_k
                self.decode_kv_tokens += s.prompt_len + len(s.tokens)
        return toks, active, temps, topk

    def _finish_decode(self, logits, temps, topk,
                       done: list[FinishedRequest]) -> None:
        """Pick + emit + retire for one decode pass's logits.  Slots still
        prefilling neither consume PRNG splits nor receive tokens."""
        decoding = [s if s is not None and s.prefill_pos is None else None
                    for s in self.slots]
        with _Phase(self, "pick"):
            if any(s is not None and s.temperature > 0.0 for s in decoding):
                keys = jnp.stack([
                    self._next_key(s) if s is not None and s.temperature > 0.0
                    else jnp.zeros((2,), jnp.uint32)
                    for s in decoding])
            else:                         # all greedy: no splits consumed
                keys = jnp.zeros((self.num_slots, 2), jnp.uint32)
            tok, lp = self._pick(self._gather_logits(logits[:, 0, :]), keys,
                                 jnp.asarray(temps), jnp.asarray(topk))
        with _Phase(self, "sync"):
            tok, lp = np.asarray(tok), np.asarray(lp)
        self.steps_run += 1
        with _Phase(self, "emit"):
            for i, s in enumerate(decoding):
                if s is None:
                    continue
                self._emit(s, int(tok[i]), float(lp[i]))
                if self._finished_reason(s):
                    done.append(self._retire(s))
                    if self.paged:
                        self._release_blocks(i)
                    self.slots[i] = None

    def _decode_once(self, done: list[FinishedRequest]) -> None:
        with _Phase(self, "inputs"):
            toks, active, temps, topk = self._decode_arrays()
            toks, active = jnp.asarray(toks), jnp.asarray(active)
        with _Phase(self, "dispatch"):
            logits, self.cache = self.model.jitted_decode_step_masked(
                self.mesh)(self.params, self.cache, toks, active)
        self._finish_decode(logits, temps, topk, done)

    def _mixed_once(self, done: list[FinishedRequest]) -> None:
        """One fused serving step: up to ``chunk_lanes`` prefill chunks
        (best-rank-first among mid-prefill slots) run alongside the
        masked decode of every fully-prefilled slot — one traced program
        per (K, C) shape, so the zero-replan contract holds under
        chunked prefill."""
        K, C = self.chunk_lanes, self.chunk_size
        with _Phase(self, "inputs"):
            toks, active, temps, topk = self._decode_arrays()
            pref = sorted(
                (self._srank(s), i) for i, s in enumerate(self.slots)
                if s is not None and s.prefill_pos is not None)
            lanes: list[tuple[int, int, int]] = []
            ck_tok = np.zeros((K, C), np.int32)
            ck_slot = np.zeros((K,), np.int32)
            ck_start = np.zeros((K,), np.int32)
            ck_true = np.ones((K,), np.int32)   # 1 keeps unused lanes in-range
            ck_active = np.zeros((K,), bool)
            ck_tables = (np.full((K, self.max_blocks), self.num_blocks,
                                 np.int32) if self.paged else None)
            for j, (_, i) in enumerate(pref[:K]):
                s = self.slots[i]
                start = s.prefill_pos
                take = min(C, len(s.prefill_toks) - start)
                ck_tok[j, :take] = s.prefill_toks[start:start + take]
                ck_slot[j] = i
                ck_start[j] = start
                ck_true[j] = take
                ck_active[j] = True
                if self.paged:
                    ck_tables[j] = s.prefill_table
                lanes.append((i, start, take))
            args = [self.params, self.cache, jnp.asarray(toks),
                    jnp.asarray(active), jnp.asarray(ck_tok),
                    jnp.asarray(ck_slot), jnp.asarray(ck_start),
                    jnp.asarray(ck_true), jnp.asarray(ck_active)]
            if self.paged:
                args.append(jnp.asarray(ck_tables))
        with _Phase(self, "dispatch"):
            logits, ck_logits, self.cache = self.model.jitted_mixed_step(
                K, C, self.mesh)(*args)
        self.prefill_chunks += len(lanes)
        if active.any():
            self._finish_decode(logits, temps, topk, done)
        for j, (i, start, take) in enumerate(lanes):
            s = self.slots[i]
            s.prefill_pos = start + take
            if s.prefill_pos >= len(s.prefill_toks):
                with _Phase(self, "first_token"):
                    self._complete_prefill(i, s, ck_logits[j], done)

    def _complete_prefill(self, i: int, s: _Slot, logits_row,
                          done: list[FinishedRequest]) -> None:
        """A lane just processed its final chunk: publish the prompt's
        full blocks for prefix sharing, pick the first generated token
        from the lane logits (same per-request PRNG discipline as a
        monolithic admission pick) and flip the slot to decode mode."""
        if self.paged and self.prefix_cache:
            blocks = self._slot_blocks[i] or []
            hashes = chain_hashes(s.prefill_toks, self.block)
            for bid, h in zip(blocks, hashes):
                self.allocator.publish(bid, h)
        tok, lp = self._pick_one(logits_row, s)
        s.prefill_pos = None
        s.prefill_toks = None
        s.prefill_table = None
        self._emit(s, tok, lp)
        if self._finished_reason(s):
            done.append(self._retire(s))
            if self.paged:
                self._release_blocks(i)
            self.slots[i] = None

    def _finished_reason(self, slot: _Slot) -> str | None:
        if self.eos_id is not None and slot.last_tok == self.eos_id:
            return "eos"
        if len(slot.tokens) >= slot.max_new:
            return "length"
        return None

    def _retire(self, slot: _Slot,
                reason: str | None = None) -> FinishedRequest:
        return FinishedRequest(
            uid=slot.uid,
            tokens=np.asarray(slot.tokens, np.int32),
            logprobs=np.asarray(slot.logprobs, np.float32),
            finish_reason=reason or self._finished_reason(slot),
            prompt_len=slot.prompt_len,
            submit_time=slot.submit_time,
            finish_time=self._now(),
            first_token_time=slot.first_token_time)


def make_requests(batch: dict, max_new_tokens: int,
                  key: jax.Array | None = None, temperature: float = 0.0,
                  top_k: int = 0, priority: int = 0,
                  deadline_s: float | None = None) -> list[Request]:
    """Split a pre-batched input dict (engine.generate contract) into one
    Request per row; row index becomes the uid.  The batch-level sampling
    params become per-request params; ``priority``/``deadline_s`` apply
    uniformly to every row."""
    arrays = {k: v for k, v in batch.items() if k != "cache_len"}
    B = arrays["tokens"].shape[0]
    out = []
    for b in range(B):
        out.append(Request(
            uid=b,
            inputs={k: v[b:b + 1] for k, v in arrays.items()},
            max_new_tokens=max_new_tokens,
            key=None if key is None else jax.random.fold_in(key, b),
            temperature=temperature, top_k=top_k,
            priority=priority, deadline_s=deadline_s))
    return out
