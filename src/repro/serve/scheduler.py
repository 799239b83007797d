"""FCFS continuous batching with preempt-by-eviction and token-budgeted
chunked prefill.

Classic continuous batching (Orca/vLLM style) over the paged KV cache:

  * requests queue FCFS; a request is ADMITTED when a batch slot is
    free and the pool can cover its prompt + one decode page;
  * every engine tick decodes ONE token for every decoding sequence,
    and assigns every PREFILLING sequence (fresh admission, preemption
    re-prefill, or a prefix-cache resume's uncovered suffix) up to
    ``prefill_chunk`` prompt tokens, all under one shared per-tick
    token budget (``tick_tokens``) — decode claims its tokens first,
    so a long prompt can never stall the decodes sharing its batch;
  * when a decode step needs a page and the pool is dry, the YOUNGEST
    running sequence is preempted by eviction: its pages are freed, it
    re-queues at the head of the waiting line (FCFS order preserved —
    it is still ahead of everything that arrived after it) and will
    re-prefill on re-admission.

``Request`` identity is OBJECT identity (``eq=False``): two requests
holding equal field values are still distinct schedulable entities, so
plan membership (``plan.preempted``) and batch-skip bookkeeping can
never conflate them; cross-object bookkeeping uses rid sets.

The scheduler is host-side and deterministic: given the same arrival
trace it makes the same decisions regardless of communicator backend,
which is what lets the mesh test demand bit-identical token streams
across xla/posh/pallas.
"""
from __future__ import annotations

import dataclasses
import itertools
from collections import deque
from typing import Optional

import numpy as np

from .kv_cache import PagedKVCache, PageMigration
from .sampling import GREEDY, SamplingParams


@dataclasses.dataclass(eq=False)
class Request:
    """One inference request.  ``prompt`` is a list of token ids;
    ``max_new`` the decode budget; ``sampling`` the per-request
    sampling policy (default greedy).

    ``eq=False``: requests compare and hash by OBJECT identity, never
    by field values — the scheduler tracks live entities, and two
    requests with identical parameters must stay distinguishable in
    membership tests (``running.remove``, ``in plan.preempted``)."""

    rid: int
    prompt: list
    max_new: int
    t_arrive: float = 0.0
    sampling: SamplingParams = GREEDY
    # SLO attributes (serve.slo): class rank orders admission and
    # (inversely) eviction; ``deadline`` is the relative TTFT budget
    # (engine clock units) attainment is measured against — and the
    # shed trigger for best-effort traffic; ``tenant`` keys the
    # token-rate fairness bucket
    priority: str = "interactive"
    deadline: Optional[float] = None
    tenant: int = 0

    # runtime (engine-owned)
    out: list = dataclasses.field(default_factory=list)
    n_done: int = 0          # prompt tokens whose KV is in pages
    prefill_chunks: list = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    t_admit: Optional[float] = None   # first admission; kept on preemption
    t_first: Optional[float] = None
    t_finish: Optional[float] = None
    preemptions: int = 0
    shed: bool = False       # dropped by deadline shedding, never served

    @property
    def n_prompt(self) -> int:
        return len(self.prompt)

    def next_input(self) -> int:
        """The token this sequence feeds next: the prompt while it is
        still being consumed, the last sampled token afterwards."""
        if self.n_done < self.n_prompt:
            return int(self.prompt[self.n_done])
        return int(self.out[-1])

    def is_prefilling(self) -> bool:
        return self.n_done < self.n_prompt

    def finished(self) -> bool:
        return len(self.out) >= self.max_new

    def reset(self) -> None:
        """Preemption: all progress is rebuilt from scratch."""
        self.out.clear()
        self.prefill_chunks.clear()
        self.n_done = 0
        self.slot = None
        self.preemptions += 1


@dataclasses.dataclass
class TickPlan:
    """What one scheduler tick decided (the engine executes it)."""

    admitted: list = dataclasses.field(default_factory=list)   # fresh
    resumed: list = dataclasses.field(default_factory=list)    # prefix-attached
    preempted: list = dataclasses.field(default_factory=list)
    migrations: list = dataclasses.field(default_factory=list)  # PageMigration
    prefill: list = dataclasses.field(default_factory=list)    # (req, n_tokens)
    shed: list = dataclasses.field(default_factory=list)       # deadline drops


class FCFSScheduler:
    """First-come-first-served admission over a PagedKVCache.

    ``prefill_chunk`` caps the prompt tokens one sequence consumes per
    tick; ``tick_tokens`` is the per-tick token budget shared by decode
    (one token per decoding sequence, claimed first) and prefill chunks
    (handed out FCFS in admission order).  The oldest prefilling
    sequence is always guaranteed one token, so prefill can never
    starve outright.  With speculation on (``spec_k > 0``) a decoding
    sequence's claim is its whole verify window — one pending token
    plus ``draft_allowance`` drafts — in both the token budget and the
    page demand, so spec decode composes with chunked prefill and
    preempt-by-eviction instead of silently overcommitting the tick.
    ``tick_tokens=0`` resolves to
    ``max_batch * (1 + spec_k) + prefill_chunk``."""

    def __init__(self, kv: PagedKVCache, *, max_batch: int,
                 max_seq: int, my_pe: int = 0, prefill_chunk: int = 8,
                 tick_tokens: int = 0, spec_k: int = 0, slo=None):
        self.kv = kv
        self.max_batch = int(max_batch)
        self.max_seq = int(max_seq)
        self.my_pe = int(my_pe)
        self.prefill_chunk = max(int(prefill_chunk), 1)
        self.spec_k = max(int(spec_k), 0)
        # under speculation a decoding sequence's tick claim is its
        # whole verify window (pending token + drafts), so the default
        # budget scales with it
        self.tick_tokens = int(tick_tokens) or (
            self.max_batch * (1 + self.spec_k) + self.prefill_chunk)
        # SLO policy (serve.slo.SLOPolicy): None keeps plain FCFS —
        # every decision below is bit-identical to the pre-SLO
        # scheduler in that case
        self.slo = slo
        self.waiting: deque = deque()
        self.running: list = []          # admission order (oldest first)
        self._decode_refund = 0          # unspent decode claims of
                                         # sequences evicted this tick
        self._admit_seq = itertools.count()
        self._admit_idx: dict = {}       # rid -> admission ticket
        self._arrive_seq = itertools.count()
        self._arrive_idx: dict = {}      # rid -> submission ticket
        self.stats = {"admitted": 0, "resumed": 0, "preempted": 0,
                      "finished": 0, "ticks": 0, "prefill_tokens": 0,
                      "released": 0, "adopted": 0, "shed": 0,
                      "rate_deferred": 0}

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        if req.n_prompt + req.max_new > self.max_seq:
            raise ValueError(
                f"request {req.rid}: {req.n_prompt}+{req.max_new} tokens "
                f"exceed max_seq {self.max_seq}")
        self._arrive_idx.setdefault(req.rid, next(self._arrive_seq))
        self.waiting.append(req)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    # ------------------------------------------------------------------
    def tick(self, now: float = 0.0) -> TickPlan:
        """One scheduling round: budget the tick's tokens (decode
        first, then prefill chunks FCFS), grow running sequences
        (preempting by eviction when the pool is dry), then admit FCFS
        while slots, pages and budget last.  Prefix-cache hits admit as
        RESUMED sequences whose first pages arrive by migration instead
        of recompute.  With an SLO policy attached: expired best-effort
        waiters shed first, admission runs in priority order, eviction
        inverse-priority, and best-effort traffic degrades (chunk cap,
        draft strip) while higher classes have unmet demand."""
        self.stats["ticks"] += 1
        plan = TickPlan()
        if self.slo is not None:
            self._shed_expired(now, plan)
            self.slo.update_pressure(self.waiting, self.running, self.kv)
            self.slo.tick_refill()
        quotas: dict = {}                # rid -> prompt tokens this tick
        budget = self.tick_tokens
        # decode claims first: one token per decoding sequence PLUS its
        # draft allowance — a verify window spends real forward tokens,
        # so speculation composes with (never starves) chunked prefill
        budget -= sum(1 + self.draft_allowance(r) for r in self.running
                      if not r.is_prefilling())
        for req in self.running:         # admission order = FCFS
            if req.is_prefilling():
                budget = self._grant(req, quotas, budget,
                                     guarantee=not quotas)
        self._decode_refund = 0
        self._ensure_running(plan, quotas)
        # tokens granted to (or claimed by) sequences that eviction
        # just removed are unspent — hand them to this tick's admissions
        for r in plan.preempted:
            budget += quotas.pop(r.rid, 0)
        budget += self._decode_refund
        self._admit(plan, quotas, budget, now)
        plan.prefill = [(r, quotas[r.rid]) for r in self.running
                        if r.rid in quotas]
        self.stats["prefill_tokens"] += sum(n for _, n in plan.prefill)
        return plan

    def draft_allowance(self, req: Request) -> int:
        """Draft tokens a decoding sequence may carry into this tick's
        verify window: ``spec_k`` capped by the output budget — a
        request with ``m`` tokens left to emit can accept at most
        ``m - 1`` drafts (the verify pass itself emits one), so pages
        and budget are never reserved for tokens that cannot exist."""
        if self.spec_k == 0 or req.is_prefilling():
            return 0
        if self.slo is not None and self.slo.strip_drafts(req):
            return 0          # degraded: plain one-token decode
        return max(0, min(self.spec_k,
                          req.max_new - len(req.out) - 1))

    def _grant(self, req: Request, quotas: dict, budget: int, *,
               guarantee: bool) -> int:
        """Assign ``req`` its chunk for this tick out of ``budget``.
        ``guarantee`` forces at least one token (the oldest prefilling
        sequence and fresh admissions always make progress)."""
        chunk = self.prefill_chunk
        if self.slo is not None:
            chunk = self.slo.chunk_cap(req, chunk)
        q = min(chunk, max(budget, 0))
        if guarantee:
            q = max(q, 1)
        q = min(q, req.n_prompt - req.n_done)
        if q > 0:
            quotas[req.rid] = q
        return budget - q

    def _ensure_running(self, plan: TickPlan, quotas: dict) -> None:
        """Every running sequence needs page room for the tokens this
        tick writes.  Out of pages -> evict the youngest until it fits
        (never evicting the sequence we are growing unless it IS the
        youngest — then it preempts itself and waits)."""
        for req in list(self.running):
            if req not in self.running:
                continue                     # evicted by an earlier loop turn
            # exact demand for THIS tick's writes: prefill covers its
            # chunk quota; decode writes the last sampled token at
            # position n_prompt + len(out) - 1 PLUS one slot per draft
            # its verify window will score.  Asking for one more would
            # preempt a neighbour for a page the final token of a
            # finishing sequence never writes.
            need = req.n_done + quotas.get(req.rid, 0) \
                if req.is_prefilling() \
                else req.n_prompt + len(req.out) + self.draft_allowance(req)
            while not self.kv.ensure(req.rid, max(need, 1)):
                victim = self._youngest()
                self._preempt(victim, plan)
                if victim is req:
                    break

    def _youngest(self) -> Request:
        """The eviction victim.  FCFS: the youngest admission.  SLO:
        strictly inverse-priority — the lowest class goes first
        (best_effort, then batch, then interactive), youngest within a
        class — so interactive sequences evict LAST."""
        if self.slo is not None:
            return max(self.running,
                       key=lambda r: self.slo.evict_key(
                           r, self._admit_idx[r.rid]))
        return max(self.running, key=lambda r: self._admit_idx[r.rid])

    def _shed_expired(self, now: float, plan: TickPlan) -> None:
        """Deadline shedding, BEFORE any admission or degradation this
        tick: waiting best-effort requests whose deadline passed are
        dropped — they leave the system without ever holding pages."""
        for req in [r for r in self.waiting
                    if self.slo.should_shed(r, now)]:
            self.waiting.remove(req)     # identity (eq=False)
            req.shed = True
            req.t_finish = now
            plan.shed.append(req)
            self.stats["shed"] += 1
            self.slo.note_shed(req)

    def _preempt(self, req: Request, plan: TickPlan) -> None:
        if not req.is_prefilling():
            # its decode claim (token + draft window) is unspent
            self._decode_refund += 1 + self.draft_allowance(req)
        self.kv.free_seq(req.rid)
        self.running.remove(req)             # identity (eq=False)
        req.reset()
        # back to the head of the line: still ahead of later arrivals
        self.waiting.appendleft(req)
        plan.preempted.append(req)
        self.stats["preempted"] += 1

    def _admission_order(self) -> list:
        """Admission candidates.  FCFS: the waiting deque as-is.  SLO:
        (class rank, arrival) — a preemption victim keeps its original
        arrival ticket, so it stays ahead of later arrivals WITHIN its
        class, and interactive arrivals jump the best-effort backlog."""
        if self.slo is None:
            return list(self.waiting)
        return sorted(self.waiting,
                      key=lambda r: self.slo.admit_key(
                          r, self._arrive_idx.setdefault(
                              r.rid, next(self._arrive_seq))))

    def _admit(self, plan: TickPlan, quotas: dict, budget: int,
               now: float) -> None:
        preempted_rids = {r.rid for r in plan.preempted}
        for req in self._admission_order():
            if len(self.running) >= self.max_batch:
                break
            if req.rid in preempted_rids:
                # evicted THIS tick to let an older sequence breathe —
                # re-admitting immediately would thrash prefill
                break
            if self.slo is not None and not self.slo.admit_charge(req):
                # tenant over its token rate: ITS request defers, the
                # line behind it does not (the fairness property)
                self.stats["rate_deferred"] += 1
                continue
            hit = self.kv.lookup_prefix(req.prompt)
            if hit is not None:
                # remote owner: pages arrive by one-sided migration;
                # same-PE owner: the identical put_nbi path with
                # self-pairs — a 0-hop page copy into fresh pages, so
                # the pinned originals stay in the index
                if not self._admit_resumed(req, hit, plan, now):
                    if self.slo is not None:
                        self.slo.admit_refund(req)
                    break
            else:
                # prompt + the first decode page, all or nothing
                if not self.kv.alloc_seq(req.rid, req.n_prompt + 1):
                    if self.slo is not None:
                        self.slo.admit_refund(req)
                    break
                self.waiting.remove(req)     # identity (eq=False)
                self._start(req, now)
                plan.admitted.append(req)
                self.stats["admitted"] += 1
            budget = self._grant(req, quotas, budget, guarantee=True)

    def _admit_resumed(self, req: Request, hit, plan: TickPlan,
                       now: float) -> bool:
        """Prefix pages live on another PE: take landing pages, plan the
        migrations, and admit with the prefix marked done — the rest of
        the prompt streams through the chunked-prefill path."""
        owner_pe, src_pages = hit
        landing = self.kv.take_pages(len(src_pages))
        if landing is None:
            return False
        self.kv.attach_seq(req.rid, landing)
        if not self.kv.ensure(req.rid, req.n_prompt + 1):
            self.kv.free_seq(req.rid)
            return False
        plan.migrations.extend(
            PageMigration(owner_pe, self.my_pe, s, d)
            for s, d in zip(src_pages, landing))
        self.waiting.remove(req)             # identity (eq=False)
        self._start(req, now)
        # leave >= 1 prompt token to feed: re-feeding the boundary token
        # rewrites identical KV (idempotent) and yields the next logits
        covered = len(landing) * self.kv.page_tokens
        req.n_done = min(covered, req.n_prompt - 1)
        plan.resumed.append(req)
        self.stats["resumed"] += 1
        self.kv.stats["prefix_hits"] += 1
        return True

    def _start(self, req: Request, now: Optional[float] = None) -> None:
        """Enter ``req`` into the running set.  ``now`` is the admitting
        tick's time, stamped on the first admission only (a handed-off
        sequence, which passes none, was admitted by its producer)."""
        self.running.append(req)
        self._admit_idx[req.rid] = next(self._admit_seq)
        if req.t_admit is None:
            req.t_admit = now

    # ------------------------------------------------------------------
    # disaggregated handoff (serve.disagg): a sequence leaves one cell's
    # scheduler mid-life and joins another's
    # ------------------------------------------------------------------
    def release(self, req: Request) -> None:
        """Hand a sequence off: remove it from this cell's running set
        WITHOUT freeing pages or resetting progress (contrast
        ``_preempt``) — its KV stays resident as the handoff payload
        source until the consumer cell acknowledges adoption."""
        self.running.remove(req)             # identity (eq=False)
        self.stats["released"] += 1

    def adopt(self, req: Request) -> None:
        """Receive a handed-off sequence: it enters this cell's running
        set mid-life (prompt consumed, first token emitted), youngest in
        eviction order like any fresh admission.  The caller has already
        attached its landing pages (``PagedKVCache.adopt_seq``)."""
        if len(self.running) >= self.max_batch:
            raise RuntimeError(
                f"adopt of {req.rid}: cell batch is full "
                f"({self.max_batch}) — the router must gate on slots")
        self._start(req)
        self.stats["adopted"] += 1

    # ------------------------------------------------------------------
    def advance(self, req: Request, token: int, now: float = 0.0) -> None:
        """Record the outcome of one decode step for ``req``: a sampled
        token appended (a still-prefilling sequence routes through
        ``note_chunk`` as a 1-token chunk, so the chunk bookkeeping
        stays the single source of truth).  The caller removes finished
        sequences via ``finish``."""
        if req.is_prefilling():
            self.note_chunk(req, 1, token, now)
        else:
            req.out.append(int(token))

    def note_chunk(self, req: Request, n: int, token: int,
                   now: float = 0.0) -> None:
        """Chunked prefill consumed ``n`` prompt tokens for ``req``;
        when the chunk completes the prompt, ``token`` (sampled after
        the last prompt position) is the first output token."""
        req.n_done += int(n)
        assert req.n_done <= req.n_prompt, (req.rid, req.n_done)
        req.prefill_chunks.append(int(n))
        if not req.is_prefilling():
            req.out.append(int(token))
            req.t_first = now

    def note_prefilled(self, req: Request, first_token: int,
                       now: float = 0.0) -> None:
        """A single chunk consumed the whole remaining prompt at once."""
        self.note_chunk(req, req.n_prompt - req.n_done, first_token, now)

    def finish(self, req: Request, now: float = 0.0,
               register_prefix: bool = True) -> None:
        req.t_finish = now
        self.running.remove(req)             # identity (eq=False)
        if register_prefix:
            pages = self.kv.tables[req.rid]
            n_full = min(len(pages),
                         req.n_prompt // self.kv.page_tokens)
            if n_full and self.kv.register_prefix(req.prompt, self.my_pe,
                                                  pages[:n_full]):
                # the registered pages stay resident (owned by the
                # prefix index, not the free list) so they remain
                # migratable; the rest return to the pool
                self.kv.tables[req.rid] = pages[n_full:]
        self.kv.free_seq(req.rid)
        self.stats["finished"] += 1
