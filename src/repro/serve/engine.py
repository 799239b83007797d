"""Continuous-batching inference engine over the paged symmetric-heap
KV cache.

The engine is split in two layers:

  * **pure step functions** (``make_prefill`` / ``make_decode_step``) —
    trace-friendly, built from the same model weights AND the same
    projection convention the registry's train/decode paths use
    (``attention.project_qkv``, ``embed``, ``mlp``), tensor-parallel
    through ``ctx.tp_comm`` so all registered communicator backends
    (xla / posh / pallas) serve traffic.  Both steps read/write K/V
    through the block table (``ops.paged_attention``), and both end in
    the TP-aware two-phase sampler (``serve.sampling``): per-shard
    top-k candidates merged via ``ctx.tp_comm.top_k_merge``, then a
    per-sequence counter-RNG draw keyed ``(rid, position)`` — token
    streams are backend- and batch-composition-invariant by
    construction.  ``make_prefill`` consumes prompt CHUNKS: a
    ``(B, prefill_chunk)`` window of each prompt, attending through the
    pages written so far, so prefill progress is metered by the
    scheduler's token budget instead of monopolizing a tick.

    ``make_verify`` is the SPECULATIVE-DECODE twin of the prefill
    window: the same trunk over a ``(B, k+1)`` window of pending token
    + proposed drafts, sampling at EVERY position with the
    non-speculative counter keys, so exact prefix-match acceptance
    reproduces the sequential stream bit-for-bit (``serve.spec`` holds
    the draft proposers).

  * a **host-side driver** (``ServeEngine``) — owns the
    ``FCFSScheduler`` + ``PagedKVCache``, executes each tick's plan
    (migrate -> chunk-prefill -> decode/verify), and drains every tick's
    planned page migrations with ``put_nbi`` + ONE ``quiet()`` on a
    ``CommQueue`` before the step functions run.  The execution
    substrate is pluggable (``LocalExec`` jits on one device;
    ``serve.MeshExec`` shard_maps the same steps over a DP x TP mesh),
    so the same scheduler drives one device and a TP mesh.

Batch slots are fixed (``ServeConfig.max_batch``): empty slots carry
the null page table and length 0, which zeroes their attention output
and routes their KV writes to the null page — no branches in the traced
step.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.heap import SymmetricHeap
from repro.core.ordering import CommQueue, LocalTransport
from repro.kernels import ops
from repro.models import attention as attn
from repro.models import embed as emb
from repro.models import lm
from repro.models import mlp as ff
from repro.models.common import norm_apply
from repro.parallel.ctx import ParallelCtx

from . import sampling
from .kv_cache import NULL_PAGE, PagedKVCache
from .scheduler import FCFSScheduler, Request
from .slo import PRIORITIES, SLOConfig, SLOPolicy
from .trace import span


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Trace-time serving shape: page geometry, batch and sequence
    bounds, prefill chunking, attention implementation, KV precision,
    sampler bounds."""

    page_tokens: int = 8
    n_pages: int = 64
    max_batch: int = 4
    max_seq: int = 64                 # prompt + decode budget per seq
    max_prompt: int = 32              # retired: prompts now stream
                                      # through chunked prefill (kept
                                      # for config compatibility)
    prefill_chunk: int = 8            # prompt tokens per seq per tick
    tick_tokens: int = 0              # shared decode+prefill budget per
                                      # tick (0 -> max_batch + chunk)
    attn_impl: str = "kernel"         # "kernel" (Pallas) | "ref" (jnp);
                                      # governs decode AND the
                                      # prefill/verify window trunk
    kv_dtype: jnp.dtype = jnp.float32
    prefix_keep: bool = False         # pin finished prompts' full pages
                                      # as migratable prefix cache
    sample_candidates: int = 8        # static top-k bound per shard
    sample_seed: int = 0              # RNG stream root for sampling
    spec_k: int = 0                   # draft tokens verified per seq per
                                      # tick (0 = speculation off)
    draft: str = "ngram"              # default proposer when none is
                                      # passed ("ngram" self-draft; a
                                      # model-backed proposer is built
                                      # by the caller, see serve.spec)
    slo: Optional[SLOConfig] = None   # SLO policy (serve.slo): priority
                                      # admission, deadline shedding,
                                      # best-effort degradation, tenant
                                      # fairness (None = plain FCFS)

    @property
    def table_slots(self) -> int:
        return -(-self.max_seq // self.page_tokens)


def _check_supported(cfg, ctx: ParallelCtx) -> None:
    if cfg.family not in ("dense", "moe"):
        raise NotImplementedError(
            f"repro.serve drives dense/moe decoders; got {cfg.family}")
    if cfg.attn_layout(ctx.tp_size) != "head":
        raise NotImplementedError(
            "repro.serve requires the head-parallel attention layout "
            f"({cfg.n_heads} heads, tp={ctx.tp_size})")
    if cfg.swa_window is not None:
        raise NotImplementedError("sliding-window + paged cache: not yet")


# ======================================================================
# pure step functions
# ======================================================================
def _write_pages(pool, li, k, v, bt, pos, page_tokens):
    """Scatter one-token-per-sequence K/V into the page pool.
    pool: (n_pages, 2, L, P, kvh, dh); k/v: (b, kvh, dh); pos: (b,).
    Inactive slots carry the null block table -> rows land in page 0."""
    page = jnp.take_along_axis(bt, (pos // page_tokens)[:, None],
                               axis=1)[:, 0]
    slot = pos % page_tokens
    dt = pool.dtype
    pool = pool.at[page, 0, li, slot].set(k.astype(dt))
    pool = pool.at[page, 1, li, slot].set(v.astype(dt))
    return pool


def make_decode_step(cfg, ctx: ParallelCtx, scfg: ServeConfig):
    """One serving tick: (params, pool, tokens, pos, bt, lens, samp) ->
    (next_tokens, pool).

    tokens (b,) int32 input token per slot; pos (b,) its position;
    bt (b, table_slots) int32 block tables; lens (b,) valid tokens
    AFTER this write (pos+1 for live slots, 0 for empty ones); samp the
    ``sampling.batch_state`` pytree (per-slot sampling params + rid).
    """
    _check_supported(cfg, ctx)
    P = scfg.page_tokens

    def step(params, pool, tokens, pos, bt, lens, samp):
        cd = ctx.compute_dtype
        with jax.named_scope("embed"):
            x = emb.embed_lookup(params["embed"], tokens[:, None],
                                 ctx)[:, 0]
        b = x.shape[0]

        def body(carry, inputs):
            x, pool = carry
            p, li = inputs
            with jax.named_scope("qkv"):
                h = norm_apply("rms", p["ln1"], x).astype(cd)
                q, k, v = attn.project_qkv(p["attn"], h[:, None],
                                           pos[:, None], cfg, ctx)
                q, k, v = q[:, 0], k[:, 0], v[:, 0]
            with jax.named_scope("kv_write"):
                pool = _write_pages(pool, li, k, v, bt, pos, P)
            with jax.named_scope("attn_kernel"):
                o = ops.paged_attention(q, pool, li, bt, lens,
                                        impl=scfg.attn_impl)
            with jax.named_scope("attn_out"):
                out = o.reshape(b, -1).astype(cd) \
                    @ p["attn"]["wo"].astype(cd)
                out = ctx.tp_comm.psum(out)
                x = x + out
            with jax.named_scope("mlp"):
                m = lm._decode_mlp(p["mlp"],
                                   norm_apply("rms", p["ln2"], x), ctx, cfg)
            return (x + m, pool), None

        (x, pool), _ = jax.lax.scan(
            body, (x, pool),
            (params["blocks"], jnp.arange(cfg.n_layers)))
        with jax.named_scope("head_sample"):
            x = norm_apply("rms" if cfg.family != "encdec" else "layer",
                           params["ln_f"], x)
            head = params["embed"] if cfg.tie_embeddings else params["head"]
            logits = emb.lm_head_logits(head, x.astype(cd), ctx)
            nxt = sampling.sample_tokens(
                logits, ctx, samp, pos + 1,
                n_candidates=scfg.sample_candidates)
        return nxt.astype(jnp.int32), pool

    return step


def _make_window_forward(cfg, ctx: ParallelCtx, scfg: ServeConfig):
    """The shared chunk-window trunk: (params, pool, ids, start, n_tok,
    bt) -> (x, pool) where ``x`` is the final-norm hidden state at every
    window position.

    ids (b, C) a token window per sequence, right-padded; start (b,)
    the absolute position of ids[:, 0]; n_tok (b,) valid tokens in the
    window (0 = inactive slot).  Writes every valid position's K/V into
    the pages through the block table and attends each position against
    the pages written so far (position j sees ``start + j + 1`` tokens
    — the paged analogue of the causal mask).  Chunked prefill and
    speculative verify are BOTH this trunk — they differ only in which
    positions they sample (prefill: the last; verify: all of them), so
    the verify pass cannot numerically drift from the prefill path the
    chunking-invariance tests pin."""
    _check_supported(cfg, ctx)
    P = scfg.page_tokens

    def window(params, pool, ids, start, n_tok, bt):
        cd = ctx.compute_dtype
        with jax.named_scope("embed"):
            x = emb.embed_lookup(params["embed"], ids, ctx)
        b, t = ids.shape
        pos = start[:, None] + jnp.arange(t)[None]           # (b, t)
        valid = jnp.arange(t)[None] < n_tok[:, None]

        def body(carry, inputs):
            x, pool = carry
            p, li = inputs
            with jax.named_scope("qkv"):
                h = norm_apply("rms", p["ln1"], x).astype(cd)
                q, k, v = attn.project_qkv(p["attn"], h, pos, cfg, ctx)
            with jax.named_scope("kv_write"):
                # page writes: token (b, j) -> page bt[b, pos//P] slot
                # pos%P; the invalid window tail lands in the null page
                sidx = jnp.clip(pos // P, 0, bt.shape[1] - 1)
                page = jnp.take_along_axis(bt, sidx, axis=1)  # (b, t)
                page = jnp.where(valid, page, NULL_PAGE)
                slot = pos % P
                dt = pool.dtype
                pool = pool.at[page, 0, li, slot].set(k.astype(dt))
                pool = pool.at[page, 1, li, slot].set(v.astype(dt))
            with jax.named_scope("attn_kernel"):
                # whole-window paged attention in one fused call:
                # position j attends to its first start+j+1 paged tokens
                # of layer li (the chunk's K/V were just written above)
                o = ops.paged_prefill_attention(q, pool, li, bt, start,
                                                n_tok, impl=scfg.attn_impl)
            with jax.named_scope("attn_out"):
                out = o.reshape(b, t, -1).astype(cd) \
                    @ p["attn"]["wo"].astype(cd)
                out = ctx.tp_comm.psum(out)
                x = x + out
            with jax.named_scope("mlp"):
                ctx1 = ctx.with_(sp=False)
                mlp = (ff.moe_apply if cfg.moe else ff.mlp_apply)(
                    p["mlp"], norm_apply("rms", p["ln2"], x), ctx1, cfg)
            return (x + mlp, pool), None

        (x, pool), _ = jax.lax.scan(
            body, (x, pool),
            (params["blocks"], jnp.arange(cfg.n_layers)))
        with jax.named_scope("head_sample"):
            return norm_apply("rms", params["ln_f"], x), pool

    return window


def make_prefill(cfg, ctx: ParallelCtx, scfg: ServeConfig):
    """Chunked prefill: (params, pool, ids, start, n_tok, bt, samp) ->
    (next_tokens, pool).

    ids (b, C) the next window of each prompt, right-padded
    (C = ``scfg.prefill_chunk``); start (b,) the absolute position of
    ids[:, 0]; n_tok (b,) valid tokens in the window (0 = inactive
    slot).  Writes every chunk position's K/V into the pages, attends
    each position against the pages written so far (the shared window
    trunk), and returns the token sampled after position
    ``start + n_tok - 1`` with RNG counter ``start + n_tok`` —
    meaningful only for slots whose chunk completes the prompt; the
    engine discards the rest.
    """
    window = _make_window_forward(cfg, ctx, scfg)

    def prefill(params, pool, ids, start, n_tok, bt, samp):
        cd = ctx.compute_dtype
        x, pool = window(params, pool, ids, start, n_tok, bt)
        with jax.named_scope("head_sample"):
            t = ids.shape[1]
            last = jnp.clip(n_tok - 1, 0, t - 1)
            xl = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
            head = params["embed"] if cfg.tie_embeddings else params["head"]
            logits = emb.lm_head_logits(head, xl.astype(cd), ctx)
            nxt = sampling.sample_tokens(
                logits, ctx, samp, start + n_tok,
                n_candidates=scfg.sample_candidates)
        return nxt.astype(jnp.int32), pool

    return prefill


def make_verify(cfg, ctx: ParallelCtx, scfg: ServeConfig):
    """Speculative verify: (params, pool, ids, start, n_tok, bt, samp)
    -> (target_tokens, pool) — ONE batched forward over a (b, k+1)
    window through the chunked-prefill machinery, sampling at EVERY
    position.

    ids[:, 0] is the sequence's pending last token (its K/V unwritten,
    exactly what a decode step would feed) and ids[:, 1:] the proposed
    draft tokens; start (b,) the absolute position of ids[:, 0]; n_tok
    (b,) = 1 + drafts (1 = a plain decode through the verify window).
    Row j of the output is the token the TARGET model generates at
    position ``start + j + 1`` — drawn with the non-speculative
    counter-RNG key ``(rid, start + j + 1)`` — so the engine's exact
    prefix-match acceptance reproduces the sequential stream
    bit-for-bit: row 0 IS the non-speculative next token, and row j is
    what the (j+1)-th sequential step would have produced given that
    all j fed drafts matched.  K/V of every fed position is written
    through the block table; rejected positions are rewound by
    ``PagedKVCache.truncate`` (page-granular) + length bookkeeping.
    """
    window = _make_window_forward(cfg, ctx, scfg)

    def verify(params, pool, ids, start, n_tok, bt, samp):
        cd = ctx.compute_dtype
        x, pool = window(params, pool, ids, start, n_tok, bt)
        with jax.named_scope("head_sample"):
            b, t = ids.shape
            head = params["embed"] if cfg.tie_embeddings else params["head"]
            logits = emb.lm_head_logits(head, x.astype(cd), ctx)  # (b,t,V/tp)
            pos = start[:, None] + jnp.arange(t)[None] + 1        # counters
            nxt = sampling.sample_window_tokens(
                logits, ctx, samp, pos,
                n_candidates=scfg.sample_candidates)
        return nxt.astype(jnp.int32), pool

    return verify


# ======================================================================
# execution substrates
# ======================================================================
class LocalExec:
    """Single-device execution: jitted step functions over the per-PE
    pool, a loopback CommQueue (LocalTransport, 1 PE) for the migration
    drain — the same ``put_nbi`` + one ``quiet()`` path the mesh runs,
    minus the wire."""

    def __init__(self, params, cfg, ctx, scfg: ServeConfig,
                 kv: PagedKVCache):
        self.params = params
        self.kv = kv
        self._prefill = jax.jit(make_prefill(cfg, ctx, scfg))
        self._decode = jax.jit(make_decode_step(cfg, ctx, scfg))
        self._verify = jax.jit(make_verify(cfg, ctx, scfg))
        self._team = ctx.tp_comm.team

    def init_pool(self):
        return self.kv.zeros()

    def prefill(self, pool, ids, start, n_tok, bt, samp):
        return self._prefill(self.params, pool, jnp.asarray(ids),
                             jnp.asarray(start), jnp.asarray(n_tok),
                             jnp.asarray(bt), samp)

    def decode(self, pool, tokens, pos, bt, lens, samp):
        return self._decode(self.params, pool, jnp.asarray(tokens),
                            jnp.asarray(pos), jnp.asarray(bt),
                            jnp.asarray(lens), samp)

    def verify(self, pool, ids, start, n_tok, bt, samp):
        return self._verify(self.params, pool, jnp.asarray(ids),
                            jnp.asarray(start), jnp.asarray(n_tok),
                            jnp.asarray(bt), samp)

    def set_params(self, params) -> None:
        """Swap the served weights (weight hot-swap flip): the jitted
        step functions take ``params`` as an explicit argument, so the
        next tick's forwards run the new generation with no re-trace."""
        self.params = params

    def migrate(self, pool, migrations):
        # whole-system view with one PE: state rows carry the PE axis
        state = {self.kv.handle.name: np.asarray(pool)[None]}
        q = CommQueue(self._team, state, transport=LocalTransport(1))
        out = self.kv.issue_migrations(q, state[self.kv.handle.name],
                                       migrations, system=True)
        return jnp.asarray(out[self.kv.handle.name][0])

    # pool-layout hooks for the disaggregated handoff (serve.disagg):
    # a page "row" here is the plain pool row; mesh substrates override
    # these to expose their (replica, tp) layout as (page, tp-shard)
    def read_pages(self, pool, pages):
        """Host copies of pool rows ``pages`` — the handoff payload."""
        return np.asarray(pool)[np.asarray(pages, np.int64)]

    def write_pages(self, pool, pages, rows):
        """Land handed-off ``rows`` at pool rows ``pages``."""
        return pool.at[jnp.asarray(np.asarray(pages, np.int64))].set(
            jnp.asarray(rows))


# ======================================================================
# the driver
# ======================================================================
class ServeEngine:
    """Continuous-batching driver: token-budgeted ticks (one decode
    token per decoding sequence + chunked prefill), FCFS admission,
    preempt-by-eviction, migration drain first."""

    def __init__(self, params, cfg, ctx: ParallelCtx, scfg: ServeConfig,
                 *, heap: Optional[SymmetricHeap] = None,
                 kv: Optional[PagedKVCache] = None, exec_=None,
                 proposer=None, my_pe: int = 0, role: str = "both"):
        if role not in ("both", "prefill", "decode"):
            raise ValueError(f"unknown engine role {role!r}")
        self.cfg, self.ctx, self.scfg = cfg, ctx, scfg
        # disaggregated cells (serve.disagg): a "prefill" engine stops
        # at the first token and parks the finished sequence on
        # ``handoff_ready``; a "decode" engine receives sequences via
        # ``adopt_request`` (it can still re-prefill its own preemption
        # victims — the counter-RNG sampler keeps streams identical
        # wherever a position is recomputed)
        self.role = role
        self.handoff_ready: list = []
        if kv is None:
            heap = heap or SymmetricHeap(
                (ctx.tp_axis,) if ctx.tp_size > 1 else ("data",))
            kv = PagedKVCache(
                heap, n_layers=cfg.n_layers,
                kv_heads=cfg.kv_per_rank(ctx.tp_size),
                head_dim=cfg.head_dim, n_pages=scfg.n_pages,
                page_tokens=scfg.page_tokens, dtype=scfg.kv_dtype)
        self.kv = kv
        self.slo = SLOPolicy(scfg.slo) if scfg.slo is not None else None
        self.sched = FCFSScheduler(kv, max_batch=scfg.max_batch,
                                   max_seq=scfg.max_seq, my_pe=my_pe,
                                   prefill_chunk=scfg.prefill_chunk,
                                   tick_tokens=scfg.tick_tokens,
                                   spec_k=scfg.spec_k, slo=self.slo)
        self.exec = exec_ or LocalExec(params, cfg, ctx, scfg, kv)
        self.proposer = proposer
        if scfg.spec_k > 0 and proposer is None:
            from . import spec                 # engine <-> spec cycle
            self.proposer = spec.make_proposer(scfg.draft)
        self.spec_stats = {"drafted": 0, "accepted": 0, "emitted": 0,
                           "verify_ticks": 0, "verify_seqs": 0}
        self.pool = self.exec.init_pool()
        self.finished: list = []
        self.shed: list = []             # deadline-shedded, never served
        # weight hot-swap (repro.ckpt.hotswap): the in-flight streamer
        # and its lifetime accounting
        self._swap = None
        self.swap_stats = {"generation": 0, "flips": 0, "swap_ticks": 0,
                           "swap_batches": 0, "swap_bytes": 0,
                           "swap_extra_quiets": 0}
        self.ticks = 0
        # inter-token gaps of decoding sequences (the serving ITL/TPOT
        # metric): a gap spans the full tick(s) between two of a
        # request's tokens, so a batch-mate's prefill stall lands here
        self.itl: list = []
        self._last_tok: dict = {}        # rid -> time of last token

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        # greedy requests ignore top_k (SamplingParams contract), so
        # the candidate bound only constrains sampled ones
        if req.sampling.temperature > 0 \
                and req.sampling.top_k > self.scfg.sample_candidates:
            raise ValueError(
                f"request {req.rid}: top_k {req.sampling.top_k} exceeds "
                f"the sampler's candidate bound "
                f"{self.scfg.sample_candidates} "
                f"(raise ServeConfig.sample_candidates)")
        self.sched.submit(req)

    def begin_hot_swap(self, new_params, *, chunk_rows: int = 4,
                       n_pe: Optional[int] = None, **kw) -> None:
        """Start streaming a new weight generation (zero-downtime swap,
        ``repro.ckpt.hotswap``): subsequent ticks each advance the
        stream by one put-with-signal batch, and the generation flips
        atomically at a tick boundary once everything has landed —
        serving never pauses and the swap queue never pays a global
        drain."""
        if self._swap is not None:
            raise RuntimeError("a weight hot-swap is already in flight")
        from repro.ckpt.hotswap import WeightStreamer
        if n_pe is None:
            n_pe = max(self.ctx.dp_size * self.ctx.tp_size, 1)
        self.swap_stats["generation"] += 1
        self._swap = WeightStreamer(
            new_params, n_pe=n_pe,
            generation=self.swap_stats["generation"],
            chunk_rows=chunk_rows, **kw)

    def swap_in_flight(self) -> bool:
        return self._swap is not None

    def _swap_step(self) -> None:
        """The per-tick hot-swap hook: one streaming step; on the flip
        tick the reassembled generation replaces the served weights
        BEFORE this tick's forwards, so every PE (and every cell
        sharing the streamer) switches on the same tick."""
        st = self._swap
        if not st.step():
            return
        self.exec.set_params(st.result())
        self.swap_stats["flips"] += st.stats["flips"]
        self.swap_stats["swap_ticks"] += st.stats["swap_ticks"]
        self.swap_stats["swap_batches"] += st.stats["batches"]
        self.swap_stats["swap_bytes"] += st.stats["bytes"]
        self.swap_stats["swap_extra_quiets"] += st.extra_global_drains()
        self._swap = None

    def tick(self, now: float = 0.0) -> None:
        """One engine tick: hot-swap stream step (when one is in
        flight) -> schedule -> migrate (one quiet) -> chunked prefill
        for every prefilling sequence's quota -> one decode token for
        every decoding sequence -> retire finished.  Each phase is a
        host span (``serve.trace``)."""
        self.ticks += 1
        with span("serve.tick", tick=self.ticks):
            if self._swap is not None:
                self._swap_step()
            with span("serve.schedule"):
                plan = self.sched.tick(now)
            # the decode batch is fixed once the plan is: sequences that
            # complete prefill this tick decode from the next one
            decoding = [r for r in self.sched.running
                        if not r.is_prefilling()]
            with span("serve.plan", waiting=len(self.sched.waiting),
                      decode_seqs=len(decoding),
                      prefill_seqs=len(plan.prefill),
                      prefill_tokens=sum(n for _, n in plan.prefill),
                      pages_free=self.kv.n_free()):
                for r in plan.shed:      # deadline drops: never served
                    self.shed.append(r)
                    self._last_tok.pop(r.rid, None)
                    if self.proposer is not None:
                        self.proposer.drop(r.rid)
                for r in plan.preempted:  # progress resets, gaps with it
                    self._last_tok.pop(r.rid, None)
                    if self.proposer is not None:
                        self.proposer.drop(r.rid)
            if plan.migrations:
                with span("serve.migrate", pages=len(plan.migrations)):
                    self.pool = self.exec.migrate(self.pool,
                                                  tuple(plan.migrations))
            if plan.prefill:
                self._chunk_prefill(plan.prefill, now)
            self._decode_tick(decoding, now)

    def _samp_state(self, reqs) -> dict:
        return sampling.batch_state(reqs, self.scfg.max_batch,
                                    self.scfg.sample_seed)

    def _chunk_prefill(self, assignments, now):
        """Feed every (req, n) chunk assignment through the prefill
        step.  A sequence whose chunk completes its prompt takes its
        first output token from the chunk."""
        B, C = self.scfg.max_batch, self.scfg.prefill_chunk
        with span("serve.prepare", step="prefill"):
            reqs = [r for r, _ in assignments]
            ids = np.zeros((B, C), np.int32)
            start = np.zeros((B,), np.int32)
            n_tok = np.zeros((B,), np.int32)
            for i, (r, n) in enumerate(assignments):
                ids[i, :n] = r.prompt[r.n_done:r.n_done + n]
                start[i] = r.n_done
                n_tok[i] = n
            bt = self.kv.block_table(
                [r.rid for r in reqs] + [None] * (B - len(reqs)),
                self.scfg.table_slots)
            samp = self._samp_state(reqs)
        with span("serve.dispatch", step="prefill",
                  tokens=sum(n for _, n in assignments)):
            toks, self.pool = self.exec.prefill(self.pool, ids, start,
                                                n_tok, bt, samp)
        with span("serve.wait", step="prefill"):
            toks = np.asarray(toks)
        with span("serve.retire", step="prefill"):
            for i, (r, n) in enumerate(assignments):
                self.sched.note_chunk(r, n, int(toks[i]), now)
                if not r.is_prefilling():
                    if self.role == "prefill" and not r.finished():
                        # prefill cell: this sequence's life here ends
                        # with its first token — park it for the page
                        # handoff (pages stay resident as the put-signal
                        # payload source until the decode cell
                        # acknowledges)
                        self.sched.release(r)
                        self.handoff_ready.append(r)
                        continue
                    self._last_tok[r.rid] = now
                    self._maybe_finish(r, now)

    def adopt_request(self, req: Request, pages, now: float = 0.0) -> None:
        """Decode-cell half of a disaggregated handoff: attach the
        landing pages (already filled by the producer's put-with-signal
        stream, drained by the router's ``signal_wait_until``) and enter
        the sequence into this cell's scheduler mid-life."""
        if self.role == "prefill":
            raise RuntimeError("a prefill cell cannot adopt sequences")
        self.kv.attach_seq(req.rid, pages)
        self.sched.adopt(req)
        # its first token was emitted on the producer cell; the next
        # inter-token gap is measured from adoption
        self._last_tok[req.rid] = now

    def _decode_tick(self, batch, now):
        """One decode token for every sequence of ``batch`` (the running
        sequences that had finished prefill when the tick was
        planned)."""
        if self.role == "prefill" or not batch:
            return
        if self.scfg.spec_k > 0:
            return self._spec_tick(batch, now)
        B = self.scfg.max_batch
        with span("serve.prepare", step="decode"):
            tokens = np.zeros((B,), np.int32)
            pos = np.zeros((B,), np.int32)
            lens = np.zeros((B,), np.int32)
            for i, r in enumerate(batch):
                tokens[i] = r.next_input()
                p = r.n_prompt + len(r.out) - 1
                pos[i] = p
                lens[i] = p + 1
            bt = self.kv.block_table(
                [r.rid for r in batch] + [None] * (B - len(batch)),
                self.scfg.table_slots)
            samp = self._samp_state(batch)
        with span("serve.dispatch", step="decode", tokens=len(batch)):
            toks, self.pool = self.exec.decode(self.pool, tokens, pos, bt,
                                               lens, samp)
        with span("serve.wait", step="decode"):
            toks = np.asarray(toks)
        with span("serve.retire", step="decode"):
            for i, r in enumerate(batch):
                self.sched.advance(r, int(toks[i]), now)
                prev = self._last_tok.get(r.rid)
                if prev is not None:
                    self.itl.append(now - prev)
                self._last_tok[r.rid] = now
                self._maybe_finish(r, now)

    def _spec_tick(self, batch, now):
        """Draft -> verify -> accept -> rewind, one batched verify
        forward for every decoding sequence.

        The proposer supplies up to ``draft_allowance(r)`` draft tokens
        per sequence (the scheduler already budgeted and paged them);
        ONE verify pass scores the pending token plus all drafts; then
        exact prefix matching against the target's own counter-RNG
        draws accepts ``m`` drafts and emits ``m + 1`` tokens — the
        Leviathan accept test collapses to exact matching here because
        the drafts are point proposals (one-hot draft distributions)
        and the target's draw at a position is a deterministic function
        of its counter key, which is what makes accepted streams
        BIT-IDENTICAL to non-speculative decoding on every backend.
        Rejected positions rewind: page-granular ``kv.truncate`` plus
        the length bookkeeping the scheduler already keeps."""
        B, K = self.scfg.max_batch, self.scfg.spec_k
        with span("serve.prepare", step="verify"):
            allow = [self.sched.draft_allowance(r) for r in batch]
            drafts = self.proposer.propose(batch, allow)
            ids = np.zeros((B, K + 1), np.int32)
            start = np.zeros((B,), np.int32)
            n_tok = np.zeros((B,), np.int32)
            for i, r in enumerate(batch):
                d = drafts[i][:allow[i]]
                drafts[i] = d
                ids[i, 0] = r.next_input()
                if d:
                    ids[i, 1:1 + len(d)] = d
                start[i] = r.n_prompt + len(r.out) - 1
                n_tok[i] = 1 + len(d)
            bt = self.kv.block_table(
                [r.rid for r in batch] + [None] * (B - len(batch)),
                self.scfg.table_slots)
            samp = self._samp_state(batch)
        with span("serve.dispatch", step="verify", tokens=int(n_tok.sum())):
            toks, self.pool = self.exec.verify(self.pool, ids, start,
                                               n_tok, bt, samp)
        with span("serve.wait", step="verify"):
            toks = np.asarray(toks)
        with span("serve.retire", step="verify"):
            self.spec_stats["verify_ticks"] += 1
            self.spec_stats["verify_seqs"] += len(batch)
            for i, r in enumerate(batch):
                d = drafts[i]
                m = 0
                while m < len(d) and int(toks[i, m]) == int(d[m]):
                    m += 1
                # the allowance already caps drafts at the output budget,
                # so emitting every accepted token can never overshoot
                emit = min(m + 1, r.max_new - len(r.out))
                self.spec_stats["drafted"] += len(d)
                self.spec_stats["accepted"] += m
                self.spec_stats["emitted"] += emit
                prev = self._last_tok.get(r.rid)
                for j in range(emit):
                    self.sched.advance(r, int(toks[i, j]), now)
                    if prev is not None:
                        # tokens of one verify pass arrive together: the
                        # first closes the inter-token gap, the rest are
                        # free (that IS the latency win)
                        self.itl.append(now - prev if j == 0 else 0.0)
                self._last_tok[r.rid] = now
                if r.finished():
                    self._maybe_finish(r, now)
                    continue
                if not d:
                    continue      # nothing speculative was written: the
                                  # allowance pages stay attached for the
                                  # next window (no alloc/free churn)
                # rewind: K/V is valid through the last ACCEPTED position
                # (the newest sampled token's K/V is written when it is fed
                # next tick, same as non-speculative decode)
                self.kv.truncate(r.rid, r.n_prompt + len(r.out) - 1)
                self.proposer.rewind(r.rid, r.n_prompt + len(r.out) - 1)

    def _maybe_finish(self, r, now):
        if not r.is_prefilling() and r.finished():
            self.sched.finish(r, now,
                              register_prefix=self.scfg.prefix_keep)
            self.finished.append(r)
            # a reused rid (fresh trace on a live engine) must not see
            # this request's last-token time as its previous gap
            self._last_tok.pop(r.rid, None)
            if self.proposer is not None:
                self.proposer.drop(r.rid)

    # ------------------------------------------------------------------
    def run(self, requests: Sequence[Request], *, clock: str = "wall",
            max_ticks: int = 100_000) -> list:
        """Replay an arrival trace to completion.  ``clock="wall"``
        admits by elapsed wall time (benchmarking); ``"tick"`` admits by
        tick count (deterministic, what the parity suites use)."""
        pending = sorted(requests, key=lambda r: r.t_arrive)
        t0 = time.monotonic()
        skipped = 0.0          # idle time fast-forwarded past
        for _ in range(max_ticks):
            now = (self.ticks if clock == "tick"
                   else time.monotonic() - t0 + skipped)
            while pending and pending[0].t_arrive <= now:
                self.submit(pending.pop(0))
            if not self.sched.has_work():
                if not pending:
                    if self._swap is None:
                        return self.finished
                    self.tick(now)       # drain the in-flight hot swap
                    continue
                if clock == "wall":      # fast-forward idle gaps
                    skipped += pending[0].t_arrive - now
                    now = time.monotonic() - t0 + skipped
                self.submit(pending.pop(0))
            self.tick(now)
        raise RuntimeError(f"serve loop did not converge in {max_ticks} "
                           f"ticks ({len(self.finished)} finished)")

    def reset_metrics(self) -> None:
        """Forget finished requests and counters (page/pool state
        stays).  Benchmarks warm the jit caches with a throwaway trace,
        reset, then measure a clean run on the SAME engine — so the
        measured rows reflect engine/scheduler structure, not XLA
        compile time."""
        self.finished.clear()
        self.shed.clear()
        self.ticks = 0
        self.itl.clear()
        self._last_tok.clear()
        for k in self.sched.stats:
            self.sched.stats[k] = 0
        for k in self.kv.stats:
            self.kv.stats[k] = 0
        for k in self.spec_stats:
            self.spec_stats[k] = 0
        for k in self.swap_stats:
            if k != "generation":        # generations keep counting up
                self.swap_stats[k] = 0
        if self.slo is not None:
            self.slo.reset()

    # ------------------------------------------------------------------
    def metrics(self) -> dict:
        """Throughput/latency summary over finished requests."""
        lat = np.array([r.t_finish - r.t_arrive for r in self.finished])
        ttft = np.array([r.t_first - r.t_arrive for r in self.finished
                         if r.t_first is not None])
        # decode latency = inter-token gaps (ITL/TPOT): the per-token
        # quantity chunked prefill protects — a batch-mate's monolithic
        # prompt admission stretches the tick every decoding neighbour
        # waits on, and that stretch lands in these gaps
        dec = np.asarray(self.itl)
        toks = sum(len(r.out) for r in self.finished)
        span = max((r.t_finish for r in self.finished), default=0.0) \
            - min((r.t_arrive for r in self.finished), default=0.0)
        pct = (lambda a, p: float(np.percentile(a, p)) if a.size else 0.0)
        sp = dict(self.spec_stats)
        sp["accept_rate"] = (sp["accepted"] / sp["drafted"]
                             if sp["drafted"] else 0.0)
        # tokens one sequence's verify pass emits (> 1 = speculation is
        # beating one-token-per-tick decode)
        sp["tokens_per_tick"] = (sp["emitted"] / sp["verify_seqs"]
                                 if sp["verify_seqs"] else 0.0)
        slo = slo_summary(self.finished, self.shed,
                          self.slo.stats if self.slo is not None else None)
        return {
            "requests": len(self.finished),
            "tokens_out": int(toks),
            "span_s": float(span),
            "throughput_tok_s": toks / span if span > 0 else 0.0,
            "latency_p50_s": pct(lat, 50), "latency_p99_s": pct(lat, 99),
            "ttft_p50_s": pct(ttft, 50), "ttft_p99_s": pct(ttft, 99),
            "decode_p50_s": pct(dec, 50), "decode_p99_s": pct(dec, 99),
            "ticks": self.ticks,
            "sched": dict(self.sched.stats),
            "kv": dict(self.kv.stats),
            "spec": sp,
            "slo": slo,
            "swap": dict(self.swap_stats),
        }


def slo_summary(finished, shed, policy_stats=None) -> dict:
    """Per-class SLO attainment and shed counts over a served trace.

    Attainment is TTFT against each request's own ``deadline``
    (requests without one count as attained — vacuously in-SLO); shed
    requests count against their class's shed bucket, never against
    attainment (they were refused, not served late)."""
    out: dict = {"attained": {}, "finished": {}, "shed": {}}
    for p in PRIORITIES:
        done = [r for r in finished if r.priority == p]
        ok = [r for r in done
              if r.deadline is None
              or (r.t_first is not None
                  and r.t_first - r.t_arrive <= r.deadline)]
        out["finished"][p] = len(done)
        out["attained"][p] = (len(ok) / len(done)) if done else 1.0
        out["shed"][p] = sum(1 for r in shed if r.priority == p)
    if policy_stats is not None:
        out["policy"] = dict(policy_stats)
    return out
