"""repro.serve — continuous-batching inference on the symmetric heap.

The first end-to-end serving workload on top of the framework's POSH
substrate: a paged KV cache whose pages are fixed-size blocks carved
from the ``SymmetricHeap`` (so block tables are plain offset arrays
valid on every PE — Fact 1 applied to serving), FCFS continuous
batching with preempt-by-eviction and TOKEN-BUDGETED CHUNKED PREFILL,
prefill/decode step functions that issue every collective through
``ctx.tp_comm`` (any registered backend: xla / posh / pallas), paged
decode attention via the Pallas block-table kernel, per-request
sampling (greedy / temperature / top-k / top-p) through the TP-aware
two-phase sampler with counter-based per-(rid, position) RNG streams,
cross-PE KV page migration as ``put_nbi`` one-sided writes drained
by one ``quiet()`` per scheduler tick, and LOSSLESS speculative
decoding (``serve.spec``): pluggable draft proposers verified through
a ``(B, k+1)`` prefill-machinery window with exact counter-RNG prefix
acceptance and page-granular rewind, so spec streams are bit-identical
to sequential decoding on every backend.  ``serve.disagg`` splits the
mesh into prefill/decode CELLS: finished prefills stream their pages
to a decode cell with ``put_signal_nbi`` (one signal word per handoff
ticket) and the consumer adopts on ``signal_wait_until`` — per-transfer
completion, zero tick-global quiets on the handoff path.

    from repro import serve
    eng = serve.ServeEngine(params, cfg, ctx, serve.ServeConfig())
    done = eng.run(serve.make_requests(serve.TrafficConfig()))
    eng.metrics()
"""
from .amo_router import AmoCellRouter
from .disagg import (CellRouter, CellSpec, DisaggEngine, HandoffTicket,
                     make_cells)
from .engine import LocalExec, ServeConfig, ServeEngine, make_decode_step, \
    make_prefill, make_verify
from .kv_cache import NULL_PAGE, PagedKVCache, PageMigration
from .mesh_exec import MeshExec, init_sharded_params
from .page_pool import SymmetricPagePool
from .sampling import (GREEDY, SamplingParams, batch_state,
                       sample_from_candidates, sample_tokens,
                       sample_window_tokens)
from .scheduler import FCFSScheduler, Request, TickPlan
from .slo import PRIORITIES, SLOConfig, SLOPolicy
from .spec import (DraftModelProposer, FixedProposer, NgramProposer,
                   ReplayProposer, SpecProposer, make_proposer)
from .traffic import TrafficConfig, make_requests

__all__ = [
    "ServeConfig", "ServeEngine", "LocalExec", "MeshExec",
    "init_sharded_params",
    "DisaggEngine", "CellRouter", "AmoCellRouter", "CellSpec",
    "HandoffTicket", "make_cells",
    "make_decode_step", "make_prefill", "make_verify",
    "PagedKVCache", "PageMigration", "NULL_PAGE", "SymmetricPagePool",
    "FCFSScheduler", "Request", "TickPlan",
    "SLOConfig", "SLOPolicy", "PRIORITIES",
    "TrafficConfig", "make_requests",
    "SamplingParams", "GREEDY", "batch_state",
    "sample_from_candidates", "sample_tokens", "sample_window_tokens",
    "SpecProposer", "NgramProposer", "DraftModelProposer",
    "ReplayProposer", "FixedProposer", "make_proposer",
]
