"""repro.serve.disagg: disaggregated prefill/decode cells with
put-with-signal page handoff.

The acceptance bar: topology is a placement choice, never a numerical
one — token streams from a P+D cell split are bit-identical to the
colocated engine's (greedy AND sampled, speculation off and on), while
the handoff path drains ONLY through ``signal_wait_until`` (zero
tick-global quiets, pinned via ``CommQueue`` stats).  Plus the
cross-pool page export/adopt paths on ``PagedKVCache`` and the
least-loaded ``CellRouter``.  The real 8-PE mesh run is
``tests/multipe/run_disagg.py``.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, serve
from repro.core import SymmetricHeap
from repro.models import registry
from repro.parallel.ctx import ParallelCtx
from repro.serve import (CellRouter, DisaggEngine, PagedKVCache, Request,
                         ServeConfig, ServeEngine, make_cells)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_kv(n_pages=8, page_tokens=4, n_layers=2, kv_heads=2, head_dim=4):
    heap = SymmetricHeap(("data",), capacity_bytes=1 << 24)
    return PagedKVCache(heap, n_layers=n_layers, kv_heads=kv_heads,
                        head_dim=head_dim, n_pages=n_pages,
                        page_tokens=page_tokens)


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.get_smoke("qwen3-8b")
    ctx = ParallelCtx(dp_size=1, tp_size=1, sp=False, remat=False,
                      param_dtype=jnp.float32, compute_dtype=jnp.float32)
    params = registry.build(cfg).init(jax.random.PRNGKey(0), cfg, ctx)
    return params, cfg, ctx


# ======================================================================
# PagedKVCache: the cross-pool handoff paths
# ======================================================================
def test_export_seq_detaches_without_freeing():
    kv = make_kv()
    assert kv.alloc_seq("s", 7)              # 2 pages
    pages = list(kv.tables["s"])
    free_before = kv.n_free()
    exported = kv.export_seq("s")
    assert exported == pages
    assert "s" not in kv.tables
    # the pages are NOT back in the pool — they stay resident as the
    # handoff payload source until the consumer acknowledges
    assert kv.n_free() == free_before
    assert not set(exported) & set(kv._free)
    assert kv.stats["exported_pages"] == 2
    # ack: the producer returns them
    kv.release_pages(exported)
    assert kv.n_free() == free_before + 2


def test_adopt_seq_remaps_block_table_on_consumer():
    """The landing ids are the CONSUMER pool's own — a handoff remaps
    the block table, it never forwards producer page ids."""
    prod, cons = make_kv(), make_kv()
    # skew the consumer's free list so ids cannot accidentally match
    assert cons.alloc_seq("skew", 9)         # eats pages 7, 6, 5
    assert prod.alloc_seq("s", 7)
    src = prod.export_seq("s")
    dst = cons.adopt_seq("s", len(src))
    assert dst is not None and len(dst) == len(src)
    assert set(dst).isdisjoint(src)
    bt = cons.block_table(["s"], 4)
    assert list(bt[0, :2]) == dst and bt[0, 2] == 0
    assert cons.stats["adopted_pages"] == 2
    # all-or-nothing when the pool is dry
    assert cons.adopt_seq("t", 99) is None
    assert "t" not in cons.tables


def test_adopted_sequence_truncates_and_grows_like_native():
    """truncate (spec rewind) and ensure (decode growth) on an adopted
    table behave exactly as on a natively-allocated one — rewound tail
    pages return to the CONSUMER's free list."""
    prod, cons = make_kv(), make_kv()
    assert prod.alloc_seq("s", 12)           # 3 pages
    dst = cons.adopt_seq("s", len(prod.export_seq("s")))
    assert cons.ensure("s", 14)              # grow into page 4
    assert len(cons.tables["s"]) == 4
    freed = cons.truncate("s", 6)            # rewind to 2 pages
    assert freed == 2
    assert cons.tables["s"] == dst[:2]
    assert cons.stats["rewound_pages"] == 2
    assert set(cons._free) >= {dst[2]}


def test_exported_pages_stay_out_of_prefix_pin_circulation():
    """A handed-off sequence's pages cannot be prefix-pinned by the
    producer (export pops the table finish would pin from), and the
    consumer can pin the ADOPTED copy under its own budget."""
    prod, cons = make_kv(n_pages=16), make_kv(n_pages=16)
    prompt = list(range(8))                  # 2 full pages
    assert prod.alloc_seq("s", 9)
    src = prod.export_seq("s")
    with pytest.raises(KeyError):
        prod.tables["s"]                     # nothing left to pin
    dst = cons.adopt_seq("s", len(src))
    assert cons.register_prefix(prompt, 1, dst[:2])
    assert cons.lookup_prefix(prompt + [77]) == (1, dst[:2])
    assert cons.pinned_pages == 2
    prod.release_pages(src)
    assert prod.pinned_pages == 0


# ======================================================================
# topology: cells + router
# ======================================================================
def test_make_cells_carves_active_sets():
    cells = make_cells(2, 2, pes_per_cell=2)
    assert [c.role for c in cells] == ["prefill"] * 2 + ["decode"] * 2
    assert [c.pes for c in cells] == [(0, 1), (2, 3), (4, 5), (6, 7)]
    with pytest.raises(ValueError):
        make_cells(0, 2)


def test_router_least_loaded_admission(smoke_model):
    params, cfg, ctx = smoke_model
    scfg = ServeConfig(page_tokens=4, n_pages=32, max_batch=2, max_seq=32)
    eng = DisaggEngine(params, cfg, ctx, scfg, n_prefill=2, n_decode=1)
    r0 = Request(rid=0, prompt=list(range(3, 11)), max_new=2)
    r1 = Request(rid=1, prompt=[5, 6, 7], max_new=2)
    eng.submit(r0)                           # cell 0 (both empty, tie)
    assert r0 in eng.engines[0].sched.waiting
    eng.submit(r1)                           # cell 1 is now lighter
    assert r1 in eng.engines[1].sched.waiting
    router = eng.router
    assert router.prefill_load(0) == 8 and router.prefill_load(1) == 3


def test_router_handoff_backpressure(smoke_model):
    """route_handoff gates on live + INBOUND sequences per decode
    cell; a full topology defers (ticket stays with the producer)."""
    params, cfg, ctx = smoke_model
    scfg = ServeConfig(page_tokens=4, n_pages=32, max_batch=2, max_seq=32)
    eng = DisaggEngine(params, cfg, ctx, scfg, n_prefill=1, n_decode=2)
    router = eng.router
    req = Request(rid=9, prompt=[1, 2], max_new=2)
    assert router.route_handoff(req) == 1    # both empty -> lowest
    router.inbound[1] = 1
    assert router.route_handoff(req) == 2
    router.inbound[2] = 2                    # cell 2 full
    assert router.route_handoff(req) == 1
    router.inbound[1] = 2                    # everything full
    assert router.route_handoff(req) is None


# ======================================================================
# end-to-end: disagg == colocated, signals-only handoff drain
# ======================================================================
def _mixed_requests():
    sp = serve.SamplingParams(temperature=0.9, top_k=5, top_p=0.9)
    return [Request(rid=0, prompt=[5, 17, 42] * 4, max_new=8),
            Request(rid=1, prompt=[5, 17, 42] * 3, max_new=8,
                    sampling=sp),
            Request(rid=2, prompt=[7, 3, 99, 12], max_new=8, t_arrive=1),
            Request(rid=3, prompt=list(range(30, 39)), max_new=6,
                    sampling=sp, t_arrive=2),
            Request(rid=4, prompt=[11, 12], max_new=1, t_arrive=2)]


@pytest.mark.parametrize("spec_k", [0, 2])
@pytest.mark.parametrize("topology", [(1, 1), (2, 2)])
def test_disagg_streams_match_colocated(smoke_model, topology, spec_k):
    """The tentpole bar: P+D cell splits produce the colocated engine's
    exact token streams — greedy and sampled in one trace, speculation
    off and on — and the handoff path completes through
    ``signal_wait_until`` alone (zero quiets/fences on the mailbox
    queue)."""
    params, cfg, ctx = smoke_model
    n_prefill, n_decode = topology

    def scfg():
        return ServeConfig(page_tokens=4, n_pages=48, max_batch=3,
                           max_seq=48, spec_k=spec_k, attn_impl="ref")

    colo = ServeEngine(params, cfg, ctx, scfg())
    ref = {r.rid: list(r.out)
           for r in colo.run(_mixed_requests(), clock="tick")}
    eng = DisaggEngine(params, cfg, ctx, scfg(), n_prefill=n_prefill,
                       n_decode=n_decode)
    done = eng.run(_mixed_requests(), clock="tick")
    got = {r.rid: list(r.out) for r in done}
    assert got == ref, (topology, spec_k)
    hs = eng.stats()
    assert hs["handoff_quiets"] == 0
    assert hs["handoff_signals"] == hs["handoff_pages"] > 0
    assert hs["handoff_waits"] == hs["handoff_tickets"]
    # rid 4 (max_new=1) finishes AT prefill: no decode cell ever saw it
    assert hs["handoff_tickets"] == len(ref) - 1
    assert eng.hq.pending_ops() == 0


def test_handoff_frees_producer_pages_after_ack(smoke_model):
    """Conservation: after a full trace every cell's pool is whole
    again — producers freed their exported pages on ack, consumers
    freed the adopted tables on finish."""
    params, cfg, ctx = smoke_model
    scfg = ServeConfig(page_tokens=4, n_pages=32, max_batch=2, max_seq=32)
    eng = DisaggEngine(params, cfg, ctx, scfg, n_prefill=1, n_decode=1)
    done = eng.run(_mixed_requests(), clock="tick")
    assert len(done) == 5
    for e in eng.engines:
        assert e.kv.n_free() == e.kv.n_pages - 1 - e.kv.pinned_pages
        assert not e.kv.tables
    prod = eng.engines[0].kv
    assert prod.stats["exported_pages"] > 0
    assert prod.stats["page_frees"] >= prod.stats["exported_pages"]


@pytest.mark.parametrize("spec_k", [0, 2])
@pytest.mark.parametrize("topology", [(1, 1), (2, 2)])
def test_amo_router_streams_match_host(smoke_model, topology, spec_k):
    """PR-9 tentpole bar: ``--router amo`` (CAS admission rings +
    claim-word mailbox + symmetric page pools) produces the host
    router's exact token streams — greedy and sampled, speculation off
    and on — while the entire control plane drains without ONE
    tick-global quiet (router queue AND every cell's pool queue)."""
    params, cfg, ctx = smoke_model
    n_prefill, n_decode = topology

    def build(router):
        scfg = ServeConfig(page_tokens=4, n_pages=48, max_batch=3,
                           max_seq=48, spec_k=spec_k, attn_impl="ref")
        return DisaggEngine(params, cfg, ctx, scfg, n_prefill=n_prefill,
                            n_decode=n_decode, router=router)

    host = build("host")
    ref = {r.rid: list(r.out)
           for r in host.run(_mixed_requests(), clock="tick")}
    eng = build("amo")
    got = {r.rid: list(r.out)
           for r in eng.run(_mixed_requests(), clock="tick")}
    assert got == ref, (topology, spec_k)
    hs = eng.stats()
    assert hs["handoff_quiets"] == 0
    assert hs["router_quiets"] == 0          # router + pool queues
    assert hs["router_amos"] > 0 and hs["handoff_amos"] > 0
    assert hs["handoff_signals"] == hs["handoff_pages"] > 0
    assert hs["handoff_waits"] == hs["handoff_tickets"]
    for pool in eng.pools:
        qs = pool.queue_stats()
        assert qs["quiets"] == 0 and qs["fences"] == 0
        assert qs["amos"] > 0
    # host mode reports the amo counters as zeros (one stats schema)
    hh = host.stats()
    assert hh["router_amos"] == hh["router_quiets"] == 0
    assert hh["steals"] == hh["alloc_cas_retries"] == 0


def test_colocated_amo_pool_is_invisible(smoke_model):
    """``--router amo`` without cells attaches a SymmetricPagePool to
    the single engine's cache: identical page grants, identical
    streams, zero quiets on the pool queue."""
    params, cfg, ctx = smoke_model

    def scfg():
        return ServeConfig(page_tokens=4, n_pages=48, max_batch=3,
                           max_seq=48, attn_impl="ref")

    host = ServeEngine(params, cfg, ctx, scfg())
    ref = {r.rid: list(r.out)
           for r in host.run(_mixed_requests(), clock="tick")}
    eng = ServeEngine(params, cfg, ctx, scfg())
    eng.kv.attach_pool(serve.SymmetricPagePool(eng.kv.n_pages))
    got = {r.rid: list(r.out)
           for r in eng.run(_mixed_requests(), clock="tick")}
    assert got == ref
    qs = eng.kv._pool.queue_stats()
    assert qs["quiets"] == 0 and qs["fences"] == 0 and qs["amos"] > 0


def test_disagg_cli_spec_and_builder():
    from repro.launch.serve import build_engine, parse_disagg
    assert parse_disagg("2+2") == (2, 2)
    assert parse_disagg("1+3") == (1, 3)
    for bad in ("2", "0+2", "2+0", "a+b"):
        with pytest.raises(SystemExit):
            parse_disagg(bad)
    eng, cfg = build_engine("qwen3-8b", smoke=True, n_pages=32, max_batch=2,
                            disagg="1+1")
    assert isinstance(eng, DisaggEngine)
    assert [c.role for c in eng.cells] == ["prefill", "decode"]
    # --router wiring: amo builds the lock-free control plane
    eng, _ = build_engine("qwen3-8b", smoke=True, n_pages=32, max_batch=2,
                          disagg="1+1", router="amo")
    assert eng.router_mode == "amo"
    assert isinstance(eng.router, serve.AmoCellRouter)
    assert len(eng.pools) == len(eng.engines)
    eng, _ = build_engine("qwen3-8b", smoke=True, n_pages=32, max_batch=2,
                          router="amo")          # colocated: pool only
    assert isinstance(eng, ServeEngine)
    assert isinstance(eng.kv._pool, serve.SymmetricPagePool)
    with pytest.raises(SystemExit):
        build_engine("qwen3-8b", smoke=True, router="bogus")


# ======================================================================
# the 8-PE mesh suite (subprocess, like the other multipe workers)
# ======================================================================
def test_disagg_mesh_8pe():
    if os.environ.get("REPRO_MULTIPE_EXPLICIT"):
        pytest.skip("multipe workers run explicitly (scripts/verify.sh)")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "tests", "multipe", "run_disagg.py")],
        capture_output=True, text=True, env=env, timeout=2400)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "DISAGG_PASS" in r.stdout
