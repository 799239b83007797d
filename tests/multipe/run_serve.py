"""Serving on a real 8-PE mesh — subprocess worker.

Mesh (2, 4) = ("data", "model"): a 2-replica serving cell, each replica
tensor-parallel over 4 PEs.  Five checks:

  1. BACKEND PARITY — the same seeded request trace served with the
     engine's collectives routed through each registered communicator
     backend (xla / posh / pallas) produces IDENTICAL token streams,
     for GREEDY requests and for SAMPLED ones (temperature > 0,
     top-p < 1): the TP-aware two-phase sampler merges per-shard
     candidates with a deterministic tie-break and draws from
     counter-based per-(rid, position) RNG streams, so any divergence
     is a numerical bug in a backend's schedules.

  2. BATCH-COMPOSITION INVARIANCE — a sampled request served ALONE
     yields the same token stream as the same request packed into a
     full batch (the RNG stream is keyed by (rid, position), never by
     batch slot or tick).

  3. TP-ARGMAX TIE-BREAK — manufactured equal-logit ties spanning
     vocab shards resolve to the LOWEST global vocab index on every
     backend (regression: the old pmax-of-candidate-index merge picked
     the highest tied shard).

  4. PAGE MIGRATION — a KV page moves replica 0 -> replica 1 as ONE
     put_nbi round over the flattened ("data","model") team (one
     (src, dst) pair per TP rank: each rank's page shard moves to its
     counterpart) drained by one quiet(), through the REAL
     PermuteTransport.  Replica-distinct scribbles prove actual cross-
     PE data motion, not SPMD replication.

  5. PREFIX-RESUME VIA MIGRATION — request A finishes and registers its
     full prompt pages in the prefix index (owner: replica 0).  A
     second serving cell (my_pe = replica 1) admits an identical-prompt
     request as RESUMED: the scheduler tick plans page migrations, the
     engine drains them with one quiet(), and the request CHUNK-
     prefills only the uncovered suffix (>= 2 tokens per tick) — its
     token stream must equal the from-scratch stream.

  6. SPECULATIVE DECODING PARITY — the same traces served with
     spec_k=3 (n-gram self-draft verified through the (B, k+1) window,
     exact counter-RNG prefix acceptance) produce the IDENTICAL token
     streams as non-speculative serving, greedy AND sampled, on every
     backend; a replay-oracle run then pins the multi-accept path
     (accept-rate 1, > 1 token per sequence per verify pass) and the
     rejection/rewind path runs under an adversarial proposer.

  7. ATTENTION-IMPL PARITY — the same traces served with
     attn_impl="kernel" (the Pallas paged decode + prefill-window grid
     kernels, interpret mode off-TPU) produce the IDENTICAL token
     streams as attn_impl="ref" on xla/posh/pallas, greedy and
     sampled, plus a spec_k run where the verify window itself runs
     the grid kernel.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat, configs, serve
from repro.core import SymmetricHeap
from repro.models import embed as emb
from repro.models import registry
from repro.parallel.ctx import ParallelCtx, smap

DP, TP = 2, 4
mesh = compat.make_mesh((DP, TP), ("data", "model"))


def build(backend, *, prefix_keep=False, my_pe=0, kv=None, scfg=None,
          spec_k=0, proposer=None):
    cfg = configs.get_smoke("qwen3-8b")
    ctx = ParallelCtx(dp_size=DP, tp_size=TP, sp=False, remat=False,
                      backend=backend, param_dtype=jnp.float32,
                      compute_dtype=jnp.float32)
    api = registry.build(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg,
                      ParallelCtx(dp_size=1, tp_size=1, sp=False,
                                  remat=False,
                                  param_dtype=jnp.float32,
                                  compute_dtype=jnp.float32))
    scfg = scfg or serve.ServeConfig(page_tokens=4, n_pages=24,
                                     max_batch=3, max_seq=32,
                                     prefill_chunk=3, attn_impl="ref",
                                     prefix_keep=prefix_keep,
                                     spec_k=spec_k)
    if kv is None:
        heap = SymmetricHeap(("data", "model"), capacity_bytes=1 << 30)
        kv = serve.PagedKVCache(
            heap, n_layers=cfg.n_layers,
            kv_heads=cfg.kv_per_rank(TP), head_dim=cfg.head_dim,
            n_pages=scfg.n_pages, page_tokens=scfg.page_tokens)
    exec_ = serve.MeshExec(params, api.specs(cfg, ctx), cfg, ctx, scfg, kv,
                           mesh, my_pe=my_pe)
    eng = serve.ServeEngine(params, cfg, ctx, scfg, kv=kv, exec_=exec_,
                            proposer=proposer, my_pe=my_pe)
    return eng, cfg


PROMPTS = [list(range(3, 11)), list(range(40, 46)), [7, 3, 99, 12, 55]]
SAMPLED = serve.SamplingParams(temperature=0.8, top_k=5, top_p=0.9)


def serve_trace(backend, sampling=None):
    eng, cfg = build(backend)
    reqs = [serve.Request(rid=i, prompt=list(p), max_new=6,
                          sampling=sampling or serve.GREEDY)
            for i, p in enumerate(PROMPTS)]
    done = eng.run(reqs, clock="tick")
    return {r.rid: list(r.out) for r in done}, eng


def check_backend_parity():
    for tag, sampling in (("greedy", None), ("sampled", SAMPLED)):
        streams = {}
        for backend in ("xla", "posh", "pallas"):
            streams[backend], _ = serve_trace(backend, sampling)
            print(f"  [{backend}/{tag}] streams: "
                  f"{ {k: v[:4] for k, v in streams[backend].items()} }")
        assert streams["xla"] == streams["posh"] == streams["pallas"], \
            (tag, streams)
        print(f"  {tag} token streams identical across xla/posh/pallas")


def check_batch_invariance():
    """The same sampled request, alone vs packed in a full batch, draws
    the identical token stream — on the mesh, through the TP sampler."""
    full, _ = serve_trace("xla", SAMPLED)
    eng, _ = build("xla")
    alone = eng.run([serve.Request(rid=1, prompt=list(PROMPTS[1]),
                                   max_new=6, sampling=SAMPLED)],
                    clock="tick")
    assert list(alone[0].out) == full[1], (alone[0].out, full[1])
    print(f"  sampled stream batch-composition-invariant "
          f"(rid 1: {full[1]})")


def check_tp_argmax_ties():
    """Manufactured equal-logit ties across vocab shards: every backend
    must resolve to the LOWEST global vocab index (the old merge used
    pmax over candidate indices, i.e. the HIGHEST tied shard won)."""
    V, vloc = 32, 32 // TP
    logits = np.zeros((2, V), np.float32)
    # row 0: the global max value 3.0 appears in shard 1 (idx 9) AND
    # shard 3 (idx 25) -> must pick 9.  row 1: tie inside shard 0
    # (idx 2, 5) AND shard 2 (idx 17) -> must pick 2.
    logits[0, 9] = logits[0, 25] = 3.0
    logits[1, 2] = logits[1, 5] = logits[1, 17] = 7.0
    for backend in ("xla", "posh", "pallas"):
        ctx = ParallelCtx(dp_size=DP, tp_size=TP, sp=False, remat=False,
                          backend=backend, param_dtype=jnp.float32,
                          compute_dtype=jnp.float32)

        def am(lg):
            return emb.tp_argmax(lg, ctx)

        out = jax.jit(smap(am, mesh, (P(None, "model"),), P()))(
            jnp.asarray(logits))
        got = list(np.asarray(out))
        assert got == [9, 2], (backend, got)
    print("  tp_argmax ties -> lowest global index on every backend")


def check_page_migration():
    """One put_nbi + one quiet() moves a page replica0 -> replica1 over
    the real permute transport; replica-distinct scribbles prove the
    bytes crossed PEs."""
    eng, cfg = build("xla")
    pool = np.asarray(eng.exec.init_pool())
    rng = np.random.RandomState(7)
    # distinct content per (replica, tp-rank): migration must copy
    # replica 0's shards, per rank, into replica 1
    pool = rng.randn(*pool.shape).astype(np.float32)
    src_page, dst_page = 3, 9
    before = pool.copy()
    out = np.asarray(eng.exec.migrate(
        jnp.asarray(pool),
        [serve.PageMigration(src_pe=0, dst_pe=1, src_page=src_page,
                             dst_page=dst_page)]))
    for t in range(TP):
        np.testing.assert_array_equal(out[1, t, dst_page],
                                      before[0, t, src_page])
    # sources and unrelated rows untouched
    np.testing.assert_array_equal(out[0], before[0])
    mask = np.ones(pool.shape[2], bool)
    mask[dst_page] = False
    np.testing.assert_array_equal(out[1][:, mask], before[1][:, mask])
    print("  page migration replica0 -> replica1 (put_nbi + 1 quiet) ok")


def check_prefix_resume_migration():
    """Scheduler-planned migration: an identical prompt re-served on
    replica 1 resumes from replica 0's registered prefix pages (moved
    by the tick's put_nbi/quiet) and CHUNK-prefills the uncovered
    suffix — >= 2 tokens per tick — to the same token stream."""
    prompt = list(range(3, 14))                # 2 full pages + 3 extra

    # from-scratch stream for this prompt
    eng0, _ = build("xla")
    scratch = eng0.run([serve.Request(rid=0, prompt=list(prompt),
                                      max_new=6)], clock="tick")
    want = list(scratch[0].out)

    # cell A (replica 0) serves and registers the prefix
    eng, cfg = build("xla", prefix_keep=True, my_pe=0)
    done = eng.run([serve.Request(rid=0, prompt=list(prompt),
                                  max_new=6)], clock="tick")
    assert list(done[0].out) == want
    assert eng.kv.lookup_prefix(prompt) is not None

    # cell B (replica 1) shares the symmetric pool + prefix index
    eng2, _ = build("xla", prefix_keep=False, my_pe=1, kv=eng.kv,
                    scfg=eng.scfg)
    eng2.pool = eng.pool                       # the shared heap state
    eng2.submit(serve.Request(rid=100, prompt=list(prompt), max_new=6))
    while eng2.sched.has_work():
        eng2.tick()
    (resumed,) = eng2.finished
    assert eng2.sched.stats["resumed"] == 1, eng2.sched.stats
    assert eng2.kv.stats["migrations"] >= 2    # 2 prefix pages moved
    # the uncovered suffix (3 tokens past the 2 migrated pages) went
    # through chunked prefill in >= 2-token chunks, not token-by-token
    assert resumed.prefill_chunks and max(resumed.prefill_chunks) >= 2, \
        resumed.prefill_chunks
    assert list(resumed.out) == want, (resumed.out, want)
    print(f"  prefix resume via migration ok "
          f"(migrated {eng2.kv.stats['migrations']} pages, suffix "
          f"chunks {resumed.prefill_chunks}, stream {resumed.out})")


def check_spec_parity():
    """Speculation is lossless on the mesh: spec_k=3 streams equal the
    non-speculative ones for greedy AND sampled traffic on every
    backend (the n-gram proposer drafts, the verify window scores, the
    counter-RNG prefix match accepts)."""
    for tag, sampling in (("greedy", None), ("sampled", SAMPLED)):
        want, _ = serve_trace("xla", sampling)   # == posh == pallas
        for backend in ("xla", "posh", "pallas"):
            eng, _ = build(backend, spec_k=3)
            done = eng.run(
                [serve.Request(rid=i, prompt=list(p), max_new=6,
                               sampling=sampling or serve.GREEDY)
                 for i, p in enumerate(PROMPTS)], clock="tick")
            got = {r.rid: list(r.out) for r in done}
            assert got == want, (backend, tag, got, want)
            assert eng.spec_stats["verify_ticks"] > 0
        print(f"  spec {tag} streams identical to non-spec across "
              f"xla/posh/pallas")


def check_spec_accept_and_rewind():
    """The two ends of the acceptance spectrum, on the real mesh: a
    replay oracle accepts every draft (multi-token verify emits), an
    adversarial proposer rejects every draft (page rewind), and both
    leave the streams untouched."""
    want, _ = serve_trace("xla")
    eng, _ = build("xla", spec_k=3,
                   proposer=serve.ReplayProposer(want))
    done = eng.run([serve.Request(rid=i, prompt=list(p), max_new=6)
                    for i, p in enumerate(PROMPTS)], clock="tick")
    assert {r.rid: list(r.out) for r in done} == want
    sp = eng.metrics()["spec"]
    assert sp["accept_rate"] == 1.0 and sp["tokens_per_tick"] > 1, sp
    eng2, _ = build("xla", spec_k=3,
                    proposer=serve.FixedProposer([101, 102, 103]))
    done2 = eng2.run([serve.Request(rid=i, prompt=list(p), max_new=6)
                      for i, p in enumerate(PROMPTS)], clock="tick")
    assert {r.rid: list(r.out) for r in done2} == want
    assert eng2.spec_stats["accepted"] == 0
    assert eng2.kv.stats["rewound_pages"] > 0
    print(f"  spec oracle accept-rate 1.0 "
          f"({sp['tokens_per_tick']:.2f} tok/seq/tick); adversarial "
          f"rewind {eng2.kv.stats['rewound_pages']} pages, streams "
          f"unchanged")


def _kernel_scfg(spec_k=0):
    return serve.ServeConfig(page_tokens=4, n_pages=24, max_batch=3,
                             max_seq=32, prefill_chunk=3,
                             attn_impl="kernel", spec_k=spec_k)


def check_attn_impl_parity():
    """attn_impl is a per-call impl choice, never a numerical one, on
    the real mesh too: kernel-served streams (Pallas paged decode +
    prefill-window grid kernels, interpret mode off-TPU) equal the ref
    streams on every backend, greedy AND sampled — and with spec_k=3
    the verify window itself runs the grid kernel to the same
    streams."""
    for tag, sampling in (("greedy", None), ("sampled", SAMPLED)):
        want, _ = serve_trace("xla", sampling)   # ref == posh == pallas
        for backend in ("xla", "posh", "pallas"):
            eng, _ = build(backend, scfg=_kernel_scfg())
            done = eng.run(
                [serve.Request(rid=i, prompt=list(p), max_new=6,
                               sampling=sampling or serve.GREEDY)
                 for i, p in enumerate(PROMPTS)], clock="tick")
            got = {r.rid: list(r.out) for r in done}
            assert got == want, (backend, tag, got, want)
        print(f"  attn kernel {tag} streams == ref streams across "
              f"xla/posh/pallas")
    want, _ = serve_trace("xla")
    eng, _ = build("xla", scfg=_kernel_scfg(spec_k=3))
    done = eng.run([serve.Request(rid=i, prompt=list(p), max_new=6)
                    for i, p in enumerate(PROMPTS)], clock="tick")
    assert {r.rid: list(r.out) for r in done} == want
    assert eng.spec_stats["verify_ticks"] > 0
    print("  attn kernel verify window (spec_k=3) streams unchanged")


def main():
    check_backend_parity()
    check_batch_invariance()
    check_tp_argmax_ties()
    check_page_migration()
    check_prefix_resume_migration()
    check_spec_parity()
    check_spec_accept_and_rewind()
    check_attn_impl_parity()
    print("SERVE_PASS")


if __name__ == "__main__":
    main()
