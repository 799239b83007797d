"""repro.serve: paged KV cache allocator, FCFS scheduler, paged
attention parity, end-to-end engine vs the contiguous decode path, and
put_nbi/quiet page migration (LocalTransport oracle; the real-mesh run
is tests/multipe/run_serve.py)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, serve
from repro.core import CommQueue, LocalTransport, SymmetricHeap
from repro.kernels import ops
from repro.kernels.paged_attention import (paged_decode_attention,
                                           paged_decode_attention_ref,
                                           paged_prefill_attention,
                                           paged_prefill_attention_ref)
from repro.models import registry
from repro.parallel.ctx import ParallelCtx
from repro.serve import (NULL_PAGE, FCFSScheduler, PagedKVCache,
                         PageMigration, Request, ServeConfig, ServeEngine)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_kv(n_pages=8, page_tokens=4, n_layers=2, kv_heads=2, head_dim=4,
            heap=None):
    heap = heap or SymmetricHeap(("data",), capacity_bytes=1 << 24)
    return PagedKVCache(heap, n_layers=n_layers, kv_heads=kv_heads,
                        head_dim=head_dim, n_pages=n_pages,
                        page_tokens=page_tokens)


# ======================================================================
# allocator
# ======================================================================
def test_kv_pool_is_symmetric_heap_object():
    heap = SymmetricHeap(("data",), capacity_bytes=1 << 24)
    kv = make_kv(heap=heap)
    assert kv.handle.name in heap.registry
    assert heap.registry["kv_pages"].shape == (8, 2, 2, 4, 2, 4)
    # page id -> pool row: the symmetric address of page p is the pool
    # offset + p rows (Corollary 1 at page granularity)
    got, off = heap.resolve(kv.handle.offset)
    assert got.name == "kv_pages" and off == 0


def test_page_alloc_free_reuse():
    kv = make_kv(n_pages=6, page_tokens=4)     # 5 usable pages
    assert kv.n_free() == 5
    assert kv.alloc_seq("a", 6)                # 2 pages
    assert kv.alloc_seq("b", 9)                # 3 pages
    assert kv.n_free() == 0
    assert not kv.alloc_seq("c", 1)            # pool dry -> refused whole
    assert "c" not in kv.tables
    pages_a = list(kv.tables["a"])
    kv.free_seq("a")
    assert kv.n_free() == 2
    assert kv.alloc_seq("d", 5)                # 2 pages, LIFO reuse
    assert set(kv.tables["d"]) == set(pages_a)
    with pytest.raises(ValueError):
        kv.alloc_seq("b", 1)                   # double alloc


def test_ensure_grows_by_page():
    kv = make_kv(n_pages=4, page_tokens=4)     # 3 usable
    assert kv.alloc_seq("a", 3)                # 1 page covers 3 tokens
    assert len(kv.tables["a"]) == 1
    assert kv.ensure("a", 4)                   # still page 1
    assert len(kv.tables["a"]) == 1
    assert kv.ensure("a", 5)                   # boundary -> page 2
    assert len(kv.tables["a"]) == 2
    assert kv.ensure("a", 12)
    assert len(kv.tables["a"]) == 3
    assert not kv.ensure("a", 13)              # pool dry


def test_block_table_padding_and_null_page():
    kv = make_kv(n_pages=8, page_tokens=4)
    kv.alloc_seq("a", 7)
    bt = kv.block_table(["a", None], n_slots=4)
    assert bt.shape == (2, 4) and bt.dtype == np.int32
    assert list(bt[0][:2]) == kv.tables["a"]
    assert (bt[0][2:] == NULL_PAGE).all()
    assert (bt[1] == NULL_PAGE).all()
    assert NULL_PAGE not in kv.tables["a"]     # page 0 never handed out


def test_truncate_rewinds_across_page_boundary():
    """Speculative rewind: shrinking 10 -> 5 tokens over 4-token pages
    frees exactly the fully-rejected page(s); the partial final page
    stays; freed pages are immediately reusable (LIFO)."""
    kv = make_kv(n_pages=8, page_tokens=4)
    assert kv.alloc_seq("a", 10)               # 3 pages
    pages = list(kv.tables["a"])
    freed = kv.truncate("a", 5)                # 2 pages cover 5 tokens
    assert freed == 1
    assert kv.tables["a"] == pages[:2]
    assert kv.stats["rewound_pages"] == 1
    assert kv.n_free() == 7 - 2
    assert kv.alloc_seq("b", 1)
    assert kv.tables["b"] == [pages[2]]        # LIFO reuse of the freed page
    # exact page multiple: nothing to free
    assert kv.truncate("a", 8) == 0
    assert kv.tables["a"] == pages[:2]


def test_truncate_to_zero_frees_all_pages():
    kv = make_kv(n_pages=8, page_tokens=4)
    assert kv.alloc_seq("a", 9)                # 3 pages
    assert kv.truncate("a", 0) == 3
    assert kv.tables["a"] == []                # attached, but empty
    assert kv.n_free() == 7
    bt = kv.block_table(["a"], n_slots=3)
    assert (bt == NULL_PAGE).all()             # all-null row
    kv.free_seq("a")                           # still detachable
    assert kv.n_free() == 7


def test_truncate_never_touches_null_page():
    """The null page is never in a table, so no rewind can free it —
    even a rewind-to-zero across every sequence."""
    kv = make_kv(n_pages=6, page_tokens=4)
    kv.alloc_seq("a", 8)
    kv.alloc_seq("b", 12)
    for sid in ("a", "b"):
        kv.truncate(sid, 0)
    assert NULL_PAGE not in kv._free
    assert kv.n_free() == 5                    # pages 1..5 back, page 0 out
    kv.alloc_seq("c", 20)                      # reuse everything
    assert NULL_PAGE not in kv.tables["c"]


def test_pool_grow_via_realloc_preserves_pages():
    heap = SymmetricHeap(("data",), capacity_bytes=1 << 24)
    kv = make_kv(n_pages=4, heap=heap)
    pool = kv.zeros().at[1].set(7.0)
    pool = kv.grow(4, pool)
    assert kv.n_pages == 8 and pool.shape[0] == 8
    assert heap.registry["kv_pages"].shape[0] == 8
    np.testing.assert_allclose(np.asarray(pool[1]), 7.0)  # contents kept
    np.testing.assert_allclose(np.asarray(pool[5]), 0.0)
    assert kv.n_free() == 3 + 4


# ======================================================================
# scheduler
# ======================================================================
def mk_sched(n_pages=8, page_tokens=4, max_batch=4, max_seq=32, **kw):
    kv = make_kv(n_pages=n_pages, page_tokens=page_tokens)
    return FCFSScheduler(kv, max_batch=max_batch, max_seq=max_seq,
                         **kw), kv


def test_fcfs_admission_order_and_batch_cap():
    s, kv = mk_sched(n_pages=16, max_batch=2)
    reqs = [Request(rid=i, prompt=[1, 2, 3], max_new=4) for i in range(4)]
    for r in reqs:
        s.submit(r)
    plan = s.tick()
    assert [r.rid for r in plan.admitted] == [0, 1]    # FCFS, capped
    assert [r.rid for r in s.running] == [0, 1]
    s.finish(reqs[0])
    plan = s.tick()
    assert [r.rid for r in plan.admitted] == [2]       # next in line


def test_admission_blocks_on_pages_not_slots():
    s, kv = mk_sched(n_pages=4, page_tokens=4, max_batch=4)  # 3 usable
    s.submit(Request(rid=0, prompt=list(range(10)), max_new=2))  # 3 pages
    s.submit(Request(rid=1, prompt=[1], max_new=1))
    plan = s.tick()
    assert [r.rid for r in plan.admitted] == [0]
    assert s.waiting[0].rid == 1                       # blocked, waiting


def test_preempt_youngest_and_requeue_at_head():
    s, kv = mk_sched(n_pages=6, page_tokens=2, max_batch=3, max_seq=16)
    r0 = Request(rid=0, prompt=[1, 2, 3], max_new=6)   # 2 pages
    r1 = Request(rid=1, prompt=[4, 5, 6], max_new=6)   # 2 pages
    for r in (r0, r1):
        s.submit(r)
    s.tick()
    assert len(s.running) == 2 and kv.n_free() == 1
    # drive r0/r1 forward until a page is needed and the pool is dry
    s.note_prefilled(r0, 9)
    s.note_prefilled(r1, 9)
    s.advance(r0, 9)                                   # out: 2 tokens
    s.advance(r1, 9)
    plan = s.tick()   # r0 takes the last page; r1 (youngest) evicted
    assert [r.rid for r in plan.preempted] == [1]
    assert r1.out == [] and r1.n_done == 0             # progress reset
    assert s.waiting[0].rid == 1                       # head of the line
    assert r1.preemptions == 1
    assert [r.rid for r in s.running] == [0]


def test_no_spurious_preemption_on_final_token():
    """Page demand is exact: a sequence writing its last token at a
    page boundary must not evict a neighbour for a page it will never
    write."""
    s, kv = mk_sched(n_pages=5, page_tokens=2, max_batch=2, max_seq=16)
    r0 = Request(rid=0, prompt=[1, 2], max_new=3)
    r1 = Request(rid=1, prompt=[3, 4], max_new=3)
    for r in (r0, r1):
        s.submit(r)
    s.tick()
    assert len(s.running) == 2 and kv.n_free() == 0   # pool exactly full
    for r in (r0, r1):
        s.note_prefilled(r, 9)
    for _ in range(2):                # tokens 2 and 3: positions 2, 3
        plan = s.tick()
        assert plan.preempted == [], "evicted for an unwritten page"
        for r in (r0, r1):
            s.advance(r, 9)
    assert r0.finished() and r1.finished()


def test_tick_token_budget_chunk_cap_and_fcfs_split():
    """Fresh prompts split the tick budget FCFS, each capped at
    prefill_chunk."""
    s, kv = mk_sched(n_pages=32, page_tokens=4, max_batch=4, max_seq=64,
                     prefill_chunk=4, tick_tokens=6)
    s.submit(Request(rid=0, prompt=list(range(20)), max_new=2))
    s.submit(Request(rid=1, prompt=list(range(100, 120)), max_new=2))
    plan = s.tick()
    # 6 tokens: rid 0 gets a full chunk (4), rid 1 the remaining 2
    assert [(r.rid, n) for r, n in plan.prefill] == [(0, 4), (1, 2)]


def test_tick_token_budget_decode_claims_first():
    """Decoding sequences claim their token before any prefill chunk
    is granted — a long prompt can never starve running decodes — and
    the oldest prefilling sequence always makes >= 1 token progress."""
    s, kv = mk_sched(n_pages=32, page_tokens=4, max_batch=4, max_seq=64,
                     prefill_chunk=4, tick_tokens=5)
    shorts = [Request(rid=i, prompt=[i, i + 1], max_new=4)
              for i in (1, 2, 3)]
    for r in shorts:
        s.submit(r)
    plan = s.tick()                 # budget 5 over three 2-token prompts
    assert [(r.rid, n) for r, n in plan.prefill] == [(1, 2), (2, 2),
                                                     (3, 1)]
    for req, n in plan.prefill:
        s.note_chunk(req, n, 42)
    assert not shorts[0].is_prefilling() and not shorts[1].is_prefilling()
    assert shorts[2].is_prefilling()            # 1 of 2 tokens done
    long = Request(rid=9, prompt=list(range(20)), max_new=2)
    s.submit(long)
    plan = s.tick()
    # 2 decoding seqs claim 2 of the 5; rid 3 finishes its prompt (1),
    # the long newcomer gets what is left (2) — not a full chunk
    assert [(r.rid, n) for r, n in plan.prefill] == [(3, 1), (9, 2)]
    # starved budget: decode eats everything, yet the oldest prefilling
    # sequence is still guaranteed one token per tick
    for req, n in plan.prefill:
        s.note_chunk(req, n, 42)
    s.tick_tokens = 2
    plan = s.tick()
    assert [(r.rid, n) for r, n in plan.prefill] == [(9, 1)]


def test_chunked_prefill_tracks_chunks_and_budget():
    s, kv = mk_sched(n_pages=32, page_tokens=4, max_batch=2, max_seq=64,
                     prefill_chunk=3, tick_tokens=8)
    r = Request(rid=0, prompt=list(range(8)), max_new=2)
    s.submit(r)
    while r.is_prefilling():
        plan = s.tick()
        for req, n in plan.prefill:
            s.note_chunk(req, n, 42)
    assert r.prefill_chunks == [3, 3, 2]
    assert r.out == [42] and r.t_first is not None
    assert s.stats["prefill_tokens"] == 8


def test_preempted_request_eventually_completes():
    cfg = configs.get_smoke("qwen3-8b")
    ctx = ParallelCtx(dp_size=1, tp_size=1, sp=False, remat=False,
                      param_dtype=jnp.float32, compute_dtype=jnp.float32)
    api = registry.build(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg, ctx)
    tight = ServeConfig(page_tokens=4, n_pages=8, max_batch=3,
                        max_seq=32, max_prompt=16, attn_impl="ref")
    roomy = ServeConfig(page_tokens=4, n_pages=32, max_batch=3,
                        max_seq=32, max_prompt=16, attn_impl="ref")
    streams = {}
    for tag, scfg in (("tight", tight), ("roomy", roomy)):
        eng = ServeEngine(params, cfg, ctx, scfg)
        reqs = [Request(rid=i, prompt=list(range(2 + i, 10 + i)),
                        max_new=8) for i in range(3)]
        done = eng.run(reqs, clock="tick")
        assert len(done) == 3
        streams[tag] = {r.rid: r.out for r in done}
        if tag == "tight":
            assert eng.sched.stats["preempted"] > 0
    # eviction + re-prefill must not change any token stream
    assert streams["tight"] == streams["roomy"]


# ======================================================================
# paged attention parity (the tier-1 acceptance bar)
# ======================================================================
def _pool(kp, vp):
    """The engine's pool layout ``(n_pages, 2, L, P, H_kv, D)`` holding
    ``kp`` / ``vp`` as its one layer."""
    return jnp.stack([jnp.asarray(kp), jnp.asarray(vp)], axis=1)[:, :, None]


def _paged_case(seed=0, B=3, H=4, Hkv=2, D=16, P=4, n_pages=10, slots=3):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, H, D).astype(np.float32))
    kp = jnp.asarray(rng.randn(n_pages, P, Hkv, D).astype(np.float32))
    vp = jnp.asarray(rng.randn(n_pages, P, Hkv, D).astype(np.float32))
    bt = jnp.asarray(rng.permutation(np.arange(1, 10))
                     .reshape(B, slots).astype(np.int32))
    lens = jnp.asarray(np.array([P * slots, 5, 0], np.int32))
    return q, kp, vp, bt, lens


def test_paged_attention_kernel_matches_ref():
    q, kp, vp, bt, lens = _paged_case()
    pool = _pool(kp, vp)
    ref = paged_decode_attention_ref(q, pool, 0, bt, lens)
    ker = paged_decode_attention(q, pool, 0, bt, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)
    # inactive sequence (len 0) -> exactly zero output
    assert float(jnp.abs(ker[2]).max()) == 0.0


def test_paged_attention_matches_contiguous_ops_attention():
    """Gathering K/V through the block table must be numerically equal
    to contiguous ops.attention on the same sequences."""
    q, kp, vp, bt, lens = _paged_case()
    for impl in ("kernel", "ref"):
        out = ops.paged_attention(q, _pool(kp, vp), 0, bt, lens, impl=impl)
        for b in range(q.shape[0]):
            L = int(lens[b])
            if L == 0:
                continue
            kc = kp[bt[b]].reshape(-1, kp.shape[2], kp.shape[3])[:L]
            vc = vp[bt[b]].reshape(-1, vp.shape[2], vp.shape[3])[:L]
            # ops.attention wants (B, H, T, D) / (B, Hkv, S, D)
            ref = ops.attention(q[b][None, :, None, :],
                                kc[None].transpose(0, 2, 1, 3),
                                vc[None].transpose(0, 2, 1, 3),
                                causal=False)
            np.testing.assert_allclose(
                np.asarray(out[b]), np.asarray(ref[0, :, 0]),
                atol=1e-5, rtol=1e-5,
                err_msg=f"impl={impl} seq={b}")


def test_paged_attention_full_final_page():
    """Sequence lengths that are EXACT multiples of page_tokens (the
    final page completely full, no partial-page mask) — with the block
    table null-padded past the live pages, exactly the shape the engine
    hands the kernel at a page boundary."""
    rng = np.random.RandomState(3)
    B, H, Hkv, D, P, n_pages, slots = 3, 4, 2, 16, 4, 12, 6
    q = jnp.asarray(rng.randn(B, H, D).astype(np.float32))
    kp = jnp.asarray(rng.randn(n_pages, P, Hkv, D).astype(np.float32))
    vp = jnp.asarray(rng.randn(n_pages, P, Hkv, D).astype(np.float32))
    bt = np.zeros((B, slots), np.int32)          # null-padded
    bt[0, :2] = [1, 2]
    bt[1, :3] = [3, 4, 5]
    bt[2, :6] = [6, 7, 8, 9, 10, 11]
    bt = jnp.asarray(bt)
    lens = jnp.asarray([2 * P, 3 * P, 6 * P], np.int32)  # all full pages
    for impl in ("kernel", "ref"):
        out = ops.paged_attention(q, _pool(kp, vp), 0, bt, lens, impl=impl)
        for b in range(B):
            L = int(lens[b])
            kc = kp[bt[b]].reshape(-1, Hkv, D)[:L]
            vc = vp[bt[b]].reshape(-1, Hkv, D)[:L]
            ref = ops.attention(q[b][None, :, None, :],
                                kc[None].transpose(0, 2, 1, 3),
                                vc[None].transpose(0, 2, 1, 3),
                                causal=False)
            np.testing.assert_allclose(
                np.asarray(out[b]), np.asarray(ref[0, :, 0]),
                atol=1e-5, rtol=1e-5, err_msg=f"impl={impl} seq={b}")


def test_paged_attention_first_decode_after_midpage_prefill():
    """Decode position 0 of the OUTPUT right after a chunked prefill
    that ended mid-page: the query at position L attends to L+1 tokens
    where L+1 is NOT page-aligned (the partial final page holds both
    the prompt tail and this step's write)."""
    rng = np.random.RandomState(4)
    B, H, Hkv, D, P = 1, 4, 2, 16, 4
    q = jnp.asarray(rng.randn(B, H, D).astype(np.float32))
    kp = rng.randn(8, P, Hkv, D).astype(np.float32)
    vp = rng.randn(8, P, Hkv, D).astype(np.float32)
    bt = jnp.asarray([[1, 2, 0, 0]], jnp.int32)
    for L in (5, 6, 7):          # prompt ended mid-page at L-1
        lens = jnp.asarray([L + 1], np.int32)    # after this write
        for impl in ("kernel", "ref"):
            out = ops.paged_attention(q, _pool(kp, vp), 0, bt, lens,
                                      impl=impl)
            kc = kp[np.asarray(bt[0])].reshape(-1, Hkv, D)[:L + 1]
            vc = vp[np.asarray(bt[0])].reshape(-1, Hkv, D)[:L + 1]
            ref = ops.attention(q[0][None, :, None, :],
                                jnp.asarray(kc[None].transpose(0, 2, 1, 3)),
                                jnp.asarray(vc[None].transpose(0, 2, 1, 3)),
                                causal=False)
            np.testing.assert_allclose(
                np.asarray(out[0]), np.asarray(ref[0, :, 0]),
                atol=1e-5, rtol=1e-5, err_msg=f"impl={impl} L={L}")


def test_paged_prefill_window_matches_per_position_decode():
    """The fused chunk-window attention equals C per-position calls of
    the decode oracle (same mask, same scale) — including padded rows
    (zeros) and windows whose last position fills a page exactly."""
    rng = np.random.RandomState(5)
    B, C, H, Hkv, D, P, n_pages, slots = 3, 4, 4, 2, 16, 4, 10, 4
    q = jnp.asarray(rng.randn(B, C, H, D).astype(np.float32))
    kp = jnp.asarray(rng.randn(n_pages, P, Hkv, D).astype(np.float32))
    vp = jnp.asarray(rng.randn(n_pages, P, Hkv, D).astype(np.float32))
    bt = jnp.asarray([[1, 2, 0, 0], [3, 4, 5, 0], [6, 7, 8, 9]],
                     jnp.int32)
    start = jnp.asarray([0, 4, 2], jnp.int32)   # mid-page + page starts
    n_tok = jnp.asarray([4, 3, 0], np.int32)    # full, padded, inactive
    pool = _pool(kp, vp)
    out = ops.paged_prefill_attention(q, pool, 0, bt, start, n_tok)
    for b in range(B):
        for j in range(C):
            if j >= int(n_tok[b]):
                assert float(jnp.abs(out[b, j]).max()) == 0.0
                continue
            lens = np.zeros(B, np.int32)
            lens[b] = int(start[b]) + j + 1
            ref = paged_decode_attention_ref(q[:, j], pool, 0, bt,
                                             jnp.asarray(lens))
            np.testing.assert_allclose(
                np.asarray(out[b, j]), np.asarray(ref[b]),
                atol=1e-6, rtol=1e-6, err_msg=f"b={b} j={j}")


def test_paged_attention_gqa_and_mqa_groups():
    for H, Hkv in ((4, 1), (6, 2), (4, 4)):
        q, kp, vp, bt, lens = _paged_case(seed=H * 10 + Hkv, H=H,
                                          Hkv=Hkv)
        pool = _pool(kp, vp)
        ref = paged_decode_attention_ref(q, pool, 0, bt, lens)
        ker = paged_decode_attention(q, pool, 0, bt, lens, interpret=True)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   atol=1e-6, rtol=1e-6,
                                   err_msg=f"H={H} Hkv={Hkv}")


N_LAYERS = 4


@pytest.mark.parametrize("layer", [0, N_LAYERS // 2, N_LAYERS - 1],
                         ids=["first", "middle", "last"])
@pytest.mark.parametrize("H,Hkv", [(6, 2), (4, 1)], ids=["gqa", "mqa"])
@pytest.mark.parametrize("kind", ["decode", "window"])
def test_paged_kernels_read_the_layer_from_the_whole_pool(kind, H, Hkv,
                                                          layer):
    """Given the whole multi-layer pool and a layer index, each kernel
    gives, bit for bit, what it gives on the one-layer pool
    ``pool[:, :, layer:layer + 1]`` at layer 0, and matches its oracle;
    its answer depends on that layer's pages alone."""
    rng = np.random.RandomState(100 + 10 * H + Hkv)
    B, C, D, P, n_pages, slots = 3, 4, 16, 4, 10, 3
    pool = jnp.asarray(
        rng.randn(n_pages, 2, N_LAYERS, P, Hkv, D).astype(np.float32))
    bt = jnp.asarray(rng.permutation(np.arange(1, 10))
                     .reshape(B, slots).astype(np.int32))
    if kind == "decode":
        q = jnp.asarray(rng.randn(B, H, D).astype(np.float32))
        rest = (jnp.asarray([P * slots, 5, 0], jnp.int32),)
        kernel, oracle, tol = paged_decode_attention, \
            paged_decode_attention_ref, 1e-6
    else:
        q = jnp.asarray(rng.randn(B, C, H, D).astype(np.float32))
        rest = (jnp.asarray([0, 5, 8], jnp.int32),     # page start,
                jnp.asarray([4, 3, 0], jnp.int32))     # mid-page, idle
        kernel, oracle, tol = paged_prefill_attention, \
            paged_prefill_attention_ref, 1e-5
    got = kernel(q, pool, jnp.int32(layer), bt, *rest, interpret=True)
    one = kernel(q, pool[:, :, layer:layer + 1], jnp.int32(0), bt, *rest,
                 interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(one))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(oracle(q, pool, layer, bt, *rest)),
        atol=tol, rtol=tol)
    # the other layers' pages are never read
    other = pool.at[:, :, np.arange(N_LAYERS) != layer].set(jnp.nan)
    assert np.array_equal(
        np.asarray(kernel(q, other, jnp.int32(layer), bt, *rest,
                          interpret=True)), np.asarray(got))


# ======================================================================
# engine end-to-end vs the contiguous decode path
# ======================================================================
def test_engine_streams_match_contiguous_decode():
    cfg = configs.get_smoke("qwen3-8b")
    ctx = ParallelCtx(dp_size=1, tp_size=1, sp=False, remat=False,
                      param_dtype=jnp.float32, compute_dtype=jnp.float32)
    api = registry.build(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg, ctx)

    def ref_decode(prompt, max_new):
        state = api.init_decode_state(cfg, ctx, 1, max_len=32)
        step = jax.jit(lambda p, t, s: api.decode_step(p, t, s, ctx, cfg))
        tok = None
        for t in prompt:
            tok, state = step(params, jnp.asarray([t], jnp.int32), state)
        out = [int(tok[0])]
        for _ in range(max_new - 1):
            tok, state = step(params, tok, state)
            out.append(int(tok[0]))
        return out

    prompts = [list(range(3, 9)), list(range(4, 10)), [7, 3, 99, 12]]
    scfg = ServeConfig(page_tokens=4, n_pages=32, max_batch=3,
                       max_seq=32, max_prompt=16, attn_impl="kernel")
    eng = ServeEngine(params, cfg, ctx, scfg)
    reqs = [Request(rid=i, prompt=p, max_new=5)
            for i, p in enumerate(prompts)]
    done = sorted(eng.run(reqs, clock="tick"), key=lambda r: r.rid)
    for r in done:
        assert r.out == ref_decode(r.prompt, 5), f"req {r.rid}"


def test_engine_streams_invariant_to_prefill_chunking():
    """Chunked prefill is a scheduling choice, not a numerical one:
    any (prefill_chunk, tick_tokens) setting must produce the token
    streams of the monolithic whole-prompt run.  Covers chunks that end
    mid-page (prompt 6 over 4-token pages, chunk 3) and the first
    decode right after such a chunk."""
    cfg = configs.get_smoke("qwen3-8b")
    ctx = ParallelCtx(dp_size=1, tp_size=1, sp=False, remat=False,
                      param_dtype=jnp.float32, compute_dtype=jnp.float32)
    api = registry.build(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg, ctx)
    prompts = [list(range(3, 9)), list(range(4, 10)), [7, 3, 99, 12]]

    def run(chunk, tick_tokens=0):
        scfg = ServeConfig(page_tokens=4, n_pages=32, max_batch=3,
                           max_seq=32, prefill_chunk=chunk,
                           tick_tokens=tick_tokens, attn_impl="ref")
        eng = ServeEngine(params, cfg, ctx, scfg)
        done = eng.run([Request(rid=i, prompt=list(p), max_new=5)
                        for i, p in enumerate(prompts)], clock="tick")
        return {r.rid: list(r.out) for r in done}, \
            {r.rid: list(r.prefill_chunks) for r in done}

    mono, mono_chunks = run(chunk=16)
    assert mono_chunks[0] == [6]               # one whole-prompt chunk
    for chunk, tick_tokens in ((1, 0), (2, 0), (3, 0), (3, 4), (5, 7)):
        streams, chunks = run(chunk, tick_tokens)
        assert streams == mono, (chunk, tick_tokens, streams, mono)
        assert all(max(c) <= chunk for c in chunks.values())
    _, c3 = run(3)
    assert c3[0] == [3, 3]                     # mid-page chunk boundary


# ======================================================================
# page migration: put_nbi + one quiet() (LocalTransport oracle)
# ======================================================================
def test_page_migration_put_nbi_one_quiet():
    """Pages move between PEs as one-sided writes: N migrations issue N
    put_nbi and drain with exactly ONE quiet(); the destination PE's
    pool rows equal the source PE's pages afterwards."""
    heap = SymmetricHeap(("pe",), capacity_bytes=1 << 24)
    kv = make_kv(n_pages=8, heap=heap)
    n_pe = 2
    rng = np.random.RandomState(0)
    system = rng.randn(n_pe, *kv.handle.shape).astype(np.float32)
    state = {kv.handle.name: system.copy()}
    q = CommQueue("pe", state, transport=LocalTransport(n_pe))
    migs = [PageMigration(src_pe=0, dst_pe=1, src_page=3, dst_page=5),
            PageMigration(src_pe=0, dst_pe=1, src_page=4, dst_page=6)]
    out = kv.issue_migrations(q, state[kv.handle.name], migs,
                              system=True)
    st = q.stats()
    assert st["puts"] == 2 and st["quiets"] == 1
    got = np.asarray(out[kv.handle.name])
    np.testing.assert_array_equal(got[1, 5], system[0, 3])
    np.testing.assert_array_equal(got[1, 6], system[0, 4])
    # adjacent dst pages, same pair -> drain coalesced them into one
    # permute round (the ROADMAP item working for serving traffic)
    assert st["coalesced"] == 1
    # everything else untouched
    untouched = np.ones(8, bool)
    untouched[[5, 6]] = False
    np.testing.assert_array_equal(got[1][untouched], system[1][untouched])
    np.testing.assert_array_equal(got[0], system[0])


def test_local_prefix_hit_resumes_via_self_pair_copy():
    """A same-PE prefix hit reuses the pinned pages through the SAME
    put_nbi path with self-pairs (0-hop copy into fresh pages): the
    re-served prompt must produce the identical stream while the
    pinned originals stay registered — and the uncovered suffix
    prefills in >= 2-token chunks, not token-by-token."""
    cfg = configs.get_smoke("qwen3-8b")
    ctx = ParallelCtx(dp_size=1, tp_size=1, sp=False, remat=False,
                      param_dtype=jnp.float32, compute_dtype=jnp.float32)
    api = registry.build(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg, ctx)
    scfg = ServeConfig(page_tokens=4, n_pages=32, max_batch=2,
                       max_seq=32, prefill_chunk=4, attn_impl="ref",
                       prefix_keep=True)
    eng = ServeEngine(params, cfg, ctx, scfg)
    prompt = list(range(5, 16))                # 2 full pages + 3 extra
    first = eng.run([Request(rid=0, prompt=list(prompt), max_new=5)],
                    clock="tick")[0]
    assert eng.kv.pinned_pages == 2
    eng.submit(Request(rid=1, prompt=list(prompt), max_new=5))
    while eng.sched.has_work():
        eng.tick()
    resumed = next(r for r in eng.finished if r.rid == 1)
    assert eng.sched.stats["resumed"] == 1
    assert eng.kv.stats["migrations"] == 2     # 2 pages, self-pair copy
    # 8 of 11 prompt tokens arrived by migration; the 3-token suffix
    # went through chunked prefill in one >= 2-token chunk
    assert resumed.prefill_chunks and max(resumed.prefill_chunks) >= 2
    assert sum(resumed.prefill_chunks) == 3
    assert resumed.out == first.out
    assert eng.kv.lookup_prefix(prompt) is not None   # originals intact


def test_prefix_pin_budget_bounds_the_cache():
    """Pinning stops at the budget: the pool can never be starved by
    the prefix index (the cache is bounded, not a leak)."""
    kv = make_kv(n_pages=9, page_tokens=4)     # budget = 8 // 4 = 2
    assert kv.pin_budget == 2
    assert kv.alloc_seq("a", 8)
    assert kv.register_prefix(list(range(8)), 0, kv.tables["a"][:2])
    assert kv.pinned_pages == 2
    assert kv.alloc_seq("b", 8)
    assert not kv.register_prefix(list(range(20, 28)), 0,
                                  kv.tables["b"][:2])   # over budget
    assert kv.pinned_pages == 2


def test_prefix_cache_registration_and_lookup():
    kv = make_kv(n_pages=10, page_tokens=4)
    prompt = list(range(11))                   # 2 full pages + 3 tokens
    assert kv.alloc_seq("a", len(prompt) + 1)
    pages = kv.tables["a"]
    assert kv.register_prefix(prompt, owner_pe=0, pages=pages[:2])
    assert not kv.register_prefix(prompt, owner_pe=1, pages=pages[:2])
    owner, src = kv.lookup_prefix(prompt + [99, 98])   # longest prefix
    assert owner == 0 and src == pages[:2]
    assert kv.lookup_prefix([5, 5, 5, 5]) is None


# ======================================================================
# the 8-PE mesh suite (subprocess, like the other multipe workers)
# ======================================================================
def test_serve_mesh_8pe():
    if os.environ.get("REPRO_MULTIPE_EXPLICIT"):
        pytest.skip("multipe workers run explicitly (scripts/verify.sh)")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "tests", "multipe", "run_serve.py")],
        capture_output=True, text=True, env=env, timeout=2400)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "SERVE_PASS" in r.stdout


# ======================================================================
# attn_impl: end-to-end threading + ref/kernel stream identity
# ======================================================================
@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.get_smoke("qwen3-8b")
    ctx = ParallelCtx(dp_size=1, tp_size=1, sp=False, remat=False,
                      param_dtype=jnp.float32, compute_dtype=jnp.float32)
    params = registry.build(cfg).init(jax.random.PRNGKey(0), cfg, ctx)
    return params, cfg, ctx


def test_attn_impl_threads_through_all_three_call_sites(
        smoke_model, monkeypatch):
    """Regression: ServeConfig.attn_impl used to be silently dropped on
    the window trunk (engine hardcoded the ref for prefill AND verify).
    Spy on the ops layer and assert the CONFIGURED impl is what every
    call site — decode, prefill window, verify window — actually
    passes."""
    params, cfg, ctx = smoke_model
    calls = []
    real_window = ops.paged_prefill_attention
    real_decode = ops.paged_attention

    def spy_window(q, *a, **kw):
        calls.append(("window", int(q.shape[1]), kw.get("impl", "ref")))
        return real_window(q, *a, **kw)

    def spy_decode(q, *a, **kw):
        calls.append(("decode", 1, kw.get("impl", "kernel")))
        return real_decode(q, *a, **kw)

    monkeypatch.setattr(ops, "paged_prefill_attention", spy_window)
    monkeypatch.setattr(ops, "paged_attention", spy_decode)

    def run(spec_k):
        scfg = ServeConfig(page_tokens=4, n_pages=32, max_batch=2,
                           max_seq=32, prefill_chunk=4, spec_k=spec_k,
                           attn_impl="kernel")
        eng = ServeEngine(params, cfg, ctx, scfg)
        eng.run([Request(rid=0, prompt=[5, 17, 42] * 3, max_new=6)],
                clock="tick")

    run(spec_k=0)            # prefill window (C=4) + plain decode
    run(spec_k=2)            # + verify windows (C=spec_k+1=3)
    widths = {c for kind, c, _ in calls if kind == "window"}
    assert 4 in widths, "prefill window never traced"
    assert 3 in widths, "verify window never traced"
    assert any(kind == "decode" for kind, _, _ in calls)
    bad = [c for c in calls if c[2] != "kernel"]
    assert not bad, f"attn_impl not threaded: {bad}"


@pytest.mark.parametrize("spec_k", [0, 2])
def test_streams_bit_identical_across_attn_impl(smoke_model, spec_k):
    """The acceptance bar: attn_impl is a performance choice, never a
    numerical one — greedy AND sampled token streams, spec off and on,
    alone and batched, are bit-identical between ref and kernel."""
    params, cfg, ctx = smoke_model
    sp = serve.SamplingParams(temperature=0.9, top_k=5, top_p=0.9)

    def mixed_reqs():
        # greedy + sampled in ONE batch; prompts repeat so the n-gram
        # proposer earns accepts when spec is on
        return [Request(rid=0, prompt=[5, 17, 42] * 4, max_new=8),
                Request(rid=1, prompt=[5, 17, 42] * 3, max_new=8,
                        sampling=sp),
                Request(rid=2, prompt=[7, 3, 99, 12], max_new=8)]

    def alone_reqs():
        return [Request(rid=0, prompt=[5, 17, 42] * 3, max_new=8,
                        sampling=sp)]

    def run(attn_impl, mk):
        scfg = ServeConfig(page_tokens=4, n_pages=48, max_batch=3,
                           max_seq=48, spec_k=spec_k,
                           attn_impl=attn_impl)
        eng = ServeEngine(params, cfg, ctx, scfg)
        done = eng.run(mk(), clock="tick")
        return {r.rid: list(r.out) for r in done}, eng

    for mk in (mixed_reqs, alone_reqs):
        ref_streams, _ = run("ref", mk)
        ker_streams, eng = run("kernel", mk)
        assert ref_streams == ker_streams, (spec_k, mk.__name__)
        if spec_k:
            assert eng.spec_stats["drafted"] > 0
