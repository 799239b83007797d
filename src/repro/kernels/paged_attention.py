"""Paged attention — K/V gathered through a block table.

The serving engine (``repro.serve``) keeps the KV cache as fixed-size
pages carved from the symmetric heap; a sequence's cache is a *block
table* of page ids, not a contiguous buffer.  The kernels here compute
attention directly against that layout — the gather happens in the
BlockSpec index map via scalar prefetch (the block table is available
before the kernel body runs, so the page id drives the HBM→VMEM DMA
itself; no gather materializes in HBM).

Two grid kernels share the same machinery:

  * ``paged_decode_attention`` — one decode step: the grid walks
    (sequence, table slot) and the KV block for slot ``j`` of sequence
    ``i`` is DMA'd from page ``block_table[i, j]``.
  * ``paged_prefill_attention`` — a whole prefill/verify WINDOW: the
    grid walks (sequence, q block, table slot), so one launch computes
    every window position's causal attention against the pages written
    so far.  This is the serving hot path's trunk — every
    chunked-prefill tick and every speculative-verify window runs it.

Online softmax runs exactly like the contiguous flash kernel
(``flash_attention._flash_kernel``): per-sequence running (m, l) and an
f32 accumulator live in VMEM scratch across table slots
(``_online_block_update`` below — the piece both kernels share), so a
paged sequence produces the same reduction tree as a contiguous one
with ``block_kv == page_tokens``.

Both take the engine's whole pool, ``(n_pages, 2, L, P, H_kv, D)``,
and a layer index: the index map picks ``(page, k|v, layer)``, a
contiguous ``(P, H_kv, D)`` page, so no caller slices a layer (or a K
or V half) out of the pool before the call.

GQA is handled by a static loop over KV heads (query rows grouped by
the KV head they read), matching the cache layout: pages store
``kv_per_rank`` heads, queries ``heads_per_rank``.

``choose_block(window, dtype)`` picks the prefill q-block rows from the
window length and the dtype's sublane tiling — the §4.5.4 compile-time
size dispatch, same philosophy as ``symm_copy.choose_variant``; the
ladder is cross-checked by ``benchmarks/attn_microbench.py``.

``interpret=None`` resolves from the platform like every other kernel
here: compiled on TPU, interpreter elsewhere (``ops.INTERPRET``).
``paged_decode_attention_ref`` / ``paged_prefill_attention_ref`` are
the jnp oracles (dense masked softmax over the gathered pages) used by
tests and as the fast CPU paths in the engine.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import symm_copy as _sc

NEG_INF = -1e30

# q-block ladder for choose_block: (window cap, f32 block rows) — small
# windows (a spec-verify (B, k+1) slab) take one minimal tile, larger
# chunked-prefill windows take wider blocks so the kv pipeline has more
# MXU work per DMA.  Rows round up to the dtype's sublane multiple.
_QBLOCK_LADDER = (
    (16, 8),       # ≤ 16-token windows: one minimal f32 tile
    (64, 16),      # chunked-prefill defaults
    (256, 32),     # long resume suffixes
)
_QBLOCK_TOP = 64


def choose_block(window: int, dtype=jnp.float32) -> int:
    """Size/dtype dispatch for the prefill-window q block (POSH §4.5.4:
    per-call compile-time selection).  Returns block rows that (a) meet
    the dtype's sublane multiple (f32 8, bf16 16, int8 32) and (b)
    never exceed the sublane-padded window — a 3-row verify window
    under f32 gets an 8-row block, not a 64-row one."""
    sub = _sc._SUBLANE.get(jnp.dtype(dtype).itemsize, 8)
    for cap, blk in _QBLOCK_LADDER:
        if window <= cap:
            break
    else:
        blk = _QBLOCK_TOP
    blk = -(-blk // sub) * sub                 # dtype sublane multiple
    padded = -(-max(window, 1) // sub) * sub   # window rounded up
    return min(blk, padded)


# ======================================================================
# shared machinery: scratch init / online-softmax update / finalize
# ======================================================================
def _init_scratch(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _online_block_update(s, valid, vh, rows, acc_ref, m_ref, l_ref):
    """One online-softmax accumulation step over a KV block for the
    scratch rows ``rows``: fold the masked score block ``s`` (NEG_INF
    where ``~valid``) and its values ``vh`` into the running
    (acc, m, l).  ``p`` is re-masked after the exp so rows with NO
    valid column yet (m still NEG_INF: exp(0) = 1) contribute exactly
    zero — the property that lets the window kernel zero padded rows
    without a separate pass."""
    m_prev = m_ref[rows, :]                    # (r, 128) lane-replicated
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
    p = jnp.exp(s - m_new[:, :1])
    p = jnp.where(valid, p, 0.0)
    l_new = alpha * l_ref[rows, :1] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[rows, :] = acc_ref[rows, :] * alpha + jax.lax.dot_general(
        p, vh, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[rows, :] = m_new
    l_ref[rows, :] = jnp.broadcast_to(l_new, l_ref[rows, :].shape)


def _normalized(acc_ref, l_ref, rows):
    denom = jnp.maximum(l_ref[rows, :1], 1e-30)
    return acc_ref[rows, :] / denom


def _page_spec(half, n_grid, n_slots, page_tokens, hkv, d):
    """The K (``half=0``) or V (``half=1``) block of one table slot: the
    ``(P, H_kv, D)`` page at ``(block_table[seq, slot], half, layer)``
    of the whole pool.  Both grids lead with the sequence and end with
    the table slot; the scalar-prefetch operands lead with the
    flattened block table and end with the layer."""
    def index(*args):
        ids, bt, layer = args[:n_grid], args[n_grid], args[-1]
        return (bt[ids[0] * n_slots + ids[-1]], half, layer[0], 0, 0, 0)
    return pl.BlockSpec((None, None, None, page_tokens, hkv, d), index)


def _layer_operand(layer) -> jax.Array:
    return jnp.asarray(layer, jnp.int32).reshape(1)


# ======================================================================
# decode kernel: one query per sequence, grid (sequence, table slot)
# ======================================================================
def _paged_kernel(bt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, sm_scale: float,
                  page_tokens: int, n_slots: int, hkv: int, group: int):
    i = pl.program_id(0)          # sequence
    j = pl.program_id(1)          # block-table slot

    @pl.when(j == 0)
    def _init():
        _init_scratch(acc_ref, m_ref, l_ref)

    length = len_ref[i]
    base = j * page_tokens

    @pl.when(base < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # (H, D)
        cols = base + jax.lax.broadcasted_iota(jnp.int32,
                                               (group, page_tokens), 1)
        valid = cols < length
        for h in range(hkv):                          # static GQA loop
            qh = q[h * group:(h + 1) * group]         # (g, D)
            kh = k_ref[:, h, :].astype(jnp.float32)   # (P, D)
            vh = v_ref[:, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(qh, kh, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s * sm_scale, NEG_INF)   # (g, P)
            rows = slice(h * group, (h + 1) * group)
            _online_block_update(s, valid, vh, rows, acc_ref, m_ref,
                                 l_ref)

    @pl.when(j == n_slots - 1)
    def _finalize():
        o_ref[0] = _normalized(acc_ref, l_ref,
                               slice(None)).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, kv_pool: jax.Array,
                           layer: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, *,
                           sm_scale: float | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """One decode step of attention through a block table.

    q:            (B, H, D) this step's queries
    kv_pool:      (n_pages, 2, L, P, H_kv, D) the page pool, K at
                  ``[:, 0]`` and V at ``[:, 1]`` (H % H_kv == 0)
    layer:        int32 scalar, the layer whose pages are read
    block_tables: (B, n_slots) int32 page ids (unused slots: any valid id)
    lengths:      (B,) int32 tokens valid per sequence (0 = inactive ->
                  zero output)

    Returns (B, H, D).  Token t of sequence b lives in page
    ``block_tables[b, t // P]`` at slot ``t % P``.
    """
    if interpret is None:
        interpret = _sc.default_interpret()
    b, h, d = q.shape
    page_tokens, hkv = kv_pool.shape[3:5]
    if h % hkv:
        raise ValueError(f"GQA requires H % H_kv == 0, got {h} % {hkv}")
    group = h // hkv
    n_slots = block_tables.shape[1]
    sm_scale = 1.0 / math.sqrt(d) if sm_scale is None else sm_scale

    kernel = functools.partial(
        _paged_kernel, sm_scale=sm_scale, page_tokens=page_tokens,
        n_slots=n_slots, hkv=hkv, group=group)

    bt_flat = block_tables.reshape(-1).astype(jnp.int32)
    lens = lengths.astype(jnp.int32)

    def q_map(i, j, bt, ln, li):
        return (i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_slots),
        in_specs=[
            pl.BlockSpec((1, h, d), q_map),
            _page_spec(0, 2, n_slots, page_tokens, hkv, d),
            _page_spec(1, 2, n_slots, page_tokens, hkv, d),
        ],
        out_specs=pl.BlockSpec((1, h, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(bt_flat, lens, _layer_operand(layer), q, kv_pool, kv_pool)


# ======================================================================
# prefill-window kernel: grid (sequence, q block, table slot)
# ======================================================================
def _prefill_kernel(bt_ref, start_ref, ntok_ref, layer_ref, q_ref, k_ref,
                    v_ref, o_ref, acc_ref, m_ref, l_ref, *, sm_scale: float,
                    page_tokens: int, n_slots: int, block_q: int,
                    hkv: int, group: int, head_dim: int):
    i = pl.program_id(0)          # sequence
    qi = pl.program_id(1)         # q block inside the window
    jk = pl.program_id(2)         # block-table slot

    @pl.when(jk == 0)
    def _init():
        _init_scratch(acc_ref, m_ref, l_ref)

    start = start_ref[i]
    ntok = ntok_ref[i]
    q_base = qi * block_q
    kv_base = jk * page_tokens

    # Block relevance: the q block must hold >= 1 valid window row, and
    # the KV page must not start past the LAST valid row's absolute
    # position (causality trims the kv walk per q block, the paged
    # analogue of the flash kernel's block-level causal skip).
    last_pos = start + jnp.minimum(ntok, q_base + block_q) - 1
    relevant = (q_base < ntok) & (kv_base <= last_pos)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (block_q, H, D)
        r = block_q * group
        # score row r -> window row j = q_base + r // group; position
        # start + j attends to cols <= start + j of the paged sequence
        jrow = q_base + jax.lax.broadcasted_iota(
            jnp.int32, (r, page_tokens), 0) // group
        cols = kv_base + jax.lax.broadcasted_iota(
            jnp.int32, (r, page_tokens), 1)
        valid = (cols <= start + jrow) & (jrow < ntok)
        for h in range(hkv):                      # static GQA loop
            qh = q[:, h * group:(h + 1) * group, :].reshape(r, head_dim)
            kh = k_ref[:, h, :].astype(jnp.float32)   # (P, D)
            vh = v_ref[:, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(qh, kh, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s * sm_scale, NEG_INF)  # (r, P)
            rows = slice(h * r, (h + 1) * r)
            _online_block_update(s, valid, vh, rows, acc_ref, m_ref,
                                 l_ref)

    @pl.when(jk == n_slots - 1)
    def _finalize():
        r = block_q * group
        for h in range(hkv):
            rows = slice(h * r, (h + 1) * r)
            out = _normalized(acc_ref, l_ref, rows)      # (r, D)
            o_ref[0, :, h * group:(h + 1) * group, :] = out.reshape(
                block_q, group, head_dim).astype(o_ref.dtype)


def paged_prefill_attention(q: jax.Array, kv_pool: jax.Array,
                            layer: jax.Array, block_tables: jax.Array,
                            start: jax.Array, n_tok: jax.Array, *,
                            sm_scale: float | None = None,
                            block_q: int | None = None,
                            interpret: bool | None = None) -> jax.Array:
    """Chunk-window prefill/verify attention through the block table —
    the Pallas grid kernel behind ``ops.paged_prefill_attention
    (impl="kernel")``.

    q:            (B, C, H, D) one prefill CHUNK (or spec-verify
                  window) of queries; row j of sequence b sits at
                  absolute position ``start[b] + j``
    kv_pool:      (n_pages, 2, L, P, H_kv, D) the page pool
    layer:        int32 scalar, the layer whose pages are read
    block_tables: (B, n_slots) int32 page ids (null-padded past the
                  live pages)
    start:        (B,) absolute position of q[:, 0]
    n_tok:        (B,) valid rows per window (0 = inactive -> zeros);
                  rows ``j >= n_tok`` produce exactly zero output

    Returns (B, C, H, D).  Row j attends to the first
    ``start[b] + j + 1`` paged tokens (the chunk's K/V must already be
    scattered into the pages) — numerically the per-position reduction
    of ``paged_decode_attention``, which is what keeps verify-path
    token streams bit-identical to sequential decode.

    ``block_q=None`` resolves via ``choose_block`` (size/dtype
    dispatch); windows are padded to a block multiple and sliced back,
    so block sizes that don't divide the window are fine.
    """
    if interpret is None:
        interpret = _sc.default_interpret()
    b, c, h, d = q.shape
    page_tokens, hkv = kv_pool.shape[3:5]
    if h % hkv:
        raise ValueError(f"GQA requires H % H_kv == 0, got {h} % {hkv}")
    group = h // hkv
    n_slots = block_tables.shape[1]
    sm_scale = 1.0 / math.sqrt(d) if sm_scale is None else sm_scale
    if block_q is None:
        block_q = choose_block(c, q.dtype)
    cp = -(-c // block_q) * block_q
    qp = jnp.pad(q, ((0, 0), (0, cp - c), (0, 0), (0, 0)))
    n_q = cp // block_q

    kernel = functools.partial(
        _prefill_kernel, sm_scale=sm_scale, page_tokens=page_tokens,
        n_slots=n_slots, block_q=block_q, hkv=hkv, group=group,
        head_dim=d)

    bt_flat = block_tables.reshape(-1).astype(jnp.int32)
    starts = start.astype(jnp.int32)
    ntoks = n_tok.astype(jnp.int32)

    def q_map(i, qi, jk, bt, st, nt, li):
        return (i, qi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, n_q, n_slots),
        in_specs=[
            pl.BlockSpec((1, block_q, h, d), q_map),
            _page_spec(0, 3, n_slots, page_tokens, hkv, d),
            _page_spec(1, 3, n_slots, page_tokens, hkv, d),
        ],
        out_specs=pl.BlockSpec((1, block_q, h, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((block_q * h, d), jnp.float32),
            pltpu.VMEM((block_q * h, 128), jnp.float32),
            pltpu.VMEM((block_q * h, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, cp, h, d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(bt_flat, starts, ntoks, _layer_operand(layer), qp, kv_pool, kv_pool)
    return out[:, :c]


# ======================================================================
# jnp oracles
# ======================================================================
def _gather_pages(kv_pool, layer, block_tables):
    """K and V of the named pages at ``layer``, each (B, S, H_kv, D):
    only the pages the tables list, never a whole pool half."""
    b = block_tables.shape[0]
    _, _, _, page_tokens, hkv, d = kv_pool.shape
    s_max = block_tables.shape[1] * page_tokens
    return (kv_pool[block_tables, 0, layer].reshape(b, s_max, hkv, d),
            kv_pool[block_tables, 1, layer].reshape(b, s_max, hkv, d))


def paged_prefill_attention_ref(q, kv_pool, layer, block_tables,
                                start, n_tok, *,
                                sm_scale: float | None = None):
    """Chunk-window prefill attention through the block table.

    q: (B, C, H, D) — the queries of one prefill CHUNK, where row j of
    sequence b sits at absolute position ``start[b] + j``; it attends
    to the first ``start[b] + j + 1`` tokens of its sequence's pages
    (the paged analogue of the causal mask, assuming the chunk's K/V
    have already been scattered into the pages).  Rows ``j >= n_tok``
    (the right-padding of a short chunk) produce exactly zero output.

    One gather + one masked softmax for the whole window — the fused
    form of C ``paged_decode_attention_ref`` calls (same mask, same
    scale, same f32 math), so chunked prefill costs one einsum per
    layer instead of C unrolled attention graphs.  The jnp oracle for
    ``paged_prefill_attention`` (the grid kernel above) and the fast
    CPU path in the engine.
    """
    b, c, h, d = q.shape
    kc, vc = _gather_pages(kv_pool, layer, block_tables)
    s_max, hkv = kc.shape[1:3]
    group = h // hkv
    sm_scale = 1.0 / math.sqrt(d) if sm_scale is None else sm_scale

    qg = q.reshape(b, c, hkv, group, d).astype(jnp.float32)
    sc = jnp.einsum("bchgd,bshd->bchgs", qg, kc.astype(jnp.float32),
                    preferred_element_type=jnp.float32) * sm_scale
    pos = start[:, None] + jnp.arange(c)[None]             # (B, C)
    lens = jnp.where(jnp.arange(c)[None] < n_tok[:, None], pos + 1, 0)
    valid = jnp.arange(s_max)[None, None] < lens[:, :, None]
    vmask = valid[:, :, None, None, :]                     # (B,C,1,1,S)
    sc = jnp.where(vmask, sc, NEG_INF)
    m = sc.max(-1)
    p = jnp.exp(sc - m[..., None])
    p = jnp.where(vmask, p, 0.0)
    l = p.sum(-1)
    acc = jnp.einsum("bchgs,bshd->bchgd", p, vc.astype(jnp.float32))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, c, h, d).astype(q.dtype)


def paged_decode_attention_ref(q, kv_pool, layer, block_tables, lengths,
                               *, sm_scale: float | None = None):
    """jnp oracle: gather the pages, dense masked softmax in f32.
    Mathematically identical to the kernel (same mask, same scale);
    the fast path off-TPU."""
    b, h, d = q.shape
    kc, vc = _gather_pages(kv_pool, layer, block_tables)
    s_max, hkv = kc.shape[1:3]
    group = h // hkv
    sm_scale = 1.0 / math.sqrt(d) if sm_scale is None else sm_scale

    qg = q.reshape(b, hkv, group, d).astype(jnp.float32)
    sc = jnp.einsum("bhgd,bshd->bhgs", qg, kc.astype(jnp.float32),
                    preferred_element_type=jnp.float32) * sm_scale
    valid = jnp.arange(s_max)[None, :] < lengths[:, None]      # (B, S)
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    m = sc.max(-1)
    p = jnp.exp(sc - m[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l = p.sum(-1)
    acc = jnp.einsum("bhgs,bshd->bhgd", p, vc.astype(jnp.float32))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, h, d).astype(q.dtype)
