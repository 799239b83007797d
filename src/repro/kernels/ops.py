"""jit'd public wrappers over the Pallas kernels.

``INTERPRET = None`` (the default) resolves per call from the actual
platform — compiled kernels on TPU, the interpreter everywhere else
(``symm_copy.default_interpret``).  Deployments can still pin it either
way at startup (trace-time constant — POSH's compile-time selection,
once more).
"""
from __future__ import annotations

import functools

import jax

from . import flash_attention as _fa
from . import paged_attention as _pa
from . import reduce_combine as _rc
from . import symm_copy as _sc

INTERPRET: bool | None = None   # None -> platform default (TPU: compiled)


def _interpret() -> bool:
    return _sc.default_interpret() if INTERPRET is None else INTERPRET


@functools.partial(jax.jit, static_argnames=("variant",))
def symm_copy(x, variant: str = _sc.DEFAULT_VARIANT):
    """The copy engine: ``variant`` may be a VMEM block name, "stock"
    (bare XLA copy) or "auto" (size/dtype dispatch)."""
    return _sc.copy(x, variant, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("op", "variant"))
def combine(a, b, op: str = "sum", variant: str = _rc.DEFAULT_VARIANT):
    return _rc.combine_blocked(a, b, op, variant, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("causal", "window", "sm_scale",
                                             "block_q", "block_kv"))
def attention(q, k, v, causal: bool = True, window: int | None = None,
              sm_scale: float | None = None, block_q: int = 128,
              block_kv: int = 128):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               sm_scale=sm_scale, block_q=block_q,
                               block_kv=block_kv, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("sm_scale", "impl"))
def paged_attention(q, kv_pool, layer, block_tables, lengths,
                    sm_scale: float | None = None, impl: str = "kernel"):
    """Paged decode attention (serving hot path): K/V gathered through a
    block table of symmetric-heap pages, straight from the whole pool
    ``(n_pages, 2, L, P, H_kv, D)`` at ``layer``.  ``impl="kernel"``
    runs the Pallas kernel (compiled on TPU, interpret elsewhere);
    ``"ref"`` the jnp oracle — numerically interchangeable (tier-1
    parity test)."""
    if impl == "ref":
        return _pa.paged_decode_attention_ref(q, kv_pool, layer,
                                              block_tables, lengths,
                                              sm_scale=sm_scale)
    if impl != "kernel":
        raise ValueError(
            f"paged_attention impl='{impl}' "
            f"(choose from {PAGED_ATTN_IMPLS})")
    return _pa.paged_decode_attention(q, kv_pool, layer, block_tables,
                                      lengths, sm_scale=sm_scale,
                                      interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("sm_scale", "impl", "block_q"))
def paged_prefill_attention(q, kv_pool, layer, block_tables, start,
                            n_tok, sm_scale: float | None = None,
                            impl: str = "ref", block_q: int | None = None):
    """Chunk-window attention through a block table: query row ``j`` of
    sequence ``b`` (absolute position ``start[b] + j``) attends to its
    first ``start[b]+j+1`` paged tokens of ``layer`` in the whole pool;
    padded rows (``j >= n_tok``) return zeros.  This is BOTH the
    chunked-prefill window and the speculative-decode verify window (a
    ``(B, k+1)`` window of pending token + drafts —
    ``serve.make_verify``): numerically the same
    per-position reduction as ``paged_attention(impl="ref")``, which is
    what lets verify-path token streams match sequential decoding.

    ``impl="kernel"`` runs the prefill-window Pallas grid kernel — a
    ``(batch, q-block, page)`` grid whose scalar-prefetched block table
    drives the HBM→VMEM K/V DMA, online softmax across pages (compiled
    on TPU, interpret elsewhere); ``"ref"`` the fused jnp gather +
    masked f32 softmax.  ``block_q`` (kernel only) overrides the
    ``choose_block`` size/dtype dispatch; windows are padded to a block
    multiple and sliced back."""
    if impl == "ref":
        return _pa.paged_prefill_attention_ref(q, kv_pool, layer,
                                               block_tables, start, n_tok,
                                               sm_scale=sm_scale)
    if impl != "kernel":
        raise ValueError(
            f"paged_prefill_attention impl='{impl}' "
            f"(choose from {PAGED_PREFILL_IMPLS})")
    return _pa.paged_prefill_attention(q, kv_pool, layer, block_tables,
                                       start, n_tok, sm_scale=sm_scale,
                                       block_q=block_q,
                                       interpret=_interpret())


COPY_VARIANTS = tuple(["stock", "auto"] + list(_sc.VARIANTS))
COMBINE_VARIANTS = tuple(_rc.VARIANTS)
PAGED_ATTN_IMPLS = ("kernel", "ref")
PAGED_PREFILL_IMPLS = ("kernel", "ref")
