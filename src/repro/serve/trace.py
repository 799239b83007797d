"""Host spans of the serving engine, on the JAX profiler's clock.

Spans are ``jax.profiler.TraceAnnotation``s: inert (about a
microsecond) when no profiler session runs, and written into the
profiler's own trace, beside the device's operations, when one does.
Keyword counters become the span's stats in the trace.

``ServeEngine.tick`` opens, per tick:

    serve.tick       the whole tick                        tick
    serve.schedule   ``FCFSScheduler.tick``
    serve.plan       shed / preempt bookkeeping of the plan waiting,
                                                           decode_seqs,
                                                           prefill_seqs,
                                                           prefill_tokens,
                                                           pages_free
    serve.migrate    page migrations (when there are any)  pages
    serve.prepare    host inputs, block table, sampling    step
    serve.dispatch   the step call until it returns        step, tokens
    serve.wait       the host read of the sampled tokens   step
    serve.retire     token bookkeeping after the wait      step

``step`` is ``prefill``, ``decode`` or ``verify``; prepare, dispatch,
wait and retire repeat, in that order, once per step call.  The step
programs carry ``jax.named_scope``s in their op metadata: ``embed``,
then per layer ``qkv``, ``kv_write``, ``attn_kernel`` (which reads the
layer's pages from the pool), ``attn_out`` and ``mlp``, then
``head_sample``.
"""
from __future__ import annotations

import jax


def span(name: str, **counters):
    """A host span named ``name`` with ``counters`` as its stats."""
    return jax.profiler.TraceAnnotation(name, **counters)
