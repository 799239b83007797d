"""Compile rehearsal for a described TPU v5e: the serving path's Pallas
kernels and the full-width gemma-2b decode step compile for the chip's
target, with the kernels present as ``tpu_custom_call`` (compiled, not
interpreted).  Nothing runs: this needs libtpu's compiler, not a chip.

The topology is described inside a fixture (never at import, in a
``skipif`` or in ``parametrize``): only one process at a time may load
libtpu, so the worker that gets this file loads it and the others never
try.  Keep every TPU-target compile in this one file.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs, serve
from repro.kernels import ops
from repro.kernels import paged_attention as pa
from repro.models import registry
from repro.parallel.ctx import ParallelCtx


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no libtpu"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# (name, query heads, KV heads, head_dim, pages) per chip: gemma-2b and
# qwen3-8b on one chip; qwen3-8b's per-rank share at TP 4 (32/4 heads,
# 8/4 KV heads)
SHAPES = [("gemma-2b", 8, 1, 256, 2048), ("qwen3-8b", 32, 8, 128, 2048),
          ("qwen3-8b-tp4-rank", 8, 2, 128, 1024)]
PAGE_TOKENS, BATCH, WINDOW, MAX_SEQ = 16, 8, 256, 4096
LAYERS = 16             # the kernels read one layer of the whole pool


def _structs(sharding, **shapes):
    return {k: jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for k, (s, dt) in shapes.items()}


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name,h,hkv,d,n_pages", SHAPES,
                         ids=[s[0] for s in SHAPES])
def test_paged_decode_kernel_compiles(one_chip, name, h, hkv, d, n_pages):
    slots = MAX_SEQ // PAGE_TOKENS
    a = _structs(one_chip,
                 q=((BATCH, h, d), jnp.bfloat16),
                 pool=((n_pages, 2, LAYERS, PAGE_TOKENS, hkv, d),
                       jnp.bfloat16),
                 layer=((), jnp.int32),
                 bt=((BATCH, slots), jnp.int32),
                 lens=((BATCH,), jnp.int32))
    fn = jax.jit(lambda q, pool, li, bt, lens: pa.paged_decode_attention(
        q, pool, li, bt, lens, interpret=False))
    _assert_kernel(fn.lower(a["q"], a["pool"], a["layer"], a["bt"],
                            a["lens"]).compile())


@pytest.mark.parametrize("name,h,hkv,d,n_pages", SHAPES,
                         ids=[s[0] for s in SHAPES])
def test_paged_prefill_kernel_compiles(one_chip, name, h, hkv, d, n_pages):
    slots = MAX_SEQ // PAGE_TOKENS
    a = _structs(one_chip,
                 q=((BATCH, WINDOW, h, d), jnp.bfloat16),
                 pool=((n_pages, 2, LAYERS, PAGE_TOKENS, hkv, d),
                       jnp.bfloat16),
                 layer=((), jnp.int32),
                 bt=((BATCH, slots), jnp.int32),
                 start=((BATCH,), jnp.int32),
                 n_tok=((BATCH,), jnp.int32))
    fn = jax.jit(lambda q, pool, li, bt, s, n: pa.paged_prefill_attention(
        q, pool, li, bt, s, n, interpret=False))
    _assert_kernel(fn.lower(a["q"], a["pool"], a["layer"], a["bt"],
                            a["start"], a["n_tok"]).compile())


def test_gemma_2b_decode_step_compiles(one_chip, monkeypatch):
    """The whole served decode step at published widths in bf16: 18
    layers, vocab 256000, a 2048-page x 16-token pool.  The backend
    here is the CPU, so the kernels' platform default would interpret;
    the test forces compiled kernels as a chip run gets them."""
    monkeypatch.setattr(ops, "INTERPRET", False)
    cfg = configs.get("gemma-2b")
    bf = jnp.bfloat16
    ctx = ParallelCtx(dp_size=1, tp_size=1, sp=False, remat=False,
                      param_dtype=bf, compute_dtype=bf)
    api = registry.build(cfg)
    scfg = serve.ServeConfig(page_tokens=PAGE_TOKENS, n_pages=2048,
                             max_batch=BATCH, max_seq=cfg.max_seq,
                             attn_impl="kernel", kv_dtype=bf)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda k: api.init(k, cfg, ctx),
                       jax.random.PRNGKey(0)))
    n_params = sum(math.prod(x.shape) for x in jax.tree.leaves(params))
    assert 2.4e9 < n_params < 2.6e9          # published: ~2.5 B
    samp = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        serve.batch_state([], BATCH, 0))
    a = _structs(one_chip,
                 pool=((scfg.n_pages, 2, cfg.n_layers, PAGE_TOKENS,
                        cfg.n_kv, cfg.head_dim), bf),
                 tok=((BATCH,), jnp.int32), pos=((BATCH,), jnp.int32),
                 bt=((BATCH, scfg.table_slots), jnp.int32),
                 lens=((BATCH,), jnp.int32))
    step = jax.jit(serve.make_decode_step(cfg, ctx, scfg))
    compiled = step.lower(params, a["pool"], a["tok"], a["pos"], a["bt"],
                          a["lens"], samp).compile()
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    # params + pool in, the pool out: all of it must fit one 16 GB chip
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 16e9, total
