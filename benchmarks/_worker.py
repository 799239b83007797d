"""Benchmark worker — every table of ``benchmarks/run.py`` in one
process, over the PEs ``jax.devices()`` reports.

Off-chip the CPU backend is split into 8 fake devices (the flag only
touches the host platform); on a TPU host the PEs are the attached
chips, 1 or 4 of them.

Covers the paper's measurements:
  Table 1: memory-copy engine variants
  Table 2: put/get latency/bandwidth through the POSH layer vs a local
           device copy (the 'memcpy' baseline)
  Table 3: POSH collectives vs native XLA collectives (the UPC/GASNet
           role) across buffer sizes
  §4.5.4:  collective algorithm selection (ring / tree / rec-doubling)
plus the smoke-config training step.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro import core as posh

REPEATS = 20   # paper: 20 reps after warm-up
WARMUP = 3


def timeit(fn, x):
    for _ in range(WARMUP):
        jax.block_until_ready(fn(x))
    t0 = time.perf_counter()
    for _ in range(REPEATS):
        out = fn(x)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / REPEATS


def bench_copy_variants():
    from repro.kernels import ops, symm_copy

    print("table,op,elems,us_per_call,derived_gbps_or_vmem_kib")
    for elems in [4096, 262144, 4194304]:
        x = jnp.arange(elems, dtype=jnp.float32)
        dt = timeit(jax.jit(lambda v: ops.symm_copy(v, "stock")), x)
        print(f"table1,copy_stock,{elems},{dt*1e6:.2f},"
              f"{elems*4/dt/1e9:.3f}")
        for variant in symm_copy.VARIANTS:
            kib = symm_copy.vmem_bytes(variant) / 1024
            print(f"table1,copy_{variant},{elems},nan,{kib:.0f}")


def bench_p2p(mesh, n):
    smap = _smap(mesh)
    print("table,op,elems_per_pe,us_per_call,gbps")
    for elems in [256, 4096, 65536, 1048576]:
        x = jnp.arange(n * elems, dtype=jnp.float32).reshape(n, elems)
        bytes_moved = elems * 4

        put_fn = jax.jit(smap(lambda v: posh.ring_shift(v, "pe", 1)))
        get_fn = jax.jit(smap(lambda v: posh.get(
            v, [((i + 1) % n, i) for i in range(n)], "pe")))
        copy_fn = jax.jit(smap(lambda v: v * 1))  # local 'memcpy' baseline

        for name, fn in [("put", put_fn), ("get", get_fn),
                         ("local_copy", copy_fn)]:
            dt = timeit(fn, x)
            print(f"table2,{name},{elems},{dt*1e6:.2f},"
                  f"{bytes_moved/dt/1e9:.3f}")


def bench_collectives(mesh, n):
    smap = _smap(mesh)
    for elems in [1024, 65536, 1048576]:
        x = jnp.arange(n * elems, dtype=jnp.float32).reshape(n, elems)
        cases = [
            ("allreduce_posh_ring",
             lambda v: posh.allreduce(v, "sum", "pe", "ring")),
            ("allreduce_posh_tree",
             lambda v: posh.allreduce(v, "sum", "pe", "tree")),
            ("allreduce_posh_rd",
             lambda v: posh.allreduce(v, "sum", "pe", "recursive_doubling")),
            ("allreduce_xla",
             lambda v: posh.allreduce(v, "sum", "pe", "xla")),
            ("bcast_posh_binomial",
             lambda v: posh.broadcast(v, 0, "pe", "binomial")),
            ("bcast_posh_linear",
             lambda v: posh.broadcast(v, 0, "pe", "linear")),
            ("bcast_xla", lambda v: posh.broadcast(v, 0, "pe", "xla")),
        ]
        for name, body in cases:
            fn = jax.jit(smap(body))
            dt = timeit(fn, x)
            print(f"table3,{name},{elems},{dt*1e6:.2f},"
                  f"{elems*4/dt/1e9:.3f}")
        ag_cases = [
            ("allgather_posh_ring",
             lambda v: posh.fcollect(v, "pe", "ring")),
            ("allgather_posh_rd",
             lambda v: posh.fcollect(v, "pe", "recursive_doubling")),
            ("allgather_xla", lambda v: posh.fcollect(v, "pe", "xla")),
        ]
        for name, body in ag_cases:
            fn = jax.jit(smap(body, out_specs=P("pe", None)))
            dt = timeit(fn, x)
            print(f"table3,{name},{elems},{dt*1e6:.2f},"
                  f"{elems*4*(n-1)/dt/1e9:.3f}")


def bench_atomics(mesh, n):
    heap = posh.SymmetricHeap(("pe",))
    h = heap.alloc("cells", (8,), jnp.float32)

    def fadd(v):
        st = {"cells": jnp.zeros((8,), jnp.float32)}
        st, old = posh.atomic_fadd(st, h, 0, v[0], "pe", owner=0)
        return old[None]

    fn = jax.jit(_smap(mesh)(fadd))
    x = jnp.ones((n, 1), jnp.float32)
    dt = timeit(fn, x)
    print(f"atomics,fadd_owner_computes,1,{dt*1e6:.2f},0")


def bench_train_throughput():
    from repro import configs
    from repro.data import SyntheticLM
    from repro.models import registry
    from repro.parallel.ctx import ParallelCtx, smap
    from repro.train.optimizer import AdamWConfig, adamw_init
    from repro.train.step import make_train_step, train_state_specs

    ctx = ParallelCtx(dp_size=1, tp_size=1, sp=False, remat=True,
                      param_dtype=jnp.float32, compute_dtype=jnp.float32)
    cfg = configs.get_smoke("qwen3-8b")
    api = registry.build(cfg)
    opt = AdamWConfig(lr=1e-3)
    mesh = compat.make_mesh((1, 1), ("data", "model"),
                            devices=jax.devices()[:1])
    sspecs = train_state_specs(cfg, ctx, api, opt)
    params = api.init(jax.random.PRNGKey(0), cfg, ctx)
    opt_state = smap(lambda p: adamw_init(p, ctx, opt), mesh,
                     (api.specs(cfg, ctx),), sspecs["opt"])(params)
    state = {"params": params, "opt": opt_state,
             "step": jnp.zeros((), jnp.int32)}
    fn = jax.jit(smap(make_train_step(cfg, ctx, api, opt), mesh,
                      (sspecs, {"tokens": P("data")}),
                      (sspecs, {"loss": P(), "grad_norm": P(),
                                "step": P()})))
    data = SyntheticLM(vocab=cfg.vocab, seq_len=cfg.max_seq, global_batch=8)
    state, m = fn(state, data.batch(0))
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    steps = 5
    for s in range(1, steps + 1):
        state, m = fn(state, data.batch(s))
    jax.block_until_ready(m["loss"])
    dt = (time.perf_counter() - t0) / steps
    toks = 8 * cfg.max_seq
    print(f"train,smoke_step,{toks},{dt*1e6:.0f},{toks/dt:.0f}")


def _smap(mesh):
    def smap(fn, out_specs=P("pe")):
        return compat.shard_map(fn, mesh=mesh, in_specs=P("pe"),
                                out_specs=out_specs, check_vma=False)
    return smap


if __name__ == "__main__":
    n = len(jax.devices())
    mesh = compat.make_mesh((n,), ("pe",))
    bench_copy_variants()
    bench_p2p(mesh, n)
    bench_collectives(mesh, n)
    bench_atomics(mesh, n)
    bench_train_throughput()
    print("WORKER_DONE")
