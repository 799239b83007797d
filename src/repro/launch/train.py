"""Production training driver: mesh from the available devices, POSH
backend, ZeRO-1 optimizer, checkpoint/restart, straggler accounting.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b \
        --smoke --steps 50 --ckpt-dir /tmp/ck

On a real pod this runs under one process per host with
jax.distributed.initialize(); in this container it runs single-device
(the step function is IDENTICAL — only the mesh differs).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat, configs
from repro.ckpt import Checkpointer
from repro.data import SyntheticLM, batch_specs
from repro.ft import StragglerPolicy
from repro.launch.cache import enable_compile_cache
from repro.models import registry
from repro.parallel.ctx import ParallelCtx, smap
from repro.train.optimizer import AdamWConfig, adamw_init
from repro.train.step import make_train_step, train_state_specs


def build_mesh():
    n = len(jax.devices())
    # squarest (data, model) factorization of the available devices
    best = (n, 1)
    for m in range(1, int(n ** 0.5) + 1):
        if n % m == 0:
            best = (n // m, m)
    return compat.make_mesh(best, ("data", "model"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced-config variant")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--backend", default="posh", choices=["posh", "xla"])
    ap.add_argument("--zero", type=int, default=0, choices=[0, 1])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--bucket-bytes", type=int, default=0,
                    help="DP grad bucketing (0 = per-leaf reductions)")
    ap.add_argument("--overlap-grad-sync", action="store_true",
                    help="issue DP reductions nonblocking and drain "
                         "with one quiet() before the optimizer "
                         "(paper §3.2 overlap; bit-identical losses)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get(args.arch)
    mesh = build_mesh()
    dp, tp = mesh.devices.shape
    ctx = ParallelCtx.from_mesh(mesh, sp=tp > 1, remat=True,
                                backend=args.backend,
                                param_dtype=jnp.float32,
                                compute_dtype=jnp.float32)
    api = registry.build(cfg)
    opt = AdamWConfig(lr=args.lr, zero=args.zero)
    sspecs = train_state_specs(cfg, ctx, api, opt)

    params = api.init(jax.random.PRNGKey(0), cfg, ctx)
    opt_state = smap(lambda p: adamw_init(p, ctx, opt), mesh,
                     (api.specs(cfg, ctx),), sspecs["opt"])(params)
    state = {"params": params, "opt": opt_state,
             "step": jnp.zeros((), jnp.int32)}
    ck = Checkpointer(args.ckpt_dir, keep=3)
    start = 0
    if args.resume:
        state, start = ck.restore(state)
        print(f"resumed at step {start}")

    step_fn = jax.jit(smap(
        make_train_step(cfg, ctx, api, opt, microbatches=args.microbatches,
                        bucket_bytes=args.bucket_bytes,
                        overlap_grad_sync=args.overlap_grad_sync),
        mesh, (sspecs, {"tokens": P("data")}),
        (sspecs, {"loss": P(), "grad_norm": P(), "step": P()})))
    data = SyntheticLM(vocab=cfg.vocab, seq_len=cfg.max_seq,
                       global_batch=args.global_batch)
    straggler = StragglerPolicy(deadline_s=600.0)
    print(f"mesh {mesh.devices.shape} backend={args.backend} "
          f"zero={args.zero} arch={cfg.name}")
    for s in range(start, args.steps):
        t0 = time.time()
        state, m = step_fn(state, data.batch(s, dp_rank=0, dp_size=1))
        jax.block_until_ready(m["loss"])
        dt = time.time() - t0
        straggler.record(0, dt)
        if s % 5 == 0 or s == args.steps - 1:
            print(f"step {s:4d}  loss {float(m['loss']):.4f}  {dt:.2f}s")
        if (s + 1) % args.ckpt_every == 0:
            ck.save_async(s + 1, state)
    ck.wait()
    print("training complete")


if __name__ == "__main__":
    main()
