"""Chip smoke test: the serving path at published widths on a TPU.

    python chip_smoke.py                # one chip: gemma-2b
    python chip_smoke.py --four-chips   # 2x2 host: qwen3-8b, TP 4

One process, which owns the chip(s).  It refuses to run anywhere but a
TPU: no CPU fallback, no Pallas interpreter, no silent switch to the
jnp attention reference.

One chip: gemma-2b at its published widths (18 layers, d_model 2048,
MQA with head_dim 256, vocab 256000) built through
``launch.serve.build_engine`` in bf16 with the Pallas paged kernels,
over a 2048-page x 16-token KV pool, serves one seeded trace through
``ServeEngine.run``.  Checks: the compiled prefill and decode steps
hold ``tpu_custom_call`` (the kernels were compiled, not interpreted);
every request ends with exactly its ``max_new`` tokens, all in
``[0, vocab)``; the paged decode and prefill kernels match the jnp
reference on the chip at the served shapes.  Reports greedy stream
agreement with an ``attn_impl="ref"`` engine (not a gate).

Four chips: qwen3-8b at published widths, TP 4 over the 2x2 mesh
through ``serve.MeshExec``, params initialized straight into their
shards.  The same trace is served with the ``posh`` and the ``xla``
communicator backends; their first-step logits must agree within a
stated tolerance, and whether the token streams are bit-identical is
reported.

Timings printed here are smoke readings of one run, not benchmarks.
The last stdout line is the JSON result; any failed check exits
non-zero before it.  The compile cache follows
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
ONE_CHIP_ARCH = "gemma-2b"
FOUR_CHIP_ARCH = "qwen3-8b"
SMOKE = False                 # True: reduced configs in f32 (rehearsal)
SIZES = dict(page_tokens=16, n_pages=2048, max_batch=8, prefill_chunk=256,
             n_requests=16, prompt=(128, 1025), out=(32, 129))
FOUR_CHIP_PAGES = 1024
# posh and xla sum the 4 TP partials of every row-parallel matmul (the
# embedding, and attention-out + MLP-down in each of 36 layers: 73
# psums) in different orders, each sum rounded to bf16 (unit roundoff
# 2**-8 ~ 3.9e-3).  Independent per-psum discrepancies compound like a
# random walk: sqrt(73) * 2**-8 ~ 0.034 relative; 0.05 leaves headroom.
# A wrong collective (a partial lost or doubled) is off by >= 0.25.
LOGIT_RTOL = 0.05
SMOKE_TAG = "[smoke reading, not a benchmark]"


def make_trace(vocab: int, n: int | None = None):
    """The seeded trace: prompts and outputs drawn uniformly from the
    SIZES ranges, even rids greedy and odd rids sampled (T=1,
    top-p 0.9)."""
    from repro import serve
    s = SIZES
    tcfg = serve.TrafficConfig(
        n_requests=n or s["n_requests"], rate=4.0, vocab=vocab, seed=SEED,
        prompt_short=s["prompt"], prompt_long=s["prompt"],
        out_short=s["out"], out_long=s["out"],
        temperature=1.0, top_p=0.9)
    reqs = serve.make_requests(tcfg)
    for r in reqs[::2]:
        r.sampling = serve.GREEDY
    return reqs


def check_served(done, reqs, vocab: int) -> None:
    if sorted(r.rid for r in done) != sorted(r.rid for r in reqs):
        raise SystemExit(f"served {len(done)} of {len(reqs)} requests")
    for r in done:
        if len(r.out) != r.max_new:
            raise SystemExit(f"request {r.rid}: {len(r.out)} tokens, "
                             f"wanted {r.max_new}")
        bad = [t for t in r.out if not 0 <= t < vocab]
        if bad:
            raise SystemExit(f"request {r.rid}: token ids {bad[:4]} "
                             f"outside [0, {vocab})")


def compile_steps(exec_, scfg) -> None:
    """Compile the engine's prefill and decode programs ahead of time
    at the served shapes (the persistent cache then serves the
    dispatch) and require the Pallas kernels inside: compiled kernels
    are ``tpu_custom_call``s, interpreted ones are plain HLO."""
    import jax.numpy as jnp

    from repro import serve
    B, C, S = scfg.max_batch, scfg.prefill_chunk, scfg.table_slots
    z = lambda *shape: jnp.zeros(shape, jnp.int32)        # noqa: E731
    samp = serve.batch_state([], B, scfg.sample_seed)
    pool = exec_.init_pool()
    steps = {
        "prefill": (exec_._prefill, (z(B, C), z(B), z(B), z(B, S), samp)),
        "decode": (exec_._decode, (z(B), z(B), z(B, S), z(B), samp)),
    }
    for name, (fn, args) in steps.items():
        t0 = time.perf_counter()
        compiled = fn.lower(exec_.params, pool, *args).compile()
        secs = time.perf_counter() - t0
        if "tpu_custom_call" not in compiled.as_text():
            raise SystemExit(f"the compiled {name} step holds no "
                             f"tpu_custom_call: Pallas kernels were not "
                             f"compiled for the chip")
        print(f"{SMOKE_TAG} compile {name} step: {secs:.1f} s "
              f"(tpu_custom_call present)")


def check_attention_parity(cfg, scfg, max_len: int) -> None:
    """Paged decode and prefill attention, kernel against the jnp
    reference, on the chip at the served shapes and dtype.

    Tolerance, elementwise: 2**-7 * (max|v| + |ref|).  Both sides see
    the same bf16 q/k/v and reduce in f32, so scores agree up to
    summation order; the softmax weights may be rounded to bf16 for an
    MXU pass on one side only, which moves a weighted mean of v by at
    most 2**-8 * max|v|; and the two f32 results round to bf16 apart
    by at most one ulp, <= 2**-7 * |ref|.  A wrong page, mask or
    normalization is off by a multiple of |v| / length, far larger at
    the short lengths included."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    rng = np.random.RandomState(SEED)
    B, C, S, P = (scfg.max_batch, scfg.prefill_chunk, scfg.table_slots,
                  scfg.page_tokens)
    H, Hkv, D = cfg.n_heads, cfg.kv_per_rank(1), cfg.head_dim
    dt = scfg.kv_dtype
    kp, kq, kw = jax.random.split(jax.random.PRNGKey(SEED), 3)
    # a two-layer pool, read at its second layer
    pool = jax.random.normal(kp, (scfg.n_pages, 2, 2, P, Hkv, D), dt)
    layer = 1
    bt = jnp.asarray(rng.randint(1, scfg.n_pages, (B, S)), jnp.int32)
    vmax = float(jnp.abs(pool[:, 1, layer]).max())

    def close(name, got, want):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        tol = 2.0 ** -7 * (vmax + np.abs(want))
        err = np.abs(got - want)
        share = float((err / tol).max())
        print(f"{name} kernel vs ref: max |diff| {err.max():.3g}, "
              f"{share:.3f} of tolerance")
        if not share <= 1.0:
            raise SystemExit(f"{name}: kernel and ref disagree beyond "
                             f"tolerance ({share:.2f}x)")

    lens = rng.randint(1, max_len + 1, B)
    lens[:4] = [1, P, P + 1, max_len]
    q = jax.random.normal(kq, (B, H, D), dt)
    lens = jnp.asarray(lens, jnp.int32)
    close("paged decode attention",
          ops.paged_attention(q, pool, layer, bt, lens, impl="kernel"),
          ops.paged_attention(q, pool, layer, bt, lens, impl="ref"))

    start = rng.randint(0, max_len - C + 1, B)
    n_tok = rng.randint(1, C + 1, B)
    start[:3] = [0, 0, max_len - C]
    n_tok[:3] = [0, C, C]
    qw = jax.random.normal(kw, (B, C, H, D), dt)
    start, n_tok = (jnp.asarray(a, jnp.int32) for a in (start, n_tok))
    close("paged prefill attention",
          ops.paged_prefill_attention(qw, pool, layer, bt, start, n_tok,
                                      impl="kernel"),
          ops.paged_prefill_attention(qw, pool, layer, bt, start, n_tok,
                                      impl="ref"))


def greedy_agreement(eng, cfg) -> None:
    """Serve the trace on the tick clock with the kernel engine and with
    an ``attn_impl="ref"`` twin on the same weights; report how far the
    greedy streams agree.  Not a gate: bf16 near-ties may diverge."""
    from repro import serve
    ref = serve.ServeEngine(eng.exec.params, cfg, eng.ctx,
                            dataclasses.replace(eng.scfg, attn_impl="ref"))
    streams = []
    for e in (eng, ref):
        e.reset_metrics()
        done = e.run(make_trace(cfg.vocab), clock="tick")
        streams.append({r.rid: r.out for r in done
                        if r.sampling.temperature == 0})
    same, prefix = 0, []
    for rid, a in streams[0].items():
        b = streams[1][rid]
        n = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                 min(len(a), len(b)))
        same += a == b
        prefix.append(n / len(a))
    print(f"{SMOKE_TAG} greedy streams kernel vs ref: {same}/"
          f"{len(prefix)} identical, mean agreeing prefix "
          f"{sum(prefix) / len(prefix):.3f} (not a gate)")


def one_chip(dev) -> None:
    import jax

    from repro.launch.serve import build_engine
    s = SIZES
    t0 = time.perf_counter()
    eng, cfg = build_engine(
        ONE_CHIP_ARCH, smoke=SMOKE, page_tokens=s["page_tokens"],
        n_pages=s["n_pages"], max_batch=s["max_batch"], attn_impl="kernel",
        prefill_chunk=s["prefill_chunk"], seed=SEED)
    params = eng.exec.params
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    p_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"built {cfg.name} ({cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab}): {n_params / 1e9:.3f} B "
          f"params, {p_bytes / 1e9:.2f} GB; KV pool {eng.pool.dtype} "
          f"{s['n_pages']} pages x {s['page_tokens']} tokens = "
          f"{eng.pool.nbytes / 1e9:.2f} GB; "
          f"{time.perf_counter() - t0:.1f} s")
    compile_steps(eng.exec, eng.scfg)

    warm = make_trace(cfg.vocab, 2)
    for r in warm:
        r.max_new = 2
    t0 = time.perf_counter()
    eng.run(warm)
    print(f"{SMOKE_TAG} warm-up serve (2 requests): "
          f"{time.perf_counter() - t0:.1f} s")
    eng.reset_metrics()

    reqs = make_trace(cfg.vocab)
    t0 = time.perf_counter()
    done = eng.run(reqs)
    wall = time.perf_counter() - t0
    check_served(done, reqs, cfg.vocab)
    m = eng.metrics()
    n_in = sum(r.n_prompt for r in reqs)
    print(f"served {len(done)} requests ({n_in} prompt tokens, "
          f"{m['tokens_out']} output tokens, half greedy / half top-p "
          f"0.9) in {wall:.1f} s over {m['ticks']} ticks; every request "
          f"got exactly max_new tokens, all ids in [0, {cfg.vocab})")
    print(f"{SMOKE_TAG} warm TTFT p50 {m['ttft_p50_s'] * 1e3:.1f} ms; "
          f"decode: inter-token gap p50 {m['decode_p50_s'] * 1e3:.1f} "
          f"ms, {m['throughput_tok_s']:.1f} output tok/s over the run")

    check_attention_parity(cfg, eng.scfg,
                           s["prompt"][1] - 1 + s["out"][1] - 1)
    greedy_agreement(eng, cfg)
    stats = dev.memory_stats() or {}
    print(f"{SMOKE_TAG} peak device memory: "
          f"{stats.get('peak_bytes_in_use', 0) / 1e9:.2f} GB")


def first_step_logits(exec_, cfg, ctx, scfg, pspecs, reqs):
    """Full-vocab logits after the first prefill window of the trace's
    first ``max_batch`` prompts on an empty pool — the served trunk
    (``engine._make_window_forward``) and LM head, gathered over TP."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.models import embed as emb
    from repro.parallel.ctx import smap
    from repro.serve import engine, mesh_exec

    B, C, S, Pt = (scfg.max_batch, scfg.prefill_chunk, scfg.table_slots,
                   scfg.page_tokens)
    window = engine._make_window_forward(cfg, ctx, scfg)
    head = "embed" if cfg.tie_embeddings else "head"

    def body(params, pool, ids, start, n_tok, bt):
        x, _ = window(params, pool[0, 0], ids, start, n_tok, bt)
        last = jnp.clip(n_tok - 1, 0, ids.shape[1] - 1)
        xl = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        return emb.lm_head_logits(params[head],
                                  xl.astype(ctx.compute_dtype), ctx)

    ids = np.zeros((B, C), np.int32)
    n_tok = np.zeros((B,), np.int32)
    bt = np.zeros((B, S), np.int32)
    per = -(-C // Pt)
    for i, r in enumerate(reqs[:B]):
        n = min(r.n_prompt, C)
        ids[i, :n] = r.prompt[:n]
        n_tok[i] = n
        bt[i, :per] = 1 + i * per + np.arange(per)
    fn = jax.jit(smap(body, exec_.mesh,
                      (pspecs, mesh_exec.POOL_SPEC, P(), P(), P(), P()),
                      P(None, ctx.tp_axis)))
    out = fn(exec_.params, exec_.init_pool(), ids, np.zeros((B,), np.int32),
             n_tok, bt)
    return np.asarray(out, np.float32)[:, :cfg.vocab]


def four_chips() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import compat, configs, serve
    from repro.core import SymmetricHeap
    from repro.models import registry
    from repro.parallel.ctx import ParallelCtx
    devs = jax.devices()
    if len(devs) != 4:
        raise SystemExit(f"--four-chips needs 4 devices, JAX found "
                         f"{len(devs)}")
    mesh = compat.make_mesh((1, 4), ("data", "model"), devices=devs)
    s = SIZES
    cfg = (configs.get_smoke if SMOKE else configs.get)(FOUR_CHIP_ARCH)
    dtype = jnp.float32 if SMOKE else jnp.bfloat16
    api = registry.build(cfg)
    scfg = serve.ServeConfig(
        page_tokens=s["page_tokens"], n_pages=FOUR_CHIP_PAGES,
        max_batch=s["max_batch"], max_seq=cfg.max_seq,
        prefill_chunk=s["prefill_chunk"], attn_impl="kernel",
        kv_dtype=dtype)

    def ctx_for(backend):
        return ParallelCtx(dp_size=1, tp_size=4, sp=False, remat=False,
                           backend=backend, param_dtype=dtype,
                           compute_dtype=dtype)

    t0 = time.perf_counter()
    params = serve.init_sharded_params(api, cfg, ctx_for("xla"), mesh,
                                       jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    per_dev = sum(sh.data.nbytes for x in jax.tree.leaves(params)
                  for sh in x.addressable_shards if sh.device == devs[0])
    print(f"built {cfg.name} ({cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab}) sharded TP 4: "
          f"{per_dev / 1e9:.2f} GB of params on each chip; "
          f"{time.perf_counter() - t0:.1f} s")
    logits, streams = {}, {}
    for backend in ("xla", "posh"):
        ctx = ctx_for(backend)
        pspecs = api.specs(cfg, ctx)
        kv = serve.PagedKVCache(
            SymmetricHeap(("data", "model")), n_layers=cfg.n_layers,
            kv_heads=cfg.kv_per_rank(4), head_dim=cfg.head_dim,
            n_pages=scfg.n_pages, page_tokens=scfg.page_tokens, dtype=dtype)
        exec_ = serve.MeshExec(params, pspecs, cfg, ctx, scfg, kv, mesh)
        print(f"[{backend}]")
        compile_steps(exec_, scfg)
        reqs = make_trace(cfg.vocab)
        logits[backend] = first_step_logits(exec_, cfg, ctx, scfg, pspecs,
                                            reqs)
        eng = serve.ServeEngine(params, cfg, ctx, scfg, kv=kv, exec_=exec_)
        t0 = time.perf_counter()
        done = eng.run(reqs, clock="tick")
        check_served(done, reqs, cfg.vocab)
        m = eng.metrics()
        print(f"{SMOKE_TAG} [{backend}] served {len(done)} requests "
              f"({m['tokens_out']} output tokens, {m['ticks']} ticks) in "
              f"{time.perf_counter() - t0:.1f} s; every request got "
              f"exactly max_new tokens")
        streams[backend] = {r.rid: r.out for r in done}
        del eng, exec_, kv

    a, b = logits["xla"], logits["posh"]
    rel = float(np.linalg.norm(b - a) / np.linalg.norm(a))
    agree = int((a.argmax(-1) == b.argmax(-1)).sum())
    print(f"first-step logits posh vs xla: relative L2 {rel:.3g} "
          f"(tolerance {LOGIT_RTOL}), max |diff| "
          f"{float(np.abs(b - a).max()):.3g} at logit scale "
          f"{float(np.abs(a).max()):.3g}, argmax agrees on {agree}/"
          f"{len(a)} rows")
    if not rel <= LOGIT_RTOL:
        raise SystemExit(f"posh and xla logits disagree: relative L2 "
                         f"{rel:.3g} > {LOGIT_RTOL}")
    same = sum(streams["xla"][r] == streams["posh"][r] for r in streams["xla"])
    print(f"token streams posh vs xla: {same}/{len(streams['xla'])} "
          f"bit-identical")
    stats = devs[0].memory_stats() or {}
    print(f"{SMOKE_TAG} peak device memory (chip 0): "
          f"{stats.get('peak_bytes_in_use', 0) / 1e9:.2f} GB")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="qwen3-8b at TP 4 on a 2x2 host, posh against "
                         "xla; no other phase")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform}; refusing to run elsewhere")
    print(f"device: {dev.platform} {dev.device_kind} x {len(devs)}; "
          f"compile cache {cache}")
    if args.four_chips:
        four_chips()
    else:
        one_chip(dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
