"""Serving CLI: continuous batching over the paged symmetric-heap KV
cache with seeded synthetic traffic.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \\
        --requests 16 --rate 8 --page-tokens 8 \\
        --temperature 0.8 --top-p 0.9

Per-request sampling params ride on every Request (greedy by default;
``--temperature/--top-k/--top-p`` set the trace-wide policy, drawn
through the TP-aware two-phase sampler), and long prompts prefill in
``--prefill-chunk``-token chunks under the ``--tick-tokens`` budget so
they never stall concurrent decodes.  ``--spec-k N`` turns on
speculative decoding (N drafts verified per sequence per tick;
``--draft`` picks the proposer — the n-gram self-draft or a registry
arch as a small draft model) without changing a single output token:
acceptance is exact matching against the engine's counter-RNG draws,
so speculation only shrinks tick counts.  Prints per-request decode
traces
when --trace is set, then the throughput/latency summary.  ``--smoke``
configs (f32) run on CPU; without it the published config is served in
bf16 on one device.  Tensor-parallel serving over a mesh goes through
``serve.MeshExec`` (``chip_smoke.py --four-chips``).
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from repro import configs, serve
from repro.launch.cache import enable_compile_cache
from repro.models import registry
from repro.parallel.ctx import ParallelCtx


def parse_slo(spec: str) -> tuple[float, float]:
    """``--slo I+B`` class-mix spec -> (interactive_frac, batch_frac);
    the remainder of the trace is best_effort."""
    try:
        i, b = spec.split("+")
        ifrac, bfrac = float(i), float(b)
    except ValueError:
        raise SystemExit(
            f"--slo wants I+B fractions (e.g. 0.5+0.25), got "
            f"{spec!r}") from None
    if ifrac < 0 or bfrac < 0 or ifrac + bfrac > 1.0 + 1e-9:
        raise SystemExit(f"--slo {spec}: fractions must be >= 0 and sum "
                         f"to <= 1")
    return ifrac, bfrac


def parse_disagg(spec: str) -> tuple[int, int]:
    """``--disagg P+D`` topology spec -> (n_prefill, n_decode)."""
    try:
        p, d = spec.split("+")
        n_prefill, n_decode = int(p), int(d)
    except ValueError:
        raise SystemExit(
            f"--disagg wants P+D (e.g. 2+2), got {spec!r}") from None
    if n_prefill < 1 or n_decode < 1:
        raise SystemExit(f"--disagg {spec}: both cell counts must be >= 1")
    return n_prefill, n_decode


def build_engine(arch: str, *, smoke: bool = False, backend: str = "xla",
                 page_tokens: int = 8, n_pages: int = 64,
                 max_batch: int = 4, attn_impl: str = "ref",
                 prefix_keep: bool = False, prefill_chunk: int = 8,
                 tick_tokens: int = 0, sample_seed: int = 0, seed: int = 0,
                 spec_k: int = 0, draft: str = "ngram", disagg: str = "",
                 router: str = "host", slo=None):
    """One-device engine for ``arch``: its published config served in
    bf16 (params, compute and KV pages), or with ``smoke=True`` the
    reduced config in f32."""
    get = configs.get_smoke if smoke else configs.get
    dtype = jnp.float32 if smoke else jnp.bfloat16
    cfg = get(arch)
    ctx = ParallelCtx(dp_size=1, tp_size=1, sp=False, remat=False,
                      backend=backend, param_dtype=dtype,
                      compute_dtype=dtype)
    api = registry.build(cfg)
    params = api.init(jax.random.PRNGKey(seed), cfg, ctx)
    scfg = serve.ServeConfig(
        page_tokens=page_tokens, n_pages=n_pages, max_batch=max_batch,
        max_seq=cfg.max_seq, prefill_chunk=prefill_chunk,
        tick_tokens=tick_tokens, attn_impl=attn_impl, kv_dtype=dtype,
        prefix_keep=prefix_keep, sample_seed=sample_seed,
        # scfg.draft only names parameterless proposers; a draft ARCH
        # becomes an explicit DraftModelProposer below
        spec_k=spec_k, draft="ngram", slo=slo)
    if router not in ("host", "amo"):
        raise SystemExit(f"--router wants 'host' or 'amo', got {router!r}")
    if disagg:
        n_prefill, n_decode = parse_disagg(disagg)
        return serve.DisaggEngine(params, cfg, ctx, scfg,
                                  n_prefill=n_prefill,
                                  n_decode=n_decode, router=router), cfg
    if spec_k > 0 and draft != "ngram":
        # --draft <arch>: a registry-backed small draft model on the
        # same mesh and page geometry (vocabularies must match); the
        # shared PagedKVCache is built first so draft and target index
        # their pools through the same block tables
        from repro.core.heap import SymmetricHeap
        kv = serve.PagedKVCache(
            SymmetricHeap(("data",)), n_layers=cfg.n_layers,
            kv_heads=cfg.kv_per_rank(1), head_dim=cfg.head_dim,
            n_pages=n_pages, page_tokens=page_tokens, dtype=dtype)
        dcfg = get(draft)
        dparams = registry.build(dcfg).init(
            jax.random.PRNGKey(seed + 1), dcfg, ctx)
        proposer = serve.DraftModelProposer(dparams, dcfg, ctx, scfg, kv,
                                            target_vocab=cfg.vocab)
        eng = serve.ServeEngine(params, cfg, ctx, scfg, kv=kv,
                                proposer=proposer)
    else:
        eng = serve.ServeEngine(params, cfg, ctx, scfg)
    if router == "amo":
        # colocated 'amo' means the page allocator: the engine's free
        # list moves onto symmetric counter words (identical page-id
        # grants, so token streams cannot move)
        eng.kv.attach_pool(serve.SymmetricPagePool(eng.kv.n_pages))
    return eng, cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced-config variant in f32 "
                         "(default: the published config in bf16)")
    ap.add_argument("--backend", default="xla",
                    help="communicator backend (xla | posh | pallas)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--page-tokens", type=int, default=8)
    ap.add_argument("--n-pages", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="max prompt tokens one sequence prefills per tick")
    ap.add_argument("--tick-tokens", type=int, default=0,
                    help="per-tick token budget shared by decode+prefill "
                         "(0 = max_batch + prefill_chunk)")
    ap.add_argument("--attn-impl", default="ref",
                    choices=["ref", "kernel"],
                    help="paged attention impl for decode AND the "
                         "prefill/verify windows: 'kernel' (Pallas "
                         "grid kernels; compiled on TPU, interpret "
                         "elsewhere) or 'ref' (fused jnp)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="per-request top-k cut (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="per-request nucleus cut (1 = off)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="root of the per-(rid, position) RNG streams")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft tokens verified "
                         "per sequence per tick (0 = off); token "
                         "streams are unchanged, only ticks shrink")
    ap.add_argument("--draft", default="ngram",
                    help="draft proposer: 'ngram' (prompt-lookup "
                         "self-draft) or a registry arch name for a "
                         "small draft model (e.g. gemma-2b)")
    ap.add_argument("--disagg", default="",
                    help="disaggregated topology 'P+D' (e.g. 2+2): P "
                         "prefill cells + D decode cells with "
                         "put-with-signal page handoff (empty = "
                         "colocated single engine)")
    ap.add_argument("--router", default="host", choices=["host", "amo"],
                    help="scheduling control plane: 'host' (Python-loop "
                         "admission/handoff routing and page free list) "
                         "or 'amo' (lock-free: CAS-arbitrated admission "
                         "rings, claim-word mailbox slots, and a "
                         "symmetric fetch-add/CAS page pool — token "
                         "streams are bit-identical across both)")
    ap.add_argument("--slo", default="",
                    help="SLO traffic mix 'I+B' (e.g. 0.5+0.25): "
                         "fractions of interactive and batch requests, "
                         "remainder best_effort; turns on priority "
                         "admission, deadline shedding, best-effort "
                         "degradation and (with --tenant-rate) per-"
                         "tenant fairness (empty = plain FCFS)")
    ap.add_argument("--ttft", type=float, default=0.25,
                    help="interactive TTFT deadline in seconds (batch "
                         "gets 4x, best_effort 8x; 0 = no deadlines)")
    ap.add_argument("--tenants", type=int, default=1,
                    help="tenant ids drawn per request for the "
                         "fairness buckets")
    ap.add_argument("--tenant-rate", type=float, default=0.0,
                    help="per-tenant admission token-bucket refill "
                         "(tokens/tick; 0 = fairness off)")
    ap.add_argument("--hot-swap", action="store_true",
                    help="stream a second weight generation (fresh "
                         "init from seed+1000) into the live engine "
                         "during the run and flip atomically mid-"
                         "serve; swap accounting lands in metrics()"
                         "['swap']")
    ap.add_argument("--trace", action="store_true",
                    help="print the per-request decode trace")
    args = ap.parse_args()
    enable_compile_cache()

    slo_cfg, slo_tkw = None, {}
    if args.slo:
        ifrac, bfrac = parse_slo(args.slo)
        ttft = args.ttft if args.ttft > 0 else None
        slo_cfg = serve.SLOConfig(
            ttft_interactive=ttft,
            ttft_batch=4 * ttft if ttft else None,
            ttft_best_effort=8 * ttft if ttft else None,
            tenant_rate=args.tenant_rate,
            tenant_burst=2 * args.tenant_rate)
        slo_tkw = dict(interactive_frac=ifrac, batch_frac=bfrac,
                       deadline_interactive=slo_cfg.ttft_interactive,
                       deadline_batch=slo_cfg.ttft_batch,
                       deadline_best_effort=slo_cfg.ttft_best_effort,
                       n_tenants=args.tenants)

    eng, cfg = build_engine(
        args.arch, smoke=args.smoke, backend=args.backend,
        page_tokens=args.page_tokens,
        n_pages=args.n_pages, max_batch=args.max_batch,
        attn_impl=args.attn_impl, prefill_chunk=args.prefill_chunk,
        tick_tokens=args.tick_tokens, sample_seed=args.sample_seed,
        seed=args.seed, spec_k=args.spec_k, draft=args.draft,
        disagg=args.disagg, router=args.router, slo=slo_cfg)
    tcfg = serve.TrafficConfig(n_requests=args.requests, rate=args.rate,
                               vocab=cfg.vocab, seed=args.seed,
                               temperature=args.temperature,
                               top_k=args.top_k, top_p=args.top_p,
                               **slo_tkw)
    reqs = serve.make_requests(tcfg)
    if args.hot_swap:
        ctx = getattr(eng, "ctx", None) or eng.engines[0].ctx
        new_params = registry.build(cfg).init(
            jax.random.PRNGKey(args.seed + 1000), cfg, ctx)
        eng.begin_hot_swap(new_params)
    print(f"arch={cfg.name} backend={args.backend} "
          f"pages={args.n_pages}x{args.page_tokens} "
          f"batch={args.max_batch} chunk={args.prefill_chunk} "
          f"sampling=(T={args.temperature} k={args.top_k} "
          f"p={args.top_p}) spec=(k={args.spec_k} "
          f"draft={args.draft}) "
          f"topology={args.disagg or 'colocated'} router={args.router} "
          f"requests={len(reqs)}")
    done = eng.run(reqs)
    if args.trace:
        for r in sorted(done, key=lambda r: r.rid):
            print(f"  req{r.rid}: prompt[{r.n_prompt}] "
                  f"chunks={r.prefill_chunks} -> "
                  f"{r.out[:10]}{'...' if len(r.out) > 10 else ''} "
                  f"({len(r.out)} tokens, {r.preemptions} preemptions)")
    print(json.dumps(eng.metrics(), indent=2))


if __name__ == "__main__":
    main()
