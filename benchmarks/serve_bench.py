"""Serving benchmark — throughput / latency percentiles for the paged
continuous-batching engine, written to ``BENCH_serve.json`` at the REPO
ROOT (the bench trajectory the driver tracks):

    {"meta": {...},
     "results": [{"case", "arch", "backend", "attn_impl", "page_tokens",
                  "n_pages", "max_batch", "prefill_chunk", "sampling",
                  "temperature", "top_p", "requests", "tokens_out",
                  "throughput_tok_s", "latency_p50_s", "latency_p99_s",
                  "ttft_p50_s", "ttft_p99_s", "decode_p50_s",
                  "decode_p99_s", "preempted", "migrations"}, ...]}

Default sweep: page size x batch size x attention impl on the smoke
qwen3 config under the same seeded Poisson trace, plus a sampled
(top-p) sweep (``--sampling top_p`` rows), a chunked-vs-monolithic
prefill pair on the long-prompt mixed trace — the row pair that shows
chunked prefill protecting p99 decode latency — and SPECULATIVE-DECODE
rows (``spec_k > 0``, n-gram self-draft) on the REPEATED-PROMPT
workload, reporting ``spec_accept_rate`` and ``spec_tokens_per_tick``
(tokens one sequence's verify pass emits; > 1 = speculation beats
one-token-per-tick decode).  Attention-impl rows come in kernel/ref
PAIRS (``smoke``/``smoke_kernel``, ``p8_b4_ref``/``p8_b4_kernel``,
``repeated_spec_k2``/``repeated_spec_k2_kernel``) whose presence
``scripts/check_bench.py`` enforces, and so does the DISAGGREGATION
topology pair (``colocated``/``disagg_2p2d``): the same engine shape
and trace served monolithically vs split 2 prefill + 2 decode cells
with put-with-signal page handoff — disagg rows carry
``handoff_signals``/``handoff_quiets`` counters, and check_bench pins
``handoff_quiets`` to ZERO (per-transfer completion carries the whole
handoff load).  The CONTROL-PLANE pair (``router_host``/``router_amo``)
runs the same 2+2 disagg shape and trace with the router as the only
knob — host Python-loop scheduling vs lock-free CAS admission rings +
claim-word mailbox + symmetric page pool — and its amo row carries
``router_amos``/``router_quiets``/``steals``/``alloc_cas_retries``
(check_bench enforces the pair, equal token counts, and zero quiets on
the AMO path).

SLO rows (PR 10): the SATURATION sweep serves a fixed fleet-like class
mix (40% interactive / 20% batch / 40% best_effort, two tenants, tick-
unit deadlines) on the TICK clock at ramped arrival rates —
``sat_low`` .. ``sat_overload`` smoke endpoints, ``sat_r1/r2/r4`` ramp
rows in the full sweep — each row carrying per-class
``slo_attained_*`` / ``shed_*`` fields.  Because the tick clock makes
the whole schedule deterministic, check_bench gates these HARD:
interactive attainment >= 0.99 on every row (the protected SLO holds
through overload) and sheds land on best_effort ONLY; the full sweep
also records ``meta["saturation_knee_rate"]``, the rate where
best-effort shedding begins.  The HOT-SWAP pair
(``hot_swap_off``/``hot_swap_on``) serves one trace twice with the
in-flight weight swap as the only knob: the on row streams a second
weight generation between serving ticks and flips mid-run, and
check_bench pins equal token counts across the pair plus
``swap_extra_quiets == 0`` (the swap queue retires on per-transfer
signal/AMO waits, never a tick-global drain).  ``meta["sweep_cases"]``
lists every full-sweep case name under BOTH modes, so check_bench can
fail on committed rows the sweep no longer emits (RETIRED_CASES is the
allowlist).

``--smoke`` runs the smallest cases — one greedy, one
with the Pallas paged-attention KERNELS, one SAMPLED, one SPECULATIVE,
one DISAGGREGATED, the router pair, the saturation endpoints and the
hot-swap pair — so the `make verify` freshness
gate covers all serving modes end-to-end; the full sweep emits
the same smoke rows under the same case names, which is what lets
``scripts/check_bench.py`` match fresh smoke rows against the
committed file.

    PYTHONPATH=src python benchmarks/serve_bench.py [--smoke]
    PYTHONPATH=src python benchmarks/serve_bench.py --sampling top_p

On CPU the numbers measure the engine/scheduler structure, not
accelerator decode throughput (meta records the platform).
"""
import argparse
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, "BENCH_serve.json")

SAMPLING = {                      # name -> (temperature, top_k, top_p)
    "greedy": (0.0, 0, 1.0),
    "top_k": (0.8, 8, 1.0),
    "top_p": (0.8, 0, 0.9),
}


def repeated_requests(n_requests, vocab, rate, seed, *, max_new=16,
                      sampling="greedy"):
    """The repeated-prompt workload speculation feeds on: periodic
    prompts (a short random pattern tiled to 12 tokens) that drive
    greedy decoding into self-repetition, where the n-gram self-draft
    proposer earns its accept rate.  Deterministic given the seed."""
    import numpy as np

    from repro import serve

    temp, top_k, top_p = SAMPLING[sampling]
    sp = serve.SamplingParams(temperature=temp, top_k=top_k, top_p=top_p)
    reqs, t = [], 0.0
    for i in range(n_requests):
        rng = np.random.RandomState(seed * 1000 + i)
        pattern = rng.randint(0, vocab, size=3 + i % 3).tolist()
        reqs.append(serve.Request(
            rid=i, prompt=(pattern * 8)[:12], max_new=max_new,
            t_arrive=t, sampling=sp))
        t += float(rng.exponential(1.0 / rate))
    return reqs


def audit_case_isolation(eng):
    """Per-case pool isolation: every case re-constructs its engine,
    and the engine's page pools must end SELF-CONTAINED — each cell's
    pages all back on its own free list/stack (or parked in that cell's
    prefix index), so a bench row can never alias page ids into the
    next case's freshly-built pools.  Runs after metrics are read and
    fails the bench loudly on a leak (a quiet leak here is exactly the
    cross-case aliasing the topology/router pairs would then measure)."""
    for cell in getattr(eng, "engines", [eng]):
        kv = cell.kv
        parked = sum(len(pages) for _, pages in kv._prefix.values())
        free = kv.n_free()
        if free + parked != kv.n_pages - 1:
            raise SystemExit(
                f"serve_bench: case left a non-conserved pool on a "
                f"{cell.role} cell — {free} free + {parked} prefix-"
                f"parked != {kv.n_pages - 1} grantable pages")


def run_case(case, arch, backend, attn_impl, page_tokens, n_pages,
             max_batch, n_requests, rate, seed, *, sampling="greedy",
             prefill_chunk=8, tick_tokens=0, long_frac=0.25,
             spec_k=0, workload="poisson", warmup=True, disagg="",
             router="host", slo=None, slo_traffic=None, hot_swap=None,
             clock="wall"):
    from repro import serve
    from repro.analysis import shmemcheck
    from repro.launch.serve import build_engine

    # isolate the (module-global) shmemcheck hooks per case: the
    # previous case's engine is garbage by now and CPython recycles
    # object ids, so stale per-queue checker state could alias onto
    # this case's freshly-built pool/mailbox queues
    shmemcheck.reset()
    eng, cfg = build_engine(arch, smoke=True, backend=backend,
                            page_tokens=page_tokens, n_pages=n_pages,
                            max_batch=max_batch, attn_impl=attn_impl,
                            prefill_chunk=prefill_chunk,
                            tick_tokens=tick_tokens, seed=seed,
                            spec_k=spec_k, disagg=disagg, router=router,
                            slo=(serve.SLOConfig(**slo)
                                 if slo is not None else None))
    temp, top_k, top_p = SAMPLING[sampling]

    def trace(seed_, n):
        if workload == "repeated":
            return repeated_requests(n, cfg.vocab, rate, seed_,
                                     sampling=sampling)
        tcfg = serve.TrafficConfig(n_requests=n, rate=rate,
                                   vocab=cfg.vocab, seed=seed_,
                                   long_frac=long_frac,
                                   temperature=temp, top_k=top_k,
                                   top_p=top_p, **(slo_traffic or {}))
        return serve.make_requests(tcfg)

    if warmup:
        # trigger every jit compile (prefill window, decode/verify,
        # sampler) on a throwaway mini-trace, then measure a clean run
        # on the same engine: rows reflect engine structure, not XLA
        # compiles
        eng.run(trace(seed + 1, 3), clock="wall")
        eng.reset_metrics()
    if hot_swap:
        # the hot_swap_on row: stream a SECOND weight generation (a
        # fresh init from seed+1000, the same derivation the CLI's
        # --hot-swap uses) into the live engine while the measured
        # trace is being served, flipping mid-run.  Token COUNTS must
        # match the off row exactly (the swap never sheds or stalls a
        # request) and the swap queue must retire on per-transfer
        # waits alone: swap_extra_quiets stays 0
        from repro.models import registry
        import jax as _jax
        ctx = getattr(eng, "ctx", None) or eng.engines[0].ctx
        new_params = registry.build(cfg).init(
            _jax.random.PRNGKey(seed + 1000), cfg, ctx)
        eng.begin_hot_swap(new_params)
    t0 = time.perf_counter()
    # explicit clock: ServeEngine and DisaggEngine default to different
    # clocks, and a topology row pair must share one.  SLO/saturation
    # and hot-swap rows run clock="tick" — deadlines and arrivals in
    # scheduler ticks — so attainment/shed numbers are DETERMINISTIC
    # and check_bench can gate them hard (>= 0.99), immune to CI wall-
    # clock jitter
    eng.run(trace(seed, n_requests), clock=clock)
    wall = time.perf_counter() - t0
    m = eng.metrics()
    row = {
        "case": case, "arch": cfg.name, "backend": backend,
        "attn_impl": attn_impl, "page_tokens": page_tokens,
        "n_pages": n_pages, "max_batch": max_batch,
        "prefill_chunk": prefill_chunk, "rate_req_s": rate,
        "sampling": sampling, "temperature": temp, "top_p": top_p,
        "workload": workload,
        "requests": m["requests"], "tokens_out": m["tokens_out"],
        "wall_s": round(wall, 4),
        "throughput_tok_s": round(m["throughput_tok_s"], 2),
        "latency_p50_s": round(m["latency_p50_s"], 4),
        "latency_p99_s": round(m["latency_p99_s"], 4),
        "ttft_p50_s": round(m["ttft_p50_s"], 4),
        "ttft_p99_s": round(m["ttft_p99_s"], 4),
        "decode_p50_s": round(m["decode_p50_s"], 4),
        "decode_p99_s": round(m["decode_p99_s"], 4),
        "preempted": m["sched"]["preempted"],
        "migrations": m["kv"]["migrations"],
        "spec_k": spec_k,
        "spec_accept_rate": round(m["spec"]["accept_rate"], 4),
        "spec_tokens_per_tick": round(m["spec"]["tokens_per_tick"], 4),
        "spec_drafted": m["spec"]["drafted"],
        "spec_emitted": m["spec"]["emitted"],
        "topology": disagg or "colocated",
        "router": router,
        "clock": clock,
    }
    if slo is not None:
        # per-class SLO fields only exist on SLO rows — check_bench
        # keys its saturation gate off slo_attained_interactive's
        # presence.  Shed counters land per class so the gate can pin
        # "sheds hit best_effort only"
        s = m["slo"]
        for cls in ("interactive", "batch", "best_effort"):
            row[f"slo_attained_{cls}"] = round(
                s["attained"].get(cls, 1.0), 4)
            row[f"shed_{cls}"] = s["shed"].get(cls, 0)
            row[f"finished_{cls}"] = s["finished"].get(cls, 0)
        pol = s.get("policy") or {}
        row["rate_deferred"] = pol.get("rate_deferred", 0)
        row["degraded_chunks"] = pol.get("degraded_chunks", 0)
    if hot_swap is not None:
        # both rows of the hot_swap pair carry the swap counters (the
        # off row all-zero): check_bench keys the pair gate off the
        # "hot_swap" field's presence
        sw = m["swap"]
        row.update(hot_swap=int(bool(hot_swap)),
                   swap_flips=sw["flips"],
                   swap_ticks=sw["swap_ticks"],
                   swap_batches=sw["swap_batches"],
                   swap_bytes=sw["swap_bytes"],
                   swap_extra_quiets=sw["swap_extra_quiets"])
    if disagg:
        # handoff counters only exist on disagg rows — check_bench
        # keys its topology gate off their presence.  The router/
        # allocator counters ride along (all zero in host mode): the
        # amo row's router_quiets is the lock-free no-barrier pin, and
        # steals/alloc_cas_retries are the contention trajectory
        h = m["handoff"]
        row.update(handoff_tickets=h["handoff_tickets"],
                   handoff_pages=h["handoff_pages"],
                   handoff_signals=h["handoff_signals"],
                   handoff_waits=h["handoff_waits"],
                   handoff_quiets=h["handoff_quiets"],
                   router_amos=h["router_amos"],
                   router_quiets=h["router_quiets"],
                   steals=h["steals"],
                   alloc_cas_retries=h["alloc_cas_retries"])
    audit_case_isolation(eng)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny cases — greedy, kernel, sampled, "
                         "speculative, disagg, router host/amo pair — "
                         "refreshed IN PLACE inside the committed file "
                         "(verify-gate freshness)")
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=16.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sampling", default="top_p",
                    choices=sorted(SAMPLING),
                    help="policy for the sampled sweep rows")
    args = ap.parse_args()

    import jax

    # (case, backend, impl, page_tokens, n_pages, max_batch, requests,
    #  sampling, extra engine kwargs)
    # the sampled smoke row must actually be non-greedy — it is what
    # gates the sampled path (top_k_merge + categorical draw) in `make
    # verify`; the spec smoke row gates the whole draft->verify->
    # accept->rewind loop (repeated-prompt workload, so its accept
    # rate is structurally > 0 and check_bench can enforce that).
    # SMOKE_CASES also open the full sweep under the SAME names: the
    # committed full file always contains the rows a fresh --smoke run
    # is compared against.
    sampled = args.sampling if args.sampling != "greedy" else "top_p"
    # the saturation sweep's shared SLO traffic shape: a fleet-like
    # class mix on the TICK clock (rate = requests/tick, deadlines in
    # ticks).  Interactive deadlines are the protected SLO; the tight
    # best-effort deadline is the pressure valve that starts shedding
    # once arrivals outrun capacity
    SAT_TRAFFIC = {"interactive_frac": 0.4, "batch_frac": 0.2,
                   "deadline_interactive": 100.0,
                   "deadline_batch": 200.0,
                   "deadline_best_effort": 6.0, "n_tenants": 2}
    SAT_KW = {"slo": {}, "slo_traffic": SAT_TRAFFIC, "clock": "tick"}
    SMOKE_CASES = [
        ("smoke", "xla", "ref", 4, 32, 3, 6, "greedy", {}),
        # the attn_impl kernel/ref PAIR: same engine shape as "smoke"
        # with the Pallas paged kernels on all three call sites
        # (decode + prefill/verify windows); check_bench enforces the
        # pair's presence
        ("smoke_kernel", "xla", "kernel", 4, 32, 3, 6, "greedy", {}),
        ("smoke_sampled", "xla", "ref", 4, 32, 3, 6, sampled, {}),
        ("smoke_spec", "xla", "ref", 4, 32, 3, 6, "greedy",
         {"spec_k": 3, "workload": "repeated"}),
        # the disagg smoke row: prefill and decode in separate cells
        # with the put-with-signal page handoff on the hot path — its
        # handoff_quiets counter is what check_bench pins to zero
        ("smoke_disagg", "xla", "ref", 4, 32, 3, 6, "greedy",
         {"disagg": "1+1"}),
        # the control-plane pair: identical 2+2 topology and trace,
        # the router is the ONLY knob — host Python-loop scheduling
        # vs CAS-arbitrated admission rings + claim-word mailbox +
        # symmetric page pools.  Token streams are bit-identical
        # (tier-1 pins the streams themselves; check_bench pins pair
        # presence, equal token counts, and zero quiets on both the
        # handoff and the router/allocator queues of the amo row)
        ("router_host", "xla", "ref", 4, 48, 3, 6, "greedy",
         {"disagg": "2+2"}),
        ("router_amo", "xla", "ref", 4, 48, 3, 6, "greedy",
         {"disagg": "2+2", "router": "amo"}),
        # the saturation pair the SLO gate rides on: the same class
        # mix under light load (sat_low) and overload (sat_overload —
        # arrivals far beyond tick capacity).  Interactive attainment
        # must hold >= 0.99 on BOTH; sheds may only land on
        # best_effort.  The full sweep ramps the rate between them
        ("sat_low", "xla", "ref", 4, 32, 3, 12, "greedy",
         dict(SAT_KW, rate=0.5)),
        ("sat_overload", "xla", "ref", 4, 32, 3, 12, "greedy",
         dict(SAT_KW, rate=8.0)),
        # the hot-swap pair: identical shape and trace on the tick
        # clock, the in-flight weight swap the ONLY knob.  check_bench
        # pins equal token counts across the pair and zero extra
        # global drains on the swap queue
        ("hot_swap_off", "xla", "ref", 4, 32, 3, 6, "greedy",
         {"hot_swap": False, "clock": "tick"}),
        ("hot_swap_on", "xla", "ref", 4, 32, 3, 6, "greedy",
         {"hot_swap": True, "clock": "tick"}),
    ]
    n = args.requests
    FULL_CASES = SMOKE_CASES + [
            ("p4_b2_ref", "xla", "ref", 4, 48, 2, n, "greedy", {}),
            ("p4_b4_ref", "xla", "ref", 4, 48, 4, n, "greedy", {}),
            ("p8_b4_ref", "xla", "ref", 8, 32, 4, n, "greedy", {}),
            ("p8_b4_kernel", "xla", "kernel", 8, 32, 4, n, "greedy", {}),
            ("p8_b4_posh", "posh", "ref", 8, 32, 4, n, "greedy", {}),
            # sampled sweep: the same engine shapes, non-greedy traffic
            ("p4_b4_" + args.sampling, "xla", "ref", 4, 48, 4, n,
             args.sampling, {}),
            ("p8_b4_" + args.sampling, "xla", "ref", 8, 32, 4, n,
             args.sampling, {}),
            # chunked-vs-monolithic prefill on the long-heavy mixed
            # trace under load: the structural probe for the token
            # budget protecting per-token DECODE latency (decode_p99 =
            # inter-token gaps, which a batch-mate's monolithic prompt
            # admission stretches).  NOTE: on the 2-layer CPU smoke
            # model the fused prefill window makes even a whole-prompt
            # call ~one decode tick, so the contrast here is within
            # noise — it grows with prefill compute per prompt (real
            # depths/lengths); the budget mechanics themselves are
            # pinned by the tier-1 scheduler tests.
            ("mixed_long_chunked", "xla", "ref", 4, 48, 4, 3 * n,
             "greedy", {"prefill_chunk": 8, "tick_tokens": 16,
                        "long_frac": 0.5, "rate": 32.0}),
            ("mixed_long_monolithic", "xla", "ref", 4, 48, 4, 3 * n,
             "greedy", {"prefill_chunk": 24, "long_frac": 0.5,
                        "rate": 32.0}),
            # speculative decoding on the repeated-prompt workload:
            # the spec_on/spec_off pair isolates what draft->verify
            # buys on self-repeating greedy streams (accept_rate and
            # tokens_per_tick are the structural wins; CPU wall time
            # grows with window width, the tick count shrinks), plus a
            # sampled spec row (acceptance is rarer — the draft must
            # hit the counter-RNG draw — but streams stay identical)
            ("repeated_spec_off", "xla", "ref", 4, 48, 4, n, "greedy",
             {"workload": "repeated"}),
            ("repeated_spec_k2", "xla", "ref", 4, 48, 4, n, "greedy",
             {"workload": "repeated", "spec_k": 2}),
            # the verify-window kernel under speculation: pairs with
            # repeated_spec_k2 the way p8_b4_kernel pairs with
            # p8_b4_ref (streams identical, only the attn impl moves)
            ("repeated_spec_k2_kernel", "xla", "kernel", 4, 48, 4, n,
             "greedy", {"workload": "repeated", "spec_k": 2}),
            ("repeated_spec_k4", "xla", "ref", 4, 48, 4, n, "greedy",
             {"workload": "repeated", "spec_k": 4}),
            ("repeated_spec_k4_" + args.sampling, "xla", "ref", 4, 48,
             4, n, args.sampling,
             {"workload": "repeated", "spec_k": 4}),
            # the disaggregation row pair: identical engine shape and
            # trace, topology is the ONLY knob — what page handoff
            # costs (TTFT, p99 decode) against the colocated engine,
            # with the signal/quiet counters showing the handoff load
            # rides per-transfer completion alone
            ("colocated", "xla", "ref", 4, 48, 3, n, "greedy", {}),
            ("disagg_2p2d", "xla", "ref", 4, 48, 3, n, "greedy",
             {"disagg": "2+2"}),
            # the saturation RAMP between the smoke endpoints: arrival
            # rate doubles per row, same class mix/deadlines/shape.
            # The knee — the first rate where best_effort starts
            # shedding — lands in meta["saturation_knee_rate"]
            ("sat_r1", "xla", "ref", 4, 32, 3, 12, "greedy",
             dict(SAT_KW, rate=1.0)),
            ("sat_r2", "xla", "ref", 4, 32, 3, 12, "greedy",
             dict(SAT_KW, rate=2.0)),
            ("sat_r4", "xla", "ref", 4, 32, 3, 12, "greedy",
             dict(SAT_KW, rate=4.0)),
        ]
    # the full sweep's case-name roster, emitted under BOTH modes: the
    # stale-case gate in check_bench compares the committed file
    # against this list, so retiring a case from the sweep without
    # allowlisting it in RETIRED_CASES fails verify loudly instead of
    # leaving a zombie row the gates still "check"
    sweep_cases = [c[0] for c in FULL_CASES]
    cases = SMOKE_CASES if args.smoke else FULL_CASES
    results = []
    for case, backend, impl, pt, np_, mb, nreq, sampling, extra in cases:
        extra = dict(extra)
        rate = extra.pop("rate", args.rate)
        row = run_case(case, args.arch, backend, impl, pt, np_, mb, nreq,
                       rate, args.seed, sampling=sampling, **extra)
        results.append(row)
        spec = (f"  accept {row['spec_accept_rate']:.2f} "
                f"tok/tick {row['spec_tokens_per_tick']:.2f}"
                if row["spec_k"] else "")
        if row["topology"] != "colocated":
            spec += (f"  [{row['topology']}] signals "
                     f"{row['handoff_signals']} quiets "
                     f"{row['handoff_quiets']}")
        if row["router"] == "amo":
            spec += (f"  [amo] amos {row.get('router_amos', 0)} "
                     f"steals {row.get('steals', 0)} "
                     f"cas_retries {row.get('alloc_cas_retries', 0)}")
        if "slo_attained_interactive" in row:
            spec += (f"  [slo] int {row['slo_attained_interactive']:.2f}"
                     f" shed_be {row['shed_best_effort']}")
        if "hot_swap" in row:
            spec += (f"  [swap {'on' if row['hot_swap'] else 'off'}] "
                     f"flips {row['swap_flips']} extra_quiets "
                     f"{row['swap_extra_quiets']}")
        print(f"{case:>22}: {row['throughput_tok_s']:8.1f} tok/s  "
              f"p50 {row['latency_p50_s']*1e3:7.1f} ms  "
              f"p99 {row['latency_p99_s']*1e3:7.1f} ms  "
              f"dec99 {row['decode_p99_s']*1e3:7.1f} ms  "
              f"preempt {row['preempted']}{spec}")

    if args.smoke and os.path.exists(OUT):
        # a smoke run REFRESHES its rows inside the committed file
        # instead of truncating the full-sweep trajectory down to 3
        # rows (a `make verify` must never destroy the other
        # baseline rows check_bench guards).  An unreadable existing
        # file fails LOUDLY here — quietly starting over would be
        # exactly the destruction this branch exists to prevent.
        with open(OUT) as f:
            old = json.load(f)
        fresh = {r["case"]: r for r in results}
        merged = [fresh.pop(r["case"], r)
                  for r in old.get("results", [])]
        results = merged + list(fresh.values())
        meta = old.get("meta", {})
        meta["smoke_refreshed"] = True
    else:
        meta = {"platform": jax.default_backend(),
                "smoke": bool(args.smoke), "rate_req_s": args.rate,
                "seed": args.seed, "sampling_sweep": args.sampling,
                "warmup": True,
                "note": "CPU rows measure engine/scheduler structure, "
                        "not accelerator decode throughput"}
    meta["sweep_cases"] = sweep_cases
    sat = sorted((r for r in results
                  if r["case"].startswith("sat_")
                  and "slo_attained_interactive" in r),
                 key=lambda r: r["rate_req_s"])
    if not args.smoke and sat:
        # the knee: the lowest arrival rate at which the policy starts
        # shedding best-effort traffic (interactive attainment is
        # gated to hold across the WHOLE ramp, so the knee is where
        # degradation begins, not where the protected SLO breaks)
        knee = next((r["rate_req_s"] for r in sat
                     if r["shed_best_effort"] > 0), None)
        meta["saturation_knee_rate"] = knee
        meta["saturation_rates"] = [r["rate_req_s"] for r in sat]
    with open(OUT, "w") as f:
        json.dump({"meta": meta, "results": results}, f, indent=1)
    print(f"wrote {OUT} ({len(results)} rows)")


if __name__ == "__main__":
    main()
