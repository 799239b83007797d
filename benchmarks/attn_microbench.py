"""Paged-attention microbenchmark — the numbers behind ``choose_block``.

Sweeps the DECODE and PREFILL-WINDOW Pallas kernels against their jnp
oracles over window / page / dtype shapes and writes ``BENCH_attn.json``
at the REPO ROOT (a bench trajectory the driver tracks):

    {"meta": {...},
     "results": [{"case", "kind", "window", "page_tokens", "slots",
                  "heads", "kv_heads", "head_dim", "dtype", "impl",
                  "block_q", "us_per_call", "max_err_vs_ref",
                  "err_tol"}, ...],
     "chosen": [{"window", "dtype", "chosen_block_q",
                 "candidates_us", "fastest_block_q"}, ...]}

Every kernel row records ``max_err_vs_ref`` on the exact inputs it was
timed on — parity is part of the trajectory, so a numerics regression
fails ``scripts/check_bench.py`` even if timing looks fine.  The
``chosen`` section times every q-block candidate per (window, dtype)
and records what ``paged_attention.choose_block`` picks next to the
measured fastest — the cross-check for the §4.5.4 dispatch ladder
(re-tune the ladder from this file, the same loop as
``DispatchTable.tuned_from_bench`` for the comm schedules).

``--smoke`` runs one decode pair and two prefill-window pairs (the
chunk shape and the spec-verify shape) and refreshes those rows IN
PLACE inside the committed file — the `make verify` freshness gate.
The full sweep emits the same case names, so fresh smoke rows always
find their committed counterparts.

    PYTHONPATH=src python benchmarks/attn_microbench.py [--smoke]

Off-TPU the kernels run the Pallas interpreter: rows measure kernel
STRUCTURE (and parity), not accelerator throughput — meta records the
platform, and check_bench's timing floor absorbs the noise.
"""
import argparse
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, "BENCH_attn.json")

B, H, HKV, D = 4, 4, 2, 16
DTYPES = {"f32": "float32", "bf16": "bfloat16"}
ERR_TOL = {"f32": 1e-5, "bf16": 3e-2}

# (kind, window, page_tokens, slots, dtype-tag); smoke = the shapes the
# serving engine actually runs per tick (prefill chunk + verify window
# + decode), full adds the size/dtype axes behind the dispatch ladder
SMOKE_CASES = [
    ("decode", None, 4, 8, "f32"),
    ("prefill", 8, 4, 8, "f32"),       # the default chunked-prefill tick
    ("prefill", 4, 4, 8, "f32"),       # the (B, spec_k+1) verify window
]
FULL_CASES = SMOKE_CASES + [
    ("decode", None, 8, 4, "f32"),
    ("decode", None, 4, 8, "bf16"),
    ("prefill", 8, 8, 4, "f32"),
    ("prefill", 16, 4, 8, "f32"),
    ("prefill", 32, 8, 8, "f32"),
    ("prefill", 8, 4, 8, "bf16"),
    ("prefill", 32, 8, 8, "bf16"),
]
CHOSEN_SWEEP = [(8, "f32"), (32, "f32"), (64, "f32"), (32, "bf16")]
CANDIDATES = (8, 16, 32, 64)


def case_name(kind, window, page_tokens, dt):
    if kind == "decode":
        return f"decode_p{page_tokens}_{dt}"
    return f"prefill_w{window}_p{page_tokens}_{dt}"


def _timeit(fn, warmup=1, reps=3):
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6      # us/call


def _inputs(kind, window, page_tokens, slots, dtype, seed=0):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(seed)
    n_pages = B * slots + 1
    kp = jnp.asarray(rng.randn(n_pages, page_tokens, HKV, D)).astype(dtype)
    vp = jnp.asarray(rng.randn(n_pages, page_tokens, HKV, D)).astype(dtype)
    pool = jnp.stack([kp, vp], axis=1)[:, :, None]   # one layer
    bt = jnp.asarray(rng.permutation(np.arange(1, n_pages))
                     .reshape(B, slots).astype(np.int32))
    span = page_tokens * slots
    if kind == "decode":
        q = jnp.asarray(rng.randn(B, H, D)).astype(dtype)
        lens = jnp.asarray(rng.randint(1, span + 1, B), jnp.int32)
        return q, pool, 0, bt, lens
    q = jnp.asarray(rng.randn(B, window, H, D)).astype(dtype)
    start = jnp.asarray(rng.randint(0, span - window + 1, B), jnp.int32)
    n_tok = jnp.asarray(rng.randint(1, window + 1, B), jnp.int32)
    return q, pool, 0, bt, start, n_tok


def run_pair(kind, window, page_tokens, slots, dt, *, block_q=None):
    """Time kernel + ref on identical inputs; returns the two rows."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    dtype = jnp.dtype(DTYPES[dt])
    args = _inputs(kind, window, page_tokens, slots, dtype)
    op = ops.paged_attention if kind == "decode" \
        else ops.paged_prefill_attention
    kw = {} if kind == "decode" else {"block_q": block_q}
    ker = lambda: op(*args, impl="kernel", **kw)
    ref = lambda: op(*args, impl="ref")
    err = float(np.max(np.abs(np.asarray(ker(), np.float32)
                              - np.asarray(ref(), np.float32))))
    name = case_name(kind, window, page_tokens, dt)
    common = dict(kind=kind, window=window, page_tokens=page_tokens,
                  slots=slots, heads=H, kv_heads=HKV, head_dim=D,
                  dtype=DTYPES[dt])
    return [
        dict(case=name + "_kernel", impl="kernel", block_q=block_q,
             us_per_call=round(_timeit(ker), 1), max_err_vs_ref=err,
             err_tol=ERR_TOL[dt], **common),
        dict(case=name + "_ref", impl="ref", block_q=None,
             us_per_call=round(_timeit(ref), 1), max_err_vs_ref=0.0,
             err_tol=ERR_TOL[dt], **common),
    ]


def sweep_chosen():
    """Time every q-block candidate per (window, dtype) and record the
    dispatch ladder's pick next to the measured fastest."""
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels import paged_attention as pa

    out = []
    for window, dt in CHOSEN_SWEEP:
        dtype = jnp.dtype(DTYPES[dt])
        args = _inputs("prefill", window, 8, max(2, window // 4), dtype)
        cand_us = {}
        for bq in CANDIDATES:
            if bq > -(-window // 8) * 8 * 2:     # pointless oversizing
                continue
            cand_us[str(bq)] = round(_timeit(
                lambda: ops.paged_prefill_attention(
                    *args, impl="kernel", block_q=bq)), 1)
        fastest = min(cand_us, key=cand_us.get)
        out.append(dict(window=window, dtype=DTYPES[dt],
                        chosen_block_q=pa.choose_block(window, dtype),
                        candidates_us=cand_us,
                        fastest_block_q=int(fastest)))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="decode + chunk + verify pairs only, rows "
                         "refreshed IN PLACE inside the committed file")
    args = ap.parse_args()

    import jax

    cases = SMOKE_CASES if args.smoke else FULL_CASES
    results = []
    for kind, window, pt, slots, dt in cases:
        rows = run_pair(kind, window, pt, slots, dt)
        results.extend(rows)
        k, r = rows
        print(f"{k['case']:>26}: kernel {k['us_per_call']:10.1f} us  "
              f"ref {r['us_per_call']:10.1f} us  "
              f"err {k['max_err_vs_ref']:.2e}")

    if args.smoke and os.path.exists(OUT):
        # refresh smoke rows inside the committed trajectory instead of
        # truncating the full sweep (same contract as serve_bench; an
        # unreadable file fails LOUDLY rather than starting over)
        with open(OUT) as f:
            old = json.load(f)
        fresh = {r["case"]: r for r in results}
        merged = [fresh.pop(r["case"], r)
                  for r in old.get("results", [])]
        results = merged + list(fresh.values())
        chosen = old.get("chosen", [])
        meta = old.get("meta", {})
        meta["smoke_refreshed"] = True
    else:
        chosen = sweep_chosen()
        for c in chosen:
            print(f"chosen w={c['window']:>3} {c['dtype']}: ladder "
                  f"{c['chosen_block_q']} fastest {c['fastest_block_q']} "
                  f"{c['candidates_us']}")
        meta = {"platform": jax.default_backend(),
                "smoke": bool(args.smoke),
                "shape": {"B": B, "H": H, "Hkv": HKV, "D": D},
                "note": "off-TPU rows run the Pallas interpreter: they "
                        "measure kernel structure and parity, not "
                        "accelerator throughput"}
    with open(OUT, "w") as f:
        json.dump({"meta": meta, "results": results, "chosen": chosen},
                  f, indent=1)
    print(f"wrote {OUT} ({len(results)} rows)")


if __name__ == "__main__":
    main()
