"""The harness: one process builds a cell's engine through the
program's normal path, warms the cell's own shapes, drives
``ServeEngine.tick`` for a fixed window, reads the metrics the cell
lists and decides ``correct`` against the plain reference.

Everything that belongs to one configuration, architecture family,
traffic mix or metric is a file found by name:
``bench/configs/<config>.json``, ``bench/families/<family>.py`` (the
configuration's ``family``), ``bench/traffic/<mix>.json``,
``bench/metrics/<base>.py`` (``base`` is a metric's name up to its
first ``.``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from collections import defaultdict
from pathlib import Path

import numpy as np

from . import check, costs, families, trace_reduce, traffic

TRACE_S = 10.0            # device trace: the first seconds of the window
STEADY_LIMIT_S = 240.0    # longest a steady set may take to prefill
clock = time.perf_counter


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


# ----------------------------------------------------------------------
# the cell, from BENCHMARK.json and its files
# ----------------------------------------------------------------------
def load_cell(root: Path, workload: str, bench: dict | None = None) -> dict:
    bench = bench or json.loads((root / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfg = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    conf = json.loads((root / cfg["file"]).read_text())
    mix = traffic.load(traffic.mix_path(root, wl["traffic"]))

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return {"root": root, "workload": wl, "conf": conf, "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def _sample_seed(seed: int) -> int:
    return int(traffic._rng(seed, 5).integers(0, 2**31 - 1))


# ----------------------------------------------------------------------
# the engine
# ----------------------------------------------------------------------
class Recorder:
    """The engine's execution substrate, unchanged, with the inputs of
    every prefill and decode call noted (the work those calls do)."""

    def __init__(self, inner):
        self.inner = inner
        self.calls: list = []

    def prefill(self, pool, ids, start, n_tok, bt, samp):
        t = clock()
        toks, pool = self.inner.prefill(pool, ids, start, n_tok, bt, samp)
        self.calls.append(("prefill", t, np.array(start), np.array(n_tok)))
        return toks, pool

    def decode(self, pool, tokens, pos, bt, lens, samp):
        t = clock()
        toks, pool = self.inner.decode(pool, tokens, pos, bt, lens, samp)
        self.calls.append(("decode", t, np.array(lens)))
        return toks, pool

    def __getattr__(self, name):
        return getattr(self.inner, name)


def build(cell: dict, seed: int, devices, wrap=None):
    """(engine, meta) for the cell, weights made on the device from
    ``seed``.  ``wrap`` (tests only) wraps the execution substrate."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro import compat, serve
    from repro.core import SymmetricHeap
    from repro.models import registry
    from repro.parallel.ctx import ParallelCtx

    from . import weights

    conf, s = cell["conf"], cell["conf"]["serve"]
    tp = int(conf["tp"])
    dtype = jnp.dtype(conf["dtype"])
    family = families.load(cell["root"], conf)
    cfg = family.arch_config(conf)
    ctx = ParallelCtx(dp_size=1, tp_size=tp, sp=False, remat=False,
                      backend=conf["comm_backend"], param_dtype=dtype,
                      compute_dtype=dtype)
    api = registry.build(cfg)
    want = family.layout(conf, tp, dtype)
    have = jax.eval_shape(lambda k: api.init(k, cfg, ctx.with_(tp_size=1)),
                          jax.random.PRNGKey(0))
    if jax.tree.map(lambda a: (a.shape, a.dtype), have) != \
            jax.tree.map(lambda a: (a.shape, a.dtype), want):
        raise RuntimeError(
            f"the program's parameter layout differs from the one "
            f"{families.path(cell['root'], conf)} regenerates")
    scfg = serve.ServeConfig(
        page_tokens=s["page_tokens"], n_pages=s["n_pages"],
        max_batch=s["max_batch"], max_seq=s["max_seq"],
        prefill_chunk=s["prefill_chunk"], tick_tokens=s["tick_tokens"],
        attn_impl=s["attn_impl"], kv_dtype=dtype,
        sample_seed=_sample_seed(seed))
    if tp == 1:
        with jax.default_device(devices[0]):
            params = weights.make(want, seed, cfg.n_layers)
            eng = serve.ServeEngine(params, cfg, ctx, scfg)
    else:
        mesh = compat.make_mesh((1, tp), ("data", "model"),
                                devices=devices[:tp])
        pspecs = api.specs(cfg, ctx)
        shard = jax.tree.map(lambda p: NamedSharding(mesh, p), pspecs,
                             is_leaf=lambda x: isinstance(x, P))
        params = weights.make(want, seed, cfg.n_layers, shard)
        kv = serve.PagedKVCache(
            SymmetricHeap(("data", "model")), n_layers=cfg.n_layers,
            kv_heads=cfg.kv_per_rank(tp), head_dim=cfg.head_dim,
            n_pages=scfg.n_pages, page_tokens=scfg.page_tokens, dtype=dtype)
        exec_ = serve.MeshExec(params, pspecs, cfg, ctx, scfg, kv, mesh)
        eng = serve.ServeEngine(params, cfg, ctx, scfg, kv=kv, exec_=exec_)
    inner = wrap(eng.exec) if wrap else eng.exec
    eng.exec = Recorder(inner)
    jax.block_until_ready((params, eng.pool))
    return eng, {"cfg": cfg, "scfg": scfg, "tp": tp}


def warm(eng) -> None:
    """Compile and run the cell's prefill and decode programs once at
    the served shapes, on empty slots (their writes land in the null
    page)."""
    import jax

    from repro import serve
    sc = eng.scfg
    B, C, S = sc.max_batch, sc.prefill_chunk, sc.table_slots
    z = lambda *shape: np.zeros(shape, np.int32)          # noqa: E731
    samp = serve.batch_state([], B, sc.sample_seed)
    ex = eng.exec.inner
    toks, eng.pool = ex.prefill(eng.pool, z(B, C), z(B), z(B), z(B, S), samp)
    np.asarray(toks)
    toks, eng.pool = ex.decode(eng.pool, z(B), z(B), z(B, S), z(B), samp)
    np.asarray(toks)
    jax.block_until_ready(eng.pool)


# ----------------------------------------------------------------------
# the window
# ----------------------------------------------------------------------
@dataclasses.dataclass
class Rec:
    spec: object
    req: object               # the program's Request; None if never sent
    due: float
    admitted: float | None = None
    first: float | None = None
    finished: float | None = None
    stamps: list = dataclasses.field(default_factory=list)


def _spans(on: bool):
    """Host spans in the profiler's trace; no-ops when not tracing."""
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def drive(eng, tr: traffic.Traffic, mix: dict, seconds: float,
          trace_dir: Path | None = None) -> dict:
    """Serve the mix for ``ramp_s`` and then the ``seconds`` window;
    every delivered token is stamped after the tick that produced it.
    Times are ``clock()`` seconds."""
    from repro import serve
    span = _spans(trace_dir is not None)
    recs: dict = {}
    live: dict = {}
    late: list = []
    gaps: list = []
    pool_use: list = []             # share of the KV pool held, per tick
    closed = mix["loop"] == "closed"
    B = eng.scfg.max_batch
    ramp = float(mix.get("ramp_s", 0.0))

    def submit(spec, due, now):
        sp = (serve.GREEDY if spec.greedy else serve.SamplingParams(
            temperature=spec.temperature, top_p=spec.top_p))
        r = serve.Request(rid=spec.rid, prompt=list(spec.prompt),
                          max_new=spec.max_new, t_arrive=due - T0,
                          sampling=sp)
        eng.submit(r)
        recs[spec.rid] = live[spec.rid] = Rec(spec, r, due)
        late.append(now - due)

    T0 = clock()
    W0, W1 = T0 + ramp, T0 + ramp + seconds
    k = 0                                   # next request of the stream
    if closed:
        for k in range(B):
            submit(tr.request(k), T0, T0)
        k = B
    # a steady start opens the window once every session of the steady
    # set holds its history and first token, and not before ``ramp_s``
    steady = list(recs.values()) if tr.steady else []
    if steady:
        W0 = W1 = float("inf")
    tracing = False
    stats0 = None
    while True:
        now = clock()
        if steady and all(r.first is not None for r in steady):
            W0 = max(now, T0 + ramp)
            W1 = W0 + seconds
            steady = []
        elif steady and now - T0 > STEADY_LIMIT_S:
            raise RuntimeError(
                f"the steady set was not prefilled in {STEADY_LIMIT_S} s: "
                f"{sum(r.first is None for r in steady)} of {B} sessions "
                f"still wait")
        if stats0 is None and now >= W0:
            trace_end = W0 + min(TRACE_S, seconds)
            stats0 = (dict(eng.sched.stats), dict(eng.kv.stats))
            if trace_dir is not None:
                import jax
                jax.profiler.start_trace(str(trace_dir),
                                         profiler_options=_trace_options())
                tracing, win_span = True, span("bench.window")
                win_span.__enter__()
                trace_t = [clock(), None]
        if tracing and now >= trace_end:
            win_span.__exit__(None, None, None)
            import jax
            trace_t[1] = clock()
            jax.profiler.stop_trace()
            tracing = False
        if now >= W1:
            break
        if not closed:
            with span("bench.submit"):
                while T0 + tr.due(k) <= now:
                    submit(tr.request(k), T0 + tr.due(k), now)
                    k += 1
        if not eng.sched.has_work():
            nxt = W1 if closed else min(T0 + tr.due(k), W1)
            time.sleep(max(0.0, min(nxt, W0 if stats0 is None else W1)
                           - clock()))
            continue
        t_tick = clock()
        with span("bench.tick"):
            eng.tick(t_tick - T0)
        if W0 <= t_tick < W1:
            pool_use.append(1.0 - eng.kv.n_free() / (eng.kv.n_pages - 1))
        with span("bench.stamp"):
            t = clock()
            for rid in list(live):
                rec = live[rid]
                r = rec.req
                if rec.admitted is None and (r.n_done or r.out):
                    rec.admitted = t_tick
                new = len(r.out) - len(rec.stamps)
                for _ in range(max(new, 0)):
                    if rec.stamps and W0 <= t < W1:
                        gaps.append(t - rec.stamps[-1])
                    rec.stamps.append(t)
                if rec.first is None and rec.stamps:
                    rec.first = t
                if r.t_finish is not None:
                    rec.finished = t
                    del live[rid]
                    if closed:
                        submit(tr.request(k), t, t)
                        k += 1
    if not closed:
        # due in the window but never handed over (the loop was held
        # up): they count, unserved, with the time they waited
        while T0 + tr.due(k) < W1:
            spec = tr.request(k)
            recs[spec.rid] = Rec(spec, None, T0 + tr.due(k))
            k += 1
    if tracing:
        import jax
        win_span.__exit__(None, None, None)
        trace_t[1] = clock()
        jax.profiler.stop_trace()
    stats1 = (dict(eng.sched.stats), dict(eng.kv.stats))
    stats0 = stats0 or stats1
    return {"T0": T0, "W0": W0, "W1": W1, "recs": recs, "gaps": gaps,
            "late": late, "closed": closed, "pool_use": pool_use,
            "sched": {k_: stats1[0][k_] - stats0[0][k_] for k_ in stats1[0]},
            "kv": {k_: stats1[1][k_] - stats0[1][k_] for k_ in stats1[1]},
            "trace_t": trace_t if trace_dir is not None else None}


def _trace_options():
    from jax._src.profiler import ProfileOptions
    po = ProfileOptions()
    po.python_tracer_level = 0
    po.host_tracer_level = 1
    return po


# ----------------------------------------------------------------------
# metrics
# ----------------------------------------------------------------------
class Run:
    """What a metric reader sees: the cell, the window's records, the
    substrate calls, and (traced runs) the reduced device trace."""

    def __init__(self, cell, meta, window, calls, setup_s, red, peak,
                 chips):
        self.cell, self.meta = cell, meta
        self.family = families.load(cell["root"], cell["conf"])
        self.model = self.family.dims(cell["conf"])
        self.tp = meta["tp"]
        self.chips = chips
        self.seconds = window["W1"] - window["W0"]
        self.W0, self.W1 = window["W0"], window["W1"]
        self.recs = window["recs"]
        self.gaps = window["gaps"]
        self.closed = window["closed"]
        self.sched, self.kv = window["sched"], window["kv"]
        self.setup_s = setup_s
        self.trace = red
        self.trace_t = window["trace_t"]
        self.calls = calls
        self.peak = peak

    def due_in_window(self) -> list:
        return [r for r in self.recs.values()
                if self.W0 <= r.due < self.W1]

    def traced_calls(self) -> list:
        if not self.trace_t:
            return []
        a, b = self.trace_t
        return [c for c in self.calls if a <= c[1] < b]


def reader(root: Path, name: str):
    base = name.split(".")[0]
    path = root / "bench" / "metrics" / f"{base}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{base}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(run: Run, metrics: list) -> dict:
    out = {}
    for m in metrics:
        v = reader(run.cell["root"], m["name"])(run, m["name"])
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------
def devices_for(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"the benchmark needs a TPU; JAX found "
                     f"{devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def serve(root: Path, workload: str, seed: int, seconds: float,
          trace: bool, *, require_chip: bool = True, wrap=None,
          bench: dict | None = None, log=sys.stderr) -> dict:
    """Build, warm and serve one window of the cell; free the program's
    state.  Returns what the metrics and the comparison read."""
    cell = load_cell(root, workload, bench)
    chips = int(cell["workload"]["chips"])
    devs = devices_for(chips, require_chip)
    peak = costs.peaks(devs[0].device_kind) if require_chip else None
    eng, meta = build(cell, seed, devs, wrap)
    warm(eng)
    tr = traffic.Traffic(cell["mix"], seed, meta["cfg"].vocab,
                         meta["scfg"].max_batch)
    trace_dir = None
    if trace:
        trace_dir = root / "bench" / ".out" / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
    before = _count_compiles()
    window = drive(eng, tr, cell["mix"], seconds, trace_dir)
    n_in = sum(window["W0"] <= t < window["W1"] for t in _COMPILES[before:])
    print(f"programs compiled inside the window: {n_in}", file=log)
    late = np.asarray(window["late"])
    print(f"generator lateness: p50 {np.median(late) * 1e3:.3f} ms, max "
          f"{late.max() * 1e3:.3f} ms over {late.size} submissions",
          file=log)
    use = np.asarray(window["pool_use"] or [0.0])
    print(f"KV pool held over the window's ticks: first {use[0]:.3f}, "
          f"mean {use.mean():.3f}, max {use.max():.3f}", file=log)
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs)
    calls = eng.exec.calls
    del eng
    gc.collect()
    # every token a greedy request was served, finished or still in
    # flight at the close: each was produced by the timed path
    served = [(r.spec, list(r.req.out)) for r in window["recs"].values()
              if r.req is not None and r.req.out]
    return {"cell": cell, "meta": meta, "window": window, "calls": calls,
            "devs": devs, "chips": chips, "peak": peak, "mem": mem,
            "served": served, "trace_dir": trace_dir}


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, **kw) -> dict:
    """One run of one cell; returns the result line's object."""
    sv = serve(root, workload, seed, seconds, trace, **kw)
    cell, window, devs = sv["cell"], sv["window"], sv["devs"]
    red = None
    if trace:
        red = trace_reduce.reduce(trace_reduce.load(_xplane(sv["trace_dir"])))
    runv = Run(cell, sv["meta"], window, sv["calls"], window["W0"] - t_start,
               red, sv["peak"], sv["chips"])
    metrics = read_metrics(runv, cell["per_layer" if trace
                                       else "end_to_end"])
    t_ref = clock()
    ok, checks = check.verdict(cell["root"], cell["conf"], seed, sv["served"],
                               cell["conf"]["correct"])
    print(f"reference check: {clock() - t_ref:.1f} s", file=sys.stderr)
    attempted = (len(window["recs"]) if window["closed"] else
                 len(runv.due_in_window()))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": sv["chips"], "memory_peak_bytes": int(sv["mem"])}
    result = {"correct": bool(ok), "attempted": int(attempted),
              "failed": 0, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = (trace_reduce.busy_share(red)
                            * red["window_ns"] / 1e9)
        device["window_s"] = red["window_ns"] / 1e9
        result["breakdown"] = trace_reduce.breakdown(red)
        shutil.rmtree(sv["trace_dir"], ignore_errors=True)
    result["checks"] = checks
    return result


_COMPILES: list = []          # clock() at the end of each XLA compile


def _count_compiles() -> int:
    """Start noting compiles (once); returns how many were noted so far,
    so ``_COMPILES[n:]`` are the ones after this call."""
    import jax
    if not _count_compiles.on:
        def note(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                _COMPILES.append(clock())
        jax.monitoring.register_event_duration_secs_listener(note)
        _count_compiles.on = True
    return len(_COMPILES)


_count_compiles.on = False


def _xplane(trace_dir: Path) -> Path:
    found = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    return found[-1]
