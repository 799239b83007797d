"""repro.serve tracing: the engine's host spans and their counters in a
profiler trace, request admission stamps, and the model-step scopes,
which change the compiled programs' metadata and nothing else."""
import contextlib
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro import configs, serve
from repro.core import SymmetricHeap
from repro.models import registry
from repro.parallel.ctx import ParallelCtx
from repro.serve import Request, SamplingParams, ServeConfig, ServeEngine

STEP_SPANS = ["serve.prepare", "serve.dispatch", "serve.wait",
              "serve.retire"]
SCOPES = ["embed", "qkv", "kv_write", "attn_kernel", "attn_out", "mlp",
          "head_sample"]


@pytest.fixture(scope="module")
def model():
    cfg = configs.get_smoke("qwen3-8b")
    ctx = ParallelCtx(dp_size=1, tp_size=1, sp=False, remat=False,
                      param_dtype=jnp.float32, compute_dtype=jnp.float32)
    api = registry.build(cfg)
    return cfg, ctx, api, api.init(jax.random.PRNGKey(0), cfg, ctx)


def _requests():
    # prompts longer than the chunk and a tick budget below the batch's
    # demand, so ticks mix decode with chunked prefill; half sampled
    sp = SamplingParams(temperature=1.0, top_p=0.9)
    return [Request(rid=i, prompt=list(range(3 + i, 10 + 2 * i)),
                    max_new=4 + i, t_arrive=float(i),
                    sampling=sp if i % 2 else serve.GREEDY)
            for i in range(5)]


def _engine(model, n_pages=32):
    cfg, ctx, _, params = model
    scfg = ServeConfig(page_tokens=4, n_pages=n_pages, max_batch=3,
                       max_seq=32, prefill_chunk=3, attn_impl="ref")
    return ServeEngine(params, cfg, ctx, scfg)


def _serve_spans(trace_dir):
    """``(name, start, end, stats)`` of every ``serve.*`` host event in
    the trace, by start (an enclosing span before what it holds)."""
    path = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    """A toy engine served under the profiler, with what each tick
    planned and what each decode call carried noted on the side."""
    eng = _engine(model)
    plans, decode_batch = [], {}
    sched_tick, decode = eng.sched.tick, eng.exec.decode

    def note_plan(now=0.0):
        plan = sched_tick(now)
        plans.append(plan)
        return plan

    def note_decode(pool, tokens, pos, bt, lens, samp):
        decode_batch[eng.ticks] = int((lens > 0).sum())
        return decode(pool, tokens, pos, bt, lens, samp)

    eng.sched.tick = note_plan
    eng.exec.decode = note_decode
    trace_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(trace_dir)):
        done = eng.run(_requests(), clock="tick")
    return eng, plans, decode_batch, done, _serve_spans(trace_dir)


def _ticks(spans):
    """Each ``serve.tick`` span with the spans it holds, in order."""
    ticks = [s for s in spans if s[0] == "serve.tick"]
    return [(t, [s for s in spans if s is not t and t[1] <= s[1]
                 and s[2] <= t[2]]) for t in ticks]


def test_every_tick_is_one_span_holding_its_phases_in_order(traced):
    eng, plans, _, _, spans = traced
    ticks = _ticks(spans)
    assert [t[3]["tick"] for t, _ in ticks] == list(range(1, eng.ticks + 1))
    assert sum(len(inner) for _, inner in ticks) + len(ticks) == len(spans)
    kinds = set()
    for (_, _, _, stats), inner in ticks:
        names = [s[0] for s in inner]
        assert names[:2] == ["serve.schedule", "serve.plan"], names
        calls = names[2:]
        assert len(calls) % 4 == 0 and len(calls) // 4 in (1, 2), names
        steps = []
        for i in range(0, len(calls), 4):
            group = inner[2 + i:6 + i]
            assert [s[0] for s in group] == STEP_SPANS
            assert len({s[3]["step"] for s in group}) == 1
            steps.append(group[0][3]["step"])
            # one after the other, never overlapping
            assert all(a[2] <= b[1] for a, b in zip(group, group[1:]))
        assert steps in (["prefill"], ["decode"], ["prefill", "decode"])
        kinds.add(tuple(steps))
    assert ("prefill", "decode") in kinds         # mixed ticks were traced


def test_span_counters_equal_the_plan(traced):
    eng, plans, decode_batch, _, spans = traced
    ticks = _ticks(spans)
    assert len(plans) == len(ticks)
    for k, ((_, _, _, stats), inner), plan in zip(
            range(1, len(ticks) + 1), ticks, plans):
        pl = next(s[3] for s in inner if s[0] == "serve.plan")
        assert pl["prefill_tokens"] == sum(n for _, n in plan.prefill)
        assert pl["prefill_seqs"] == len(plan.prefill)
        assert pl["decode_seqs"] == decode_batch.get(k, 0)
        assert 0 <= pl["pages_free"] < eng.kv.n_pages
        assert pl["waiting"] >= 0
        tokens = {s[3]["step"]: s[3]["tokens"] for s in inner
                  if s[0] == "serve.dispatch"}
        assert tokens.get("prefill", 0) == pl["prefill_tokens"]
        assert tokens.get("decode", 0) == pl["decode_seqs"]


def test_streams_are_the_same_with_the_profiler_off(model, traced):
    traced_out = {r.rid: r.out for r in traced[3]}
    plain = _engine(model).run(_requests(), clock="tick")
    assert {r.rid: r.out for r in plain} == traced_out
    assert len(traced_out) == 5


def test_admission_stamp_is_set_once_and_survives_preemption(model):
    # a pool too small for all three: the youngest is evicted and
    # re-admitted, and its admission stamp stays the first admission's
    eng = _engine(model, n_pages=8)
    admitted = {}
    sched_tick = eng.sched.tick

    def note(now=0.0):
        plan = sched_tick(now)
        for r in plan.admitted + plan.resumed:
            admitted.setdefault(r.rid, []).append(now)
        return plan

    eng.sched.tick = note
    reqs = [Request(rid=i, prompt=list(range(2 + i, 10 + i)), max_new=8)
            for i in range(3)]
    done = eng.run(reqs, clock="tick")
    assert len(done) == 3
    assert eng.sched.stats["preempted"] > 0
    for r in done:
        assert r.t_admit == admitted[r.rid][0]
        assert r.t_admit <= r.t_first
        assert len(admitted[r.rid]) == 1 + r.preemptions
    assert any(r.preemptions for r in done)


# ----------------------------------------------------------------------
# model-step scopes
# ----------------------------------------------------------------------
def _lowered(model, make, window: bool):
    cfg, ctx, api, _ = model
    scfg = ServeConfig(page_tokens=4, n_pages=16, max_batch=2, max_seq=16,
                       prefill_chunk=4, attn_impl="kernel")
    params = jax.eval_shape(lambda k: api.init(k, cfg, ctx),
                            jax.random.PRNGKey(0))
    kv = serve.PagedKVCache(
        SymmetricHeap(("data",)), n_layers=cfg.n_layers,
        kv_heads=cfg.kv_per_rank(1), head_dim=cfg.head_dim,
        n_pages=scfg.n_pages, page_tokens=scfg.page_tokens)
    pool = jax.ShapeDtypeStruct(kv.zeros().shape, jnp.float32)
    B, S = scfg.max_batch, scfg.table_slots

    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32)

    samp = serve.batch_state([], B, 0)
    first = i32(B, scfg.prefill_chunk) if window else i32(B)
    return jax.jit(make(cfg, ctx, scfg)).lower(
        params, pool, first, i32(B), *((i32(B), i32(B, S)) if window
                                       else (i32(B, S), i32(B))), samp)


# what a scope may change: op metadata, the debug tables that compiled
# text lists (file names, stack frames), and the instruction names the
# name stack seeds; ``canonical`` drops the first two and renumbers
# the names in order of first use
METADATA = re.compile(r',? metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
DEBUG = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*",
    re.M)
NAME = re.compile(r"(?<![\w.\-])%?[A-Za-z_][\w\-]*\.\d+(?![\w.\-])")


def canonical(hlo: str) -> str:
    names: dict = {}
    return NAME.sub(
        lambda m: names.setdefault(m.group(0).lstrip("%"), f"v{len(names)}"),
        METADATA.sub("", DEBUG.sub("", hlo)))


@pytest.mark.parametrize("make,window", [
    (serve.make_decode_step, False), (serve.make_prefill, True),
    (serve.make_verify, True)], ids=["decode", "prefill", "verify"])
def test_scopes_change_only_the_metadata(model, monkeypatch, make, window):
    scoped = _lowered(model, make, window)
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope",
                  lambda name: contextlib.nullcontext())
        bare = _lowered(model, make, window)
    assert canonical(scoped.as_text(dialect="hlo")) == \
        canonical(bare.as_text(dialect="hlo"))
    a, b = scoped.compile().as_text(), bare.compile().as_text()
    assert a != b                                 # the metadata differs
    assert canonical(a) == canonical(b)
    names = set(re.findall(r'op_name="([^"]*)"', a))
    for scope in SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope
    kv = [n for n in names if "/kv_write/" in n]
    assert kv and all("/while/body/" in n for n in kv)  # in the layer scan


@pytest.mark.parametrize("make,window", [
    (serve.make_decode_step, False), (serve.make_prefill, True),
    (serve.make_verify, True)], ids=["decode", "prefill", "verify"])
def test_no_step_slices_a_pool_half(model, make, window):
    # the kernels take the whole pool and a layer index: no instruction
    # of the step makes a K or V half of the pool, (n_pages, 1, L, P,
    # kvh, dh) or (n_pages, L, P, kvh, dh), to take its layer from
    lowered = _lowered(model, make, window)
    n_pages, _, n_layers, page, kvh, dh = lowered.args_info[0][1].shape
    half = re.compile(r"\[%d,(?:1,)?%d,%d,%d,%d\]"
                      % (n_pages, n_layers, page, kvh, dh))
    hlo = lowered.as_text(dialect="hlo")
    assert f"[{n_pages},2,{n_layers},{page},{kvh},{dh}]" in hlo
    assert not half.findall(hlo)


SCOPE_KEY = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    def step(scope):
        def f(x):
            with jax.named_scope(scope):
                return jnp.sin(x) * 2
        return f

    for scope in ("first_scope", "second_scope"):
        text = jax.jit(step(scope)).lower(jnp.ones(8)).compile().as_text()
        print(scope in text)
""")


def test_compile_cache_keeps_programs_apart_by_their_scopes(tmp_path):
    # two programs that differ only in a scope: a cache keyed without
    # the metadata hands the second the first's executable, and with it
    # the first's op names
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=os.path.join(os.path.dirname(
                   os.path.dirname(os.path.abspath(__file__))), "src"))
    r = subprocess.run([sys.executable, "-c", SCOPE_KEY], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == ["True", "True"]
