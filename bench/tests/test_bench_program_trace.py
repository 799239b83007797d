"""The readers of the program's own tracing: the ``tf_op`` decoder on
the chip fixture, the host-span readers on a trace made here on the
CPU, ``kv_io_ms`` on a toy trace recorded on the chip, and a whole
traced run of a toy cell."""
import gzip
import time
import types

import jax
import numpy as np
import pytest

from bench_tiny import DATA, REPO, make_root

from bench import harness, trace_reduce
from bench.metrics import _program

CHIP_TRACE = DATA / "chip_trace.xplane.pb.gz"
# one prefill tick and one decode tick of a toy engine with the step
# programs' scopes, traced on a TPU v5e (``capture_trace.py``)
TOY_TRACE = DATA / "toy_trace.xplane.pb.gz"
SCOPES = ["embed", "qkv", "kv_write", "kv_read", "attn_kernel", "attn_out",
          "mlp", "head_sample"]


def _run_on(tmp_path, raw: bytes):
    """A traced run's view of ``raw``: the file where the harness keeps
    it, and its reduction."""
    d = tmp_path / "bench" / ".out" / "trace" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "t.xplane.pb").write_bytes(raw)
    red = trace_reduce.reduce(
        trace_reduce.load(d / "t.xplane.pb"))
    return types.SimpleNamespace(trace=red, cell={"root": tmp_path})


def _read(run, name):
    return harness.reader(REPO, name)(run, name)


def test_tf_op_decoder_on_the_chip_fixture(tmp_path):
    run = _run_on(tmp_path, gzip.open(CHIP_TRACE).read())
    stacks = _program.tf_ops(run)
    assert list(stacks) == [0]
    events = [n for _, n, _, _ in run.trace["op_events"]]
    named = [n for n in events if n in stacks[0]]
    # every op XLA made from the program's own operations carries the
    # name stack of the prefill program; 43 of the 1755 events are ops
    # the compiler adds itself (copies between memories, one layout
    # fusion, the loop) and carry none
    assert (len(events), len(named)) == (1755, 1712)
    assert all(stacks[0][n].startswith("jit(prefill)/") for n in named)
    assert sum(stacks[0][n] == "jit(prefill)/while" for n in named) > 0
    # this trace predates the scopes: nothing to read
    assert _read(run, "kv_io_ms.code") is None


def test_kv_io_ms_on_a_toy_chip_trace(tmp_path):
    run = _run_on(tmp_path, gzip.open(TOY_TRACE).read())
    assert [m for _, m, _, _ in run.trace["modules"]] == ["jit_prefill",
                                                         "jit_step"]
    stacks = _program.tf_ops(run)[0]
    kv = {}
    for _, op, s, e in run.trace["op_events"]:
        stack = stacks.get(op, "")
        parts = stack.split("/")
        if {"kv_read", "kv_write"} & set(parts):
            kv.setdefault(parts[0], []).append(e - s)
    for prog in ("jit(prefill)", "jit(step)"):
        names = {p for st in stacks.values() if st.startswith(prog + "/")
                 for p in st.split("/")}
        assert set(SCOPES) <= names, (prog, set(SCOPES) - names)
    # 39 op events under the two scopes, 20 in the prefill program and
    # 19 in the decode program, 29144 ns in all, over two executions;
    # the largest is the decode program's slice of the pool's K and V
    # halves (4221 ns, then 4218 ns in the second layer)
    assert (len(kv["jit(prefill)"]), len(kv["jit(step)"])) == (20, 19)
    assert sum(map(sum, kv.values())) == 29144
    assert max(kv["jit(step)"]) == 4221
    assert _read(run, "kv_io_ms.code") == pytest.approx(29144 / 2 / 1e6)
    assert _read(run, "kv_io_ms.longout") == _read(run, "kv_io_ms.code")


def _synthetic_trace(root):
    """A profile of hand-made spans: ticks (one before the window) with
    plan counters, each holding a 2 ms prepare and a 6 ms wait."""
    plans = [(2, 0), (3, 40), (0, 9), (1, 0)]     # (decode, prefill tokens)
    trace_dir = root / "bench" / ".out" / "trace"
    with jax.profiler.trace(str(trace_dir)):
        with jax.profiler.TraceAnnotation("serve.tick", tick=0):
            time.sleep(0.002)
        with jax.profiler.TraceAnnotation("bench.window"):
            for k, (dec, pre) in enumerate(plans, 1):
                with jax.profiler.TraceAnnotation("serve.tick", tick=k):
                    with jax.profiler.TraceAnnotation(
                            "serve.plan", waiting=1, decode_seqs=dec,
                            prefill_seqs=int(pre > 0), prefill_tokens=pre,
                            pages_free=7):
                        pass
                    with jax.profiler.TraceAnnotation("serve.prepare",
                                                      step="decode"):
                        time.sleep(0.002)
                    with jax.profiler.TraceAnnotation("serve.wait",
                                                      step="decode"):
                        time.sleep(0.006)
    path = harness._xplane(trace_dir)
    red = trace_reduce.reduce(trace_reduce.load(path))
    return types.SimpleNamespace(trace=red, cell={"root": root}), path


def test_host_span_readers_on_a_cpu_trace(tmp_path):
    run, path = _synthetic_trace(tmp_path)
    ticks = _program.ticks(run)
    assert [t[3]["tick"] for t, _ in ticks] == [1, 2, 3, 4]
    # decoding ticks 1, 2 and 4; tick 2 also prefills
    assert _read(run, "mixed_tick_share.code") == pytest.approx(100 / 3)
    # the host's own time: each tick less its wait, from the events
    ev = {}
    for p in trace_reduce.load(path).planes:
        for line in p.lines:
            for e in line.events:
                if e.name in ("serve.tick", "serve.wait"):
                    ev.setdefault(e.name, []).append(e)
    own = sorted(t.duration_ns - w.duration_ns for t, w in zip(
        sorted(ev["serve.tick"], key=lambda e: e.start_ns)[1:],
        sorted(ev["serve.wait"], key=lambda e: e.start_ns)))
    want = (own[1] + own[2]) / 2 / 1e6
    got = _read(run, "host_tick_ms.longout")
    assert got == pytest.approx(want)
    assert 2.0 <= got < 6.0
    # an untraced run, or one without the spans, reads nothing
    empty = types.SimpleNamespace(trace=None, cell={"root": tmp_path})
    assert _read(empty, "host_tick_ms.code") is None
    assert _read(empty, "mixed_tick_share.code") is None
    assert _read(empty, "kv_io_ms.code") is None


def test_a_traced_toy_run_reads_the_program_metrics(tmp_path):
    root = make_root(tmp_path)
    res = harness.run(root, "tiny.open", 2**33 + 11, 1.5, True, 0.0,
                      require_chip=False)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert res["correct"]
    assert m["host_tick_ms.code"] > 0
    assert m["host_tick_ms.code"] == m["host_tick_ms.longout"]
    assert 0 <= m["mixed_tick_share.code"] <= 100
    # the scheduler stamps admission in the tick the harness first sees
    # progress in: both readings of the wait agree
    assert m["admit_wait_p90_ms.code"] == pytest.approx(
        m["queue_wait_p90_ms.code"], rel=1e-6, abs=1e-6)
    assert "kv_io_ms.code" not in m         # no device plane on the CPU
    assert not (root / "bench" / ".out" / "trace").exists()


def test_admit_wait_counts_the_unadmitted_to_the_close():
    def rec(due, t_admit, t0=100.0):
        req = types.SimpleNamespace(t_arrive=due - t0, t_admit=t_admit)
        return types.SimpleNamespace(due=due, req=req)

    # engine clock zero at 100 s; the window closes at 107 s
    recs = [rec(101.0 + i, i + 1.5) for i in range(9)]   # wait 0.5 s
    recs.append(rec(105.0, None))            # never admitted: 2 s so far
    recs.append(rec(104.0, 8.0))             # admitted after the close: 3 s
    run = types.SimpleNamespace(
        W0=100.0, W1=107.0, recs=dict(enumerate(recs)),
        due_in_window=lambda: [r for r in recs if r.due < 107.0])
    waits = [0.5] * 6 + [2.0, 3.0]
    v = _read(run, "admit_wait_p90_ms.code")
    assert v == pytest.approx(1e3 * np.percentile(waits, 90))
    parent = types.SimpleNamespace(
        recs={0: types.SimpleNamespace(req=types.SimpleNamespace())})
    assert _read(parent, "admit_wait_p90_ms.code") is None
