"""The trace reduction on a trace recorded on the chip: one tick of
``qwen3-8b-d16.code`` on a TPU v5e (a (8, 512) prefill-window program
execution, ~0.34 s), traced by the harness with its own host spans.
The numbers were read off the trace's events by hand."""
import pytest

from bench_tiny import DATA

from bench import trace_reduce
from bench.metrics import _util

TRACE = DATA / "chip_trace.xplane.pb.gz"


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce(trace_reduce.load(TRACE))


def test_window_is_the_bench_window_span(red):
    # bench.window on the host plane: 43062950 .. 386163463 ns
    assert (red["t0"], red["t1"]) == (43062950, 386163463)
    assert red["window_ns"] == 343100513
    assert red["chips"] == [0]


def test_per_module_device_time(red):
    # XLA Modules line: one jit_prefill execution, 46918166 .. 383496887
    assert red["modules"] == [(0, "jit_prefill", 46918166, 383496887)]
    assert trace_reduce.module_times(red, "jit_prefill") == [336578721]
    assert trace_reduce.module_times(red, "jit_step") == []


def test_busy_share(red):
    # the module's span less 722 ns of gaps between its ops (290 ns
    # before its first op, the rest a few ns each)
    assert red["busy_ns"] == {0: 336577999}
    assert trace_reduce.busy_share(red) == pytest.approx(
        336577999 / 343100513)


def test_idle_gaps_named_by_the_host_span(red):
    # before the first op: the host dispatching inside bench.tick
    # (43062950 -> 46918456); after the module: the host waiting for
    # the result inside bench.tick (383496887 -> 386163463)
    assert red["gaps"] == [(3855506, "bench.tick"), (2666576, "bench.tick")]
    b = trace_reduce.breakdown(red)
    assert b["idle_gaps"] == [["bench.tick", 0.003855506],
                              ["bench.tick", 0.002666576]]


def test_kernel_ops_and_breakdown(red):
    # 16 layers, one paged prefill kernel call each
    calls = [n for _, n, _, _ in red["op_events"]
             if n.startswith("%paged_prefill_attention.")]
    assert len(calls) == 16
    assert trace_reduce.op_time(red, _util.PREFILL_KERNEL) == \
        sum(e - s for _, n, s, e in red["op_events"]
            if n.startswith("%paged_prefill_attention."))
    assert trace_reduce.op_time(red, _util.DECODE_KERNEL) == 0
    names = [n for n, _ in trace_reduce.breakdown(red)["device_ops"]]
    assert len(names) == 10
    assert not any(n.startswith("while") for n in names)
