"""The driver loop: open-loop timing from due time, and the entry
point's refusal to run without the chip."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

from bench_tiny import REPO, make_root

from bench import harness, traffic


class Stall:
    """The substrate, with one decode call held for ``secs`` seconds."""

    def __init__(self, inner, at: int, secs: float):
        self.inner, self.at, self.secs = inner, at, secs
        self.n, self.span = 0, None

    def prefill(self, *a):
        return self.inner.prefill(*a)

    def decode(self, *a):
        self.n += 1
        out = self.inner.decode(*a)
        if self.n == self.at:
            t0 = time.perf_counter()
            time.sleep(self.secs)
            self.span = (t0, time.perf_counter())
        return out

    def __getattr__(self, name):
        return getattr(self.inner, name)


def _drive(tmp_path, stall_at, stall_s, seconds):
    root = make_root(tmp_path)
    cell = harness.load_cell(root, "tiny.open")
    devs = harness.devices_for(1, require_chip=False)
    box = {}

    def wrap(inner):
        box["stall"] = Stall(inner, stall_at, stall_s)
        return box["stall"]

    eng, meta = harness.build(cell, 5, devs, wrap)
    harness.warm(eng)
    tr = traffic.Traffic(cell["mix"], 5, meta["cfg"].vocab)
    w = harness.drive(eng, tr, cell["mix"], seconds)
    run = harness.Run(cell, meta, w, eng.exec.calls, 0.0, None, None, 1)
    return w, run, box["stall"].span


def test_a_stalled_tick_shows_in_ttft_from_due_time(tmp_path):
    w, run, (s0, s1) = _drive(tmp_path, stall_at=12, stall_s=0.8,
                              seconds=2.0)
    during = [r for r in w["recs"].values() if s0 < r.due < s1 - 0.1]
    assert during, "no request fell due during the stall"
    for r in during:
        # timed from when it was due: the stall's remainder is in it
        assert r.first is not None and r.first - r.due >= s1 - r.due
    assert any(r.first - r.due >= 0.1 for r in during)
    ttft = harness.reader(run.cell["root"], "ttft_p90_ms")(run, "ttft_p90_ms")
    assert ttft >= 1e3 * np.percentile([s1 - r.due for r in during], 50)


def test_a_request_unserved_at_the_close_counts_its_wait(tmp_path):
    # the stall outlasts the window: requests due after it began have no
    # first token, and enter the tail with the time waited so far
    w, run, (s0, s1) = _drive(tmp_path, stall_at=12, stall_s=2.5,
                              seconds=1.5)
    assert s1 > w["W1"]
    waiting = [r for r in run.due_in_window()
               if r.first is None or r.first >= w["W1"]]
    assert waiting
    ttft = harness.reader(run.cell["root"], "ttft_p90_ms")(run, "ttft_p90_ms")
    assert ttft / 1e3 >= min(w["W1"] - r.due for r in waiting)
    assert ttft / 1e3 <= w["W1"] - w["W0"] + 1e-9


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-8b-d16.code",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    return not any(line.startswith("{") for line in p.stdout.splitlines())


def test_run_py_exits_non_zero_without_a_tpu():
    p = _run_py(REPO)
    assert p.returncode != 0
    assert _no_result(p)
    assert "TPU" in p.stderr


def test_run_py_exits_non_zero_with_only_the_benchmark(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert _no_result(p)


def test_benchmark_names_files_that_exist():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (REPO / c["file"]).is_file()
    for w in bench["workloads"]:
        assert traffic.mix_path(REPO, w["traffic"]).is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        base = m["name"].split(".")[0]
        assert (REPO / "bench" / "metrics" / f"{base}.py").is_file()


def test_steady_start_opens_the_window_once_the_set_is_prefilled(tmp_path):
    root = make_root(tmp_path)
    cell = harness.load_cell(root, "tiny.steady")
    devs = harness.devices_for(1, require_chip=False)
    eng, meta = harness.build(cell, 7, devs)
    harness.warm(eng)
    B = meta["scfg"].max_batch
    tr = traffic.Traffic(cell["mix"], 7, meta["cfg"].vocab, B)
    w = harness.drive(eng, tr, cell["mix"], 1.0)
    steady = [w["recs"][k] for k in range(B)]
    assert sum(r.spec.history for r in steady) > 0
    # every session of the set held its first token before the window
    assert all(r.first is not None and r.first <= w["W0"] for r in steady)
    assert w["W1"] - w["W0"] == 1.0
    run = harness.Run(cell, meta, w, eng.exec.calls, 0.0, None, None, 1)
    tok_s = harness.reader(root, "output_tok_s")(run, "output_tok_s")
    assert tok_s > 0
