"""A tensor-parallel cell on four virtual CPU devices (the ``posh``
backend, a toy configuration): sound it is correct; with the exchange
between chips left out (every TP psum a no-op) ``correct`` comes out
false.  Runs in a child process, which needs its own device count."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench_tiny import REPO

CHILD = textwrap.dedent("""
    import json, shutil, sys, time
    from pathlib import Path
    sys.path.insert(0, {tests!r})
    import bench_tiny
    from bench import harness
    root = bench_tiny.make_root(Path(sys.argv[1]))
    conf = json.loads((bench_tiny.DATA / "tiny16.json").read_text())
    conf.update(name="tinytp", tp=4, comm_backend="posh")
    conf["model"].update(num_attention_heads=8, num_key_value_heads=4)
    (root / "bench/configs/tinytp.json").write_text(json.dumps(conf))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({{"name": "tinytp",
                          "file": "bench/configs/tinytp.json"}})
    b["workloads"].append({{"name": "tinytp.closed", "config": "tinytp",
                            "traffic": "tiny_closed", "chips": 4}})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    if sys.argv[2] == "exchange":
        from repro.comm.communicator import Communicator
        Communicator.psum = lambda self, x, *a, **k: x
    res = harness.run(root, "tinytp.closed", 2**33 + 5, 2.0, False,
                      time.perf_counter(), require_chip=False)
    print(json.dumps(res))
""").format(tests=str(REPO / "bench" / "tests"))


@pytest.mark.parametrize("fault,expect", [("none", True),
                                          ("exchange", False)])
def test_tp_cell_and_exchange_fault(tmp_path, fault, expect):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHILD, str(tmp_path), fault],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"] is expect, res["checks"]
