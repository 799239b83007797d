"""Architecture families are files found by name: a family with another
parameter layout (a toy mixture of experts) enters a checkout with new
files only and is judged by its own reference; a configuration with no
family, or an unknown one, is refused; ``step_mfu`` counts with the
family's own work; the dense family's reference runs without the
program."""
import functools
import json
import os
import shutil
import subprocess
import sys
import textwrap
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench_tiny import DATA, REPO, make_root

from bench import families, harness
from bench.families import dense

CELL = "tiny_moe.open"


def moe_root(tmp_path):
    """A toy checkout with the toy MoE family and its cell added as
    files and entries, as a configuration of a new family would be."""
    root = make_root(tmp_path)
    shutil.copy(DATA / "families" / "toy_moe.py", root / "bench" / "families")
    shutil.copy(DATA / "tiny_moe.json", root / "bench" / "configs")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny_moe",
                         "file": "bench/configs/tiny_moe.json"})
    b["workloads"].append({"name": CELL, "config": "tiny_moe",
                           "traffic": "tiny_open", "chips": 1})
    for m in b["end_to_end"]:
        if "tiny.open" in m["workloads"]:
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


@pytest.mark.parametrize("fewer,expect", [(0, True), (1, False)])
def test_a_new_family_enters_with_files_only(tmp_path, monkeypatch, fewer,
                                             expect):
    # sound, the toy MoE serves through the program's own ``moe`` path
    # and matches its reference; with the reference routing each token
    # to one expert fewer than the program, ``correct`` comes out false
    root = moe_root(tmp_path)
    if fewer:
        conf = json.loads((DATA / "tiny_moe.json").read_text())
        fam = families.load(root, conf)
        k = conf["model"]["num_experts_per_tok"] - fewer
        monkeypatch.setattr(fam, "Reference",
                            functools.partial(fam.Reference, top_k=k))
    res = harness.run(root, CELL, 2**34 + 17, 1.5, False,
                      time.perf_counter(), require_chip=False)
    assert res["correct"] is expect, res["checks"]
    assert res["checks"]["tokens_checked"]["value"] >= \
        res["checks"]["tokens_checked"]["limit"]
    assert "ttft_p90_ms" in res["metrics"]


@pytest.mark.parametrize("family,looked_for", [
    (None, "bench/families/<family>.py"),
    ("nosuch", "bench/families/nosuch.py")])
def test_a_configuration_needs_a_family_file(tmp_path, family, looked_for):
    root = make_root(tmp_path)
    path = root / "bench" / "configs" / "tiny.json"
    conf = json.loads(path.read_text())
    del conf["family"]
    if family:
        conf["family"] = family
    path.write_text(json.dumps(conf))
    cell = harness.load_cell(root, "tiny.open")
    devs = harness.devices_for(1, require_chip=False)
    with pytest.raises((KeyError, FileNotFoundError), match=looked_for):
        harness.build(cell, 1, devs)


def test_step_mfu_counts_with_the_family(tmp_path):
    root = moe_root(tmp_path)
    conf = json.loads((DATA / "tiny_moe.json").read_text())
    fam = families.load(root, conf)
    m = fam.dims(conf)
    # one decode call of two live sequences (3 and 5 cached tokens) in a
    # 1 s traced window
    run = SimpleNamespace(
        family=fam, model=m, trace={"window_ns": 1e9}, trace_t=(0.0, 1.0),
        peak={"bf16_flops_per_s": 1e12}, chips=1, recs={},
        traced_calls=lambda: [("decode", 0.5, np.array([3, 0, 5]))])
    got = harness.reader(root, "step_mfu")(run, "step_mfu.x")
    want = fam.model_flops(m, new_tokens=2, attended=8, logit_rows=2)
    assert got == pytest.approx(100.0 * want / 1e12)
    as_dense = dense.model_flops(dict(m, act="silu"), 2, 8, 2)
    assert as_dense != want


def test_the_dense_reference_runs_without_the_program():
    child = textwrap.dedent(f"""
        import json, sys
        import numpy as np
        sys.path.insert(0, {str(REPO)!r})
        from bench.families import dense
        conf = json.load(open({str(DATA / "tiny.json")!r}))
        ref = dense.Reference(conf, 3)
        hs = ref.hidden([[5, 6, 7, 8, 9]], [np.arange(5)])
        best, arg, _ = ref.logits_stats(hs)
        assert hs.shape == (5, 64) and best.shape == (5,), hs.shape
        print(json.dumps(sorted(m for m in sys.modules
                                if m.split(".")[0] == "repro")))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "-c", child], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) == []
