"""The chip entry points off the chip: ``chip_smoke.py`` refuses to run
anywhere but a TPU, the compile-cache helper follows
``JAX_COMPILATION_CACHE_DIR`` or a fixed gitignored path in the
checkout, and ``build_engine``'s published-config path serves in bf16
(exercised with the reduced config standing in for the published one,
which does not fit this host)."""
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import configs, serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE_SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _env(**kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(kw)
    return env


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_refuses_cpu(where, tmp_path):
    """On the CPU, and in a directory holding chip_smoke.py and nothing
    else of the repo, the script exits non-zero without a result."""
    script = SMOKE_SCRIPT
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SMOKE_SCRIPT, script)
    env = _env(PYTHONPATH="")
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(script),
                       timeout=300)
    assert r.returncode != 0, r.stdout
    assert '"ok": true' not in r.stdout
    assert not (tmp_path / ".jax_cache").exists()


_PROBE = ("import json, jax; "
          "from repro.launch.cache import enable_compile_cache as e; "
          "a, b = e(), e(); "
          "print(json.dumps([a, b, jax.config.jax_compilation_cache_dir]))")


def _probe(**env):
    r = subprocess.run([sys.executable, "-c", _PROBE], capture_output=True,
                       text=True, timeout=300,
                       env=_env(PYTHONPATH=os.path.join(ROOT, "src"), **env))
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_compile_cache_follows_env(tmp_path):
    want = str(tmp_path / "cache")
    assert _probe(JAX_COMPILATION_CACHE_DIR=want) == [want, want, want]


def test_compile_cache_default_is_fixed_and_ignored():
    want = os.path.join(ROOT, ".jax_cache")
    assert _probe() == [want, want, want]
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.parametrize("smoke", [True, False])
def test_build_engine_published_path_dtypes(smoke, monkeypatch):
    """``smoke=False`` takes ``configs.get`` and serves params, compute
    and KV pages in bf16; ``smoke=True`` keeps the f32 reduced config."""
    from repro.launch.serve import build_engine
    monkeypatch.setattr(configs, "get", configs.get_smoke)
    eng, cfg = build_engine("gemma-2b", smoke=smoke, n_pages=32,
                            max_batch=2, attn_impl="ref")
    want = jnp.dtype(jnp.float32 if smoke else jnp.bfloat16)
    assert eng.ctx.param_dtype == want and eng.ctx.compute_dtype == want
    assert eng.pool.dtype == want and eng.scfg.kv_dtype == want
    assert {x.dtype for x in jax.tree.leaves(eng.exec.params)} == {want}
    reqs = [serve.Request(rid=i, prompt=list(range(3 + i, 9 + 2 * i)),
                          max_new=4) for i in range(3)]
    done = eng.run(reqs, clock="tick")
    assert sorted(len(r.out) for r in done) == [4, 4, 4]
    assert all(0 <= t < cfg.vocab for r in done for t in r.out)


# chip_smoke's phases rehearsed on the CPU at a tiny size: the reduced
# configs in f32, Pallas kernels interpreted, and the compiled-kernel
# check (which only a chip can pass) stubbed out
_TINY = dict(page_tokens=4, n_pages=64, max_batch=4, prefill_chunk=8,
             n_requests=6, prompt=(4, 12), out=(2, 6))
_REHEARSE = f"""
import sys
sys.path.insert(0, {ROOT!r})
import chip_smoke as cs
cs.SMOKE, cs.SIZES, cs.FOUR_CHIP_PAGES = True, {_TINY!r}, 64
cs.compile_steps = lambda exec_, scfg: None
cs.four_chips()
"""


def test_chip_smoke_one_chip_phase_rehearsal(monkeypatch, capsys):
    monkeypatch.syspath_prepend(ROOT)
    import chip_smoke as cs
    monkeypatch.setattr(cs, "SMOKE", True)
    monkeypatch.setattr(cs, "SIZES", _TINY)
    monkeypatch.setattr(cs, "compile_steps", lambda exec_, scfg: None)
    cs.one_chip(jax.devices()[0])
    out = capsys.readouterr().out
    assert "served 6 requests" in out and "of tolerance" in out


def test_chip_smoke_four_chip_phase_rehearsal():
    r = subprocess.run(
        [sys.executable, "-c", _REHEARSE], capture_output=True, text=True,
        timeout=600, env=_env(
            PYTHONPATH=os.path.join(ROOT, "src"),
            XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "token streams posh vs xla: 6/6 bit-identical" in r.stdout
