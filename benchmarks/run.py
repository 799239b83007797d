"""Benchmark harness — one section per paper table.

  Table 1 (paper §5.1): memory-copy engine variants (VMEM tilings vs
          stock copy).  The stock path is a bare XLA copy; the
          Pallas variants are characterized structurally (working-set
          bytes — interpret-mode wall-clock is not hardware-indicative;
          correctness is covered in tests/test_kernels.py).
  Table 2 (§5.2): put/get latency/bandwidth through the full POSH layer
          vs a local device copy.
  Table 3 (§5.3): POSH collectives vs native XLA collectives (the
          Berkeley-UPC/GASNet role), incl. the compile-time
          algorithm-selection comparison (§4.5.4).

Every table runs in ONE worker process (``benchmarks/_worker.py``) over
``jax.devices()``: 8 fake CPU PEs off-chip, the attached chips on a TPU
host.  This parent never imports jax, so the worker can own the chip.

Prints ``table,name,elems,us_per_call,derived`` CSV lines.
"""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "_worker.py")],
        capture_output=True, text=True, env=env, timeout=3600)
    if r.returncode != 0 or "WORKER_DONE" not in r.stdout:
        print("benchmark worker FAILED", file=sys.stderr)
        print(r.stdout[-4000:], file=sys.stderr)
        print(r.stderr[-4000:], file=sys.stderr)
        raise SystemExit(1)
    for line in r.stdout.splitlines():
        if line and not line.startswith("WORKER_DONE"):
            print(line)


if __name__ == "__main__":
    main()
