"""The readings that set a cell's ``correct`` limit, on the chip at the
cell's own size: for each seed, the widest logit gap of the program's
served greedy tokens (sound runs), and the widest gap of the tokens the
float8 control ranks first on the same prompts and tokens.  One process
serves a short window at the cell's own load for every seed in turn.
The benchmark's own runs do not run this.

    python3 bench/control.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3,...

Prints one JSON line per seed, each reading judged by the cell's own
``correct`` limits (the control has to come out not correct), then the
largest sound reading and the smallest control reading.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def readings(root, workload, seed, seconds, **kw) -> dict:
    """One seed: the served tokens and the control's, each judged by
    ``check.verdict`` against the cell's own limits."""
    from bench import check, harness
    sv = harness.serve(root, workload, seed, seconds, False, **kw)
    conf = sv["cell"]["conf"]
    out = {"seed": seed}
    for kind, ctl in (("sound", False), ("control", True)):
        ok, checks = check.verdict(root, conf, seed, sv["served"],
                                   conf["correct"], control=ctl)
        gap = checks["max_logit_gap"]["value"]
        out[kind] = gap if gap != float("inf") else None
        out[f"{kind}_correct"] = ok
        out["tokens"] = checks["tokens_checked"]["value"]
        gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rows.append(readings(ROOT, args.workload, seed, args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    sound = [r["sound"] for r in rows if r["sound"] is not None]
    ctrl = [r["control"] for r in rows if r["control"] is not None]
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "lower": max(sound) if sound else None,
                      "upper": min(ctrl) if ctrl else None,
                      "sound_correct": all(r["sound_correct"] for r in rows),
                      "control_correct": any(r["control_correct"]
                                             for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
