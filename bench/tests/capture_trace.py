"""Record ``data/toy_trace.xplane.pb.gz``: one prefill tick and one
decode tick of a toy ``ServeEngine`` (the qwen3-8b block at toy widths
and the cells' head size, bf16, the Pallas kernels), traced on a TPU
inside a ``bench.window`` span as the harness traces a cell.

    python3 bench/tests/capture_trace.py <out.xplane.pb.gz>

Needs the chip; the test that reads the file runs anywhere.
"""
import gzip
import shutil
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

CONF = {
    "name": "toy128", "arch": "qwen3-8b", "family": "dense",
    "model": {
        "hidden_size": 512, "intermediate_size": 1024,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
        "num_hidden_layers": 2, "vocab_size": 2048, "hidden_act": "silu",
        "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": False, "qk_norm": True},
    "chips": 1, "tp": 1, "comm_backend": "xla", "dtype": "bfloat16",
    "serve": {"page_tokens": 16, "n_pages": 64, "max_batch": 8,
              "prefill_chunk": 128, "tick_tokens": 256, "max_seq": 512,
              "attn_impl": "kernel"},
}


def main(out: str) -> int:
    import jax

    from bench import harness
    from repro import serve
    devs = harness.devices_for(1, require_chip=True)
    eng, _ = harness.build({"conf": CONF, "root": REPO}, 1, devs)
    harness.warm(eng)
    for rid in range(2):
        eng.submit(serve.Request(rid=rid, prompt=list(range(7, 107 + rid)),
                                 max_new=4))
    tmp = Path(tempfile.mkdtemp())
    try:
        jax.profiler.start_trace(str(tmp),
                                 profiler_options=harness._trace_options())
        with jax.profiler.TraceAnnotation("bench.window"):
            eng.tick(0.0)       # both prompts in one chunk: a prefill
            eng.tick(0.1)       # one decode token each
        jax.profiler.stop_trace()
        path = harness._xplane(tmp)
        with open(path, "rb") as f, gzip.open(out, "wb") as g:
            shutil.copyfileobj(f, g)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out}: {Path(out).stat().st_size} bytes; calls "
          f"{[c[0] for c in eng.exec.calls]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
