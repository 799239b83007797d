"""The work the algorithm needs, counted from shapes and real lengths.

Nothing here reads what the implementation touches: padding slots of a
batch, table slots past a sequence's length and padded window rows are
not work.  ``peaks`` reads ``bench/peaks.json`` by JAX's
``device_kind``; a kind not in the table is an error.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def decode_attention(lens, h: int, hkv: int, dh: int, kv_bytes: int = 2,
                     io_bytes: int = 2) -> tuple:
    """(flops, bytes) of one paged decode attention call for one layer
    on one chip: one query per sequence with ``lens[i] > 0`` against its
    ``lens[i]`` cached tokens.  ``h`` and ``hkv`` are this chip's heads."""
    lens = np.asarray(lens, np.int64)
    lens = lens[lens > 0]
    flops = 4 * h * dh * int(lens.sum())
    nbytes = (2 * hkv * dh * kv_bytes * int(lens.sum())
              + 2 * lens.size * h * dh * io_bytes)
    return float(flops), float(nbytes)


def prefill_attention(start, n_tok, h: int, hkv: int, dh: int,
                      kv_bytes: int = 2, io_bytes: int = 2) -> tuple:
    """(flops, bytes) of one paged prefill-window attention call for one
    layer on one chip: row ``j < n_tok[i]`` of sequence ``i`` attends to
    its first ``start[i] + j + 1`` tokens; each sequence's
    ``start + n_tok`` cached tokens are read once."""
    s = np.asarray(start, np.int64)
    n = np.asarray(n_tok, np.int64)
    s, n = s[n > 0], n[n > 0]
    attended = int((n * s + n * (n + 1) // 2).sum())
    flops = 4 * h * dh * attended
    nbytes = (2 * hkv * dh * kv_bytes * int((s + n).sum())
              + 2 * int(n.sum()) * h * dh * io_bytes)
    return float(flops), float(nbytes)


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies in one decoder layer (whole model,
    all chips): q, k, v, o and the feed-forward."""
    d, h, hkv, dh, ff = m["d"], m["h"], m["hkv"], m["dh"], m["ff"]
    glu = 3 if m["act"] == "silu" else 2
    return d * (h + 2 * hkv) * dh + h * dh * d + glu * d * ff


def model_flops(m: dict, new_tokens: int, attended: int,
                logit_rows: int) -> float:
    """Model FLOPs (whole model, all chips) of processing ``new_tokens``
    tokens that together attend to ``attended`` cached positions in
    each layer, and of ``logit_rows`` rows of the output head."""
    L = m["layers"]
    return float(2 * new_tokens * layer_matmul_params(m) * L
                 + 4 * attended * m["h"] * m["dh"] * L
                 + 2 * logit_rows * m["d"] * m["vocab"])
