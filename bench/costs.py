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
