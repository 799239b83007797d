"""Work counted from shapes and real lengths, checked by hand: the
kernels' counts and the dense family's model FLOPs; the peaks table."""
import pytest

import bench_tiny  # noqa: F401  (puts the repo on sys.path)

from bench import costs
from bench.families import dense

V5E = "TPU v5 lite"


def test_decode_attention_by_hand():
    # 2 live sequences of 3 and 5 tokens, 4 query heads over 2 kv heads
    # of 8: QK and PV are 2 flops per multiply-add each
    fl, nb = costs.decode_attention([3, 5], h=4, hkv=2, dh=8)
    assert fl == 4 * 4 * 8 * (3 + 5)
    # K and V of 8 cached tokens in bf16, plus q and out of 2 rows
    assert nb == 2 * 2 * 8 * 2 * 8 + 2 * 2 * 4 * 8 * 2


def test_decode_padding_slots_are_not_work():
    assert costs.decode_attention([3, 0, 5, 0], 4, 2, 8) == \
        costs.decode_attention([3, 5], 4, 2, 8)


def test_prefill_attention_by_hand():
    # sequence 0: 3 new rows after 2 cached tokens attend to 3, 4, 5
    fl, nb = costs.prefill_attention([2], [3], h=4, hkv=2, dh=8)
    assert fl == 4 * 4 * 8 * (3 + 4 + 5)
    # K/V of 5 tokens read once, q and out of 3 rows
    assert nb == 2 * 2 * 8 * 2 * 5 + 2 * 3 * 4 * 8 * 2


def test_prefill_padding_rows_and_slots_are_not_work():
    # inactive slots (n_tok 0) and the window's padded rows (only n_tok
    # rows are counted, whatever the window length) add nothing
    assert costs.prefill_attention([2, 7, 0], [3, 0, 0], 4, 2, 8) == \
        costs.prefill_attention([2], [3], 4, 2, 8)


def test_model_flops_by_hand():
    m = {"d": 8, "h": 2, "hkv": 1, "dh": 4, "ff": 16, "vocab": 10,
         "layers": 3, "act": "silu"}
    per_layer = 8 * (2 + 2) * 4 + 2 * 4 * 8 + 3 * 8 * 16
    assert dense.layer_matmul_params(m) == per_layer
    assert dense.model_flops(m, new_tokens=5, attended=9, logit_rows=2) \
        == 2 * 5 * per_layer * 3 + 4 * 9 * 2 * 4 * 3 + 2 * 2 * 8 * 10
    relu2 = dict(m, act="relu2")
    assert dense.layer_matmul_params(relu2) == per_layer - 8 * 16


def test_roofline_takes_the_larger_bound():
    pk = costs.peaks(V5E)
    assert costs.roofline_s(197e12, 0, pk) == pytest.approx(1.0)
    assert costs.roofline_s(1.0, 819e9 * 2, pk) == pytest.approx(2.0)


def test_peaks_table():
    pk = costs.peaks(V5E)
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert pk["hbm_bytes"] == 16 * 2**30
    assert "TPU v5e" in pk["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        costs.peaks("cpu")
