"""The benchmark's counts of operations and bytes (the dense reference's
and ``bench/counts.py``), against shapes worked out by hand for both
configurations."""
import json
from pathlib import Path

import pytest

from bench import counts
from bench.reference import dense

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def sizes(name):
    with open(CONFIGS / f"{name}.json") as f:
        return dense.sizes(json.load(f))


def test_qwen25_3b():
    s = sizes("qwen25_3b")
    # q, o: 2048 x 16 x 128 each; k, v: 2048 x 2 x 128 each; MLP 3 x
    # 2048 x 11008
    layer = 2 * 2048 * 2048 + 2 * 2048 * 256 + 3 * 2048 * 11008
    assert layer == 77_070_336
    assert dense.layer_linear_params(s) == layer
    # 36 layers and one 151936 x 2048 table, tied as embedding and LM
    # head, bf16
    assert dense.weight_bytes(s) == (36 * layer + 151936 * 2048) * 2
    assert dense.weight_bytes(s) == 6_171_394_048
    # K and V: 36 layers x 2 heads x 128 x 2 bytes, each
    assert dense.kv_bytes_per_token(s) == 36_864
    assert dense.row_bytes(s, 1089) == 36_864 * 1089 == 40_144_896
    # one token at a context of 1056: 2 per weight of the layers and the
    # head, and 4 x 36 layers x 16 heads x 128 x 1056 for attention
    assert dense.decode_flops(s, 1, 1056) == (
        2 * (36 * layer + 151936 * 2048) + 4 * 36 * 16 * 128 * 1056)
    assert dense.decode_flops(s, 32, 1056) == pytest.approx(207.45e9,
                                                             rel=1e-3)
    # weights read once (layers and head, not the embedding table) and
    # the rows' K and V over ctx + 1 positions
    assert dense.decode_min_bytes(s, 32, 1056) == (
        (36 * layer + 151936 * 2048) * 2 + 32 * 1057 * 36_864)


def test_olmo_1b():
    s = sizes("olmo_1b")
    layer = 4 * 2048 * 2048 + 3 * 2048 * 8192      # MHA: k, v as wide as q
    assert dense.layer_linear_params(s) == layer == 67_108_864
    # tied: one 50304 x 2048 table serves as embedding and LM head
    assert dense.weight_bytes(s) == (16 * layer + 50304 * 2048) * 2
    assert dense.weight_bytes(s) == 2_353_528_832
    assert dense.kv_bytes_per_token(s) == 16 * 16 * 128 * 2 * 2 == 131_072
    assert dense.row_bytes(s, 1089) == 142_737_408
    assert dense.decode_min_bytes(s, 1, 0) == (
        (16 * layer + 50304 * 2048) * 2 + 131_072)


def test_transfer_least_time():
    p = counts.PEAKS["TPU v5 lite"]
    # one chip: every byte is read and written over HBM
    one = counts.transfer_least_s([(0, 0, 100e6), (0, 0, 50e6)], p)
    assert one == pytest.approx(2 * 150e6 / 819e9)
    # across chips: device 0 sends 300 MB to 1 and 2, and reads it over
    # HBM; its 4 links carry 200 GB/s at most
    many = counts.transfer_least_s([(0, 1, 200e6), (0, 2, 100e6)], p)
    assert many == pytest.approx(max(300e6 / 819e9, 300e6 / 200e9))
    assert counts.transfer_least_s([], p) == 0.0


def test_unknown_device_is_an_error():
    with pytest.raises(ValueError):
        counts.peaks("TPU v9 imaginary")
    assert counts.peaks("TPU v5 lite").hbm_bw == 819e9
