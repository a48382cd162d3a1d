"""Operations and bytes at qwen2.5-3b widths, worked out by hand, and the
table of peaks."""
import json
from pathlib import Path

import pytest

from bench.costs import (Shapes, attention_call, layer_matmul_params,
                         lmhead_call, position_flops, positions_flops,
                         roofline_seconds)
from bench.peaks import peaks

CFG = json.loads((Path(__file__).parents[1] / "configs"
                  / "qwen2.5-3b.json").read_text())


@pytest.fixture(scope="module")
def s():
    return Shapes.from_config(CFG)


def test_shapes(s):
    assert (s.d_model, s.n_heads, s.n_kv_heads, s.head_dim) == (2048, 16, 2, 128)
    assert (s.d_ff, s.vocab, s.n_layers) == (11008, 151936, 36)


def test_position_flops_by_hand(s):
    # Q 2048x2048, K and V 2048x256 each, O 2048x2048, MLP 3 x 2048x11008
    per_layer = 2048 * 2048 + 2 * 2048 * 256 + 2048 * 2048 + 3 * 2048 * 11008
    assert layer_matmul_params(s) == per_layer == 77_070_336
    dense = 2 * (36 * per_layer + 2048 * 151936)
    assert dense == 6_171_394_048
    # attention at a context of 1000 keys: 4 * 36 layers * 16 heads * 128 * 1000
    assert position_flops(s, 1000) == pytest.approx(dense + 294_912_000)
    # positions 0, 1, 2 attend 1, 2 and 3 keys
    assert positions_flops(s, [0], [3]) == pytest.approx(
        3 * dense + 4 * 36 * 16 * 128 * 6)


def test_attention_call_by_hand(s):
    # two rows of 32 queries, starting at 0 and 100
    flops, nbytes = attention_call(s, [0, 100], 32)
    keys = (32 * 33 / 2) + (32 * 100 + 32 * 33 / 2)
    assert flops == pytest.approx(4 * 16 * 128 * keys)
    qo = 2 * 2 * 32 * 16 * 128          # Q and output, both rows
    kv = 2 * (32 + 132) * 2 * 128       # K and V of the live positions
    assert nbytes == pytest.approx((qo + kv) * 2)


def test_lmhead_call_by_hand(s):
    flops, nbytes = lmhead_call(s, 1024)
    assert flops == 2 * 1024 * 2048 * 151936
    assert nbytes == (2048 * 151936 + 1024 * 2048) * 2 + 1024 * 8
    t = roofline_seconds(flops, nbytes, peaks("TPU v5 lite"))
    assert t == pytest.approx(flops / 197e12)      # compute-bound


def test_peaks_known_and_unknown():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
