"""bench/counts.py against values worked out by hand at both
configurations' published widths."""
import json
from pathlib import Path

import pytest

from bench import counts
from bench.peaks import PEAKS, peaks_for

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def dims(name):
    return counts.Dims.from_config(json.loads((CONFIGS / name).read_text()))


@pytest.fixture
def internlm2():
    return dims("internlm2_1_8b_pd.json")


@pytest.fixture
def qwen():
    return dims("qwen1_5_4b.json")


def test_layer_params(internlm2, qwen):
    # 2048 x 128 x (16 + 16 + 8 + 8) + 3 x 2048 x 8192
    assert counts.layer_matmul_params(internlm2) == 12_582_912 + 50_331_648
    # 2560 x 128 x (20 + 20 + 20 + 20) + 3 x 2560 x 6912
    assert counts.layer_matmul_params(qwen) == 26_214_400 + 53_084_160


def test_linear_flops_per_token(internlm2, qwen):
    # 2 x (24 x 62,914,560 + 2048 x 92,544)
    assert counts.linear_flops_per_token(internlm2) == 3_398_959_104
    # 2 x (40 x 79,298,560 + 2560 x 151,936)
    assert counts.linear_flops_per_token(qwen) == 7_121_797_120


def test_prefill_chunk_flops(internlm2):
    # 64 tokens at positions 64..127 attend over 65..128 keys: 6,176 in all;
    # attention per key per token: 24 layers x 4 x 16 x 128 = 196,608
    assert counts.prefill_chunk_flops(internlm2, 64, 64) == \
        64 * 3_398_959_104 + 196_608 * 6_176


def test_decode_step_flops(qwen):
    # two sequences of 10 and 20 keys; 40 x 4 x 20 x 128 = 409,600 per key
    assert counts.decode_step_flops(qwen, [10, 20]) == \
        2 * 7_121_797_120 + 409_600 * 30


def test_paged_kernel(internlm2, qwen):
    # one layer, one sequence of 100 tokens (7 pages of 16)
    assert counts.paged_kernel_flops(internlm2, [100]) == 4 * 16 * 128 * 100
    # q f32 + o bf16: 16 x 128 x 6; table 7 x 4 + length 4; K and V of
    # 100 tokens: 2 x 100 x 8 x 128 x 2
    assert counts.paged_kernel_bytes(internlm2, [100], 16) == \
        12_288 + 32 + 409_600
    # Qwen: 20 x 128 x 6 + (4 x 4 + 4) + 2 x 50 x 20 x 128 x 2
    assert counts.paged_kernel_bytes(qwen, [50], 16) == 15_360 + 20 + 512_000


def test_roofline_is_the_larger_bound(internlm2):
    v5e = peaks_for("TPU v5 lite")
    f = counts.paged_kernel_flops(internlm2, [100])
    b = counts.paged_kernel_bytes(internlm2, [100], 16)
    assert counts.roofline_seconds(f, b, v5e) == pytest.approx(421_920 / 819e9)


def test_unknown_device_kind_is_an_error():
    assert "TPU v5 lite" in PEAKS
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
