"""Operation and byte counts against hand counts at MiniCPM-2B widths
(40 layers, d 2304, 36 heads x 64, FFN 5760, vocabulary 122,753, tied,
bf16)."""
import json
from pathlib import Path

import pytest

from bench import shapes

CONFIG = Path(__file__).parents[1] / "configs" / "minicpm-2b.json"


@pytest.fixture(scope="module")
def dims():
    return shapes.Dims.of(json.loads(CONFIG.read_text()))


def test_sizes(dims):
    # q, k, v, o: 4 x 2304 x 36 x 64; gate, up, down: 3 x 2304 x 5760
    assert dims.layer_matmul_params == 4 * 2304 * 2304 + 3 * 2304 * 5760
    assert dims.layer_matmul_params == 61_046_784
    assert dims.matmul_params == 40 * 61_046_784 + 2304 * 122_753
    assert dims.matmul_params == 2_724_694_272
    # 81 norm scales of 2304
    assert dims.weight_bytes == 2 * (2_724_694_272 + 81 * 2304)
    assert dims.kv_bytes_per_token == 40 * 2 * 36 * 64 * 2 == 368_640


def test_prefill(dims):
    flops, nbytes = dims.prefill(100)
    assert flops == (2 * 100 * 40 * 61_046_784      # projections
                     + 2 * 2304 * 122_753            # head, last token
                     + 40 * 2 * 36 * 64 * 100 * 101)  # causal attention
    assert flops == 490_801_549_824
    assert nbytes == 5_449_761_792 + 100 * 2304 * 2 + 100 * 368_640


def test_decode(dims):
    flops, nbytes = dims.decode([10, 20])
    assert flops == 2 * 2 * 2_724_694_272 + 40 * 4 * 36 * 64 * (10 + 20)
    assert nbytes == (5_449_761_792 + 2 * 2304 * 2
                      + (10 + 20 + 2) * 368_640)


def test_least_time_takes_the_larger_bound():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert shapes.least_seconds(197e12, 1.0, peaks) == pytest.approx(1.0)
    assert shapes.least_seconds(1.0, 819e9, peaks) == pytest.approx(1.0)
