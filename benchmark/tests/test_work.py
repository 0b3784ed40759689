"""The dense work file's FLOP and byte functions against numbers worked by
hand."""

import json
import pathlib

from benchmark.lib.peaks import PEAKS, least_seconds, peaks
from benchmark.work import dense as work

import pytest

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


def test_smollm2_parameters_and_cache():
    m = model("smollm2-1.7b")
    # attention 4 x 2048 x 2048, feed-forward 3 x 2048 x 8192, two norms.
    layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert work.layer_matmul_params(m) == layer == 67_108_864
    held = 24 * (layer + 2 * 2048) + 49152 * 2048 + 2048  # tied head
    assert work.param_count(m) == held == 1_711_376_384
    assert work.weight_bytes(m) == 3_422_752_768
    # 2 (k, v) x 24 layers x 32 heads x 64 x 2 bytes = 192 KiB.
    assert work.kv_bytes_per_token(m) == 192 * 1024


def test_mistral_h16_parameters_and_cache():
    m = model("mistral-7b-v0.3-h16")
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert work.layer_matmul_params(m) == layer == 218_103_808
    held = 16 * (layer + 2 * 4096) + 2 * 32768 * 4096 + 4096  # untied head
    assert work.param_count(m) == held == 3_758_231_552
    assert round(work.param_count(m) / 1e9, 2) == 3.76
    # 2 x 16 layers x 8 heads x 128 x 2 bytes = 64 KiB.
    assert work.kv_bytes_per_token(m) == 64 * 1024


def test_span_flops_by_hand():
    m = model("smollm2-1.7b")
    # One new position after 9 cached ones, scored: 2 FLOPs a layer
    # parameter, attention over 10 keys, one vocabulary projection.
    layers = 2 * 24 * 67_108_864
    attention = 4 * 10 * 32 * 64 * 24
    head = 2 * 49152 * 2048
    assert work.span_flops(m, 9, 1, 1) == layers + attention + head
    # Three positions from empty: contexts 1 + 2 + 3.
    assert work.span_flops(m, 0, 3) == 3 * layers + 4 * 6 * 32 * 64 * 24


def test_step_bytes_and_bound():
    m = model("mistral-7b-v0.3-h16")
    # Positions that rows share are read once: the rows change nothing.
    for rows in (1, 32, 160):
        assert work.step_bytes(m, 1000, rows) == 7_516_463_104 + 1000 * 65536
    seconds, bound = least_seconds(1e9, work.step_bytes(m, 0, 1),
                                   peaks("TPU v5 lite"))
    assert bound == "bandwidth"
    assert seconds == pytest.approx(7_516_463_104 / 819e9)
    assert least_seconds(1e15, 1.0, peaks("TPU v5 lite"))[1] == "compute"


def test_unknown_device_raises():
    assert "TPU v5 lite" in PEAKS
    with pytest.raises(ValueError):
        peaks("TPU v9000")
