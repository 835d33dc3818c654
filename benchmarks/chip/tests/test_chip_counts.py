"""Operation and byte counts against hand values at published widths."""

import json
import pathlib

import pytest

from benchmarks.chip import counts

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


def test_olmo_1b_matmul_params():
    # 16 x (4 x 2048^2 + 3 x 2048 x 8192) + 2048 x 50304 head
    assert counts.matmul_params(model("olmo_1b")) == 1_176_764_416
    assert counts.matmul_params(model("olmo_1b")) == pytest.approx(
        1.18e9, rel=0.01)


def test_chatglm3_6b_14_layers_matmul_params():
    # 14 x (2 x 4096^2 + 2 x 4096 x 256 + 3 x 4096 x 13696) + 4096 x 65024
    assert counts.matmul_params(model("chatglm3_6b")) == 3_121_610_752
    assert counts.matmul_params(model("chatglm3_6b")) == pytest.approx(
        3.12e9, rel=0.01)


def test_token_and_prefill_flops():
    m = model("olmo_1b")
    p = counts.matmul_params(m)
    per_key = 4 * 16 * 16 * 128
    assert counts.token_flops(m, 1000) == 2 * p + per_key * 1000
    # 3 tokens after 5 cached: keys 6 + 7 + 8
    assert counts.prefill_flops(m, 3, 5) == 3 * 2 * p + per_key * 21
    assert counts.decode_flops(m, [10, 20]) == pytest.approx(
        counts.token_flops(m, 10) + counts.token_flops(m, 20))


def test_paged_decode_cost_from_lengths():
    m = model("chatglm3_6b")
    flops, nbytes = counts.paged_decode_cost(m, [100, 300])
    assert flops == 4 * 14 * 32 * 128 * 400
    kv = 2 * 14 * 2 * 128 * 2              # K and V bytes per token
    qo = 2 * 14 * 32 * 128 * 2             # query in, output out, per lane
    assert nbytes == kv * 400 + 2 * qo
    assert counts.kv_bytes_per_token(model("olmo_1b")) == 128 * 1024


def test_peaks_and_roofline():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert counts.roofline_s(197e12, 1.0, p) == pytest.approx(1.0)
    assert counts.roofline_s(1.0, 819e9, p) == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        counts.peaks("cpu")
