"""The model FLOP count and the peaks table."""
import json
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP.parent))

from chip import harness  # noqa: E402
from chip.flops import matmul_params, train_step_flops  # noqa: E402


def config(name):
    return json.loads((CHIP / "configs" / f"{name}.json").read_text())


def test_olmo_1b_six_layers_matches_the_hand_count():
    # 6 x (4 x 2048^2 + 3 x 2048 x 8192) + 50304 x 2048 = 0.506e9, and
    # 8192 tokens x (6 x 0.506e9 + 12 x 6 x 2048 x 2048) = 27.3e12
    m = config("olmo1b-6l")["model"]
    assert matmul_params(m) == pytest.approx(0.506e9, rel=5e-3)
    assert train_step_flops(m, 4, 2048) == pytest.approx(27.4e12, rel=1e-2)


def test_olmo_1b_sixteen_layers_per_token():
    # the published depth: 1.18e9 matmul parameters, 7.87e9 FLOPs a token
    m = dict(config("olmo1b-6l")["model"], n_layers=16)
    assert matmul_params(m) == pytest.approx(1.18e9, rel=5e-3)
    per_token = train_step_flops(m, 26, 2048) / (26 * 2048)
    assert per_token == pytest.approx(7.87e9, rel=5e-3)


def test_peaks_are_keyed_by_device_kind():
    assert harness.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError):
        harness.peak_flops("TPU v9 imaginary")
