"""The comparison fails its controls at a toy size on the CPU: the float32
reference put in the program's place with its state and products in
bfloat16, and with its products' inputs rounded to float8, each fails a
number the program passes. So does the reference over half the batch.
(On the chip the same readings, at the cells' own sizes, set the limits.)"""
import json

import pytest

import chip_bench_tiny as tiny
from chip import calibrate

SEEDS = [2**31 + 101, 2**31 + 102]


@pytest.fixture(scope="module")
def readings():
    config = json.loads((tiny.HERE / "tiny.json").read_text())
    traffic = json.loads((tiny.CHIP / "traffic" / "train.json").read_text())
    out = calibrate.training_readings(config, traffic, SEEDS, SEEDS[:1],
                                      log=lambda m: None)
    return config["limits"], out


def failed(numbers, limits):
    return [k for k, v in numbers.items() if not v <= limits[k]]


def test_program_passes(readings):
    limits, out = readings
    for seed in SEEDS:
        assert failed(out["program"][seed], limits) == []


@pytest.mark.parametrize("control", sorted(calibrate.CONTROLS))
def test_control_fails(readings, control):
    limits, out = readings
    assert failed(out["controls"][control][SEEDS[0]], limits)
