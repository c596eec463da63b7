"""The control of each cell's check, at a size the CPU holds: the plain
reference computed in bfloat16 in the program's place must come out as
not correct against the cell's limits."""

import importlib.util
import os

import pytest

import harness
from conftest import SMALL


def _control():
    path = os.path.join(harness.BENCH, "control.py")
    spec = importlib.util.spec_from_file_location("bench_control", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("name", ["forest_logreg_fit", "forest_select_open"])
def test_the_control_is_not_correct(name):
    spec = harness.load_spec()
    config = {w["name"]: w["config"] for w in spec["workloads"]}[name]
    cell = harness.resolve(name, spec, config_override=SMALL[config])
    reading = _control().readings(harness, cell, 2**33 + 29)
    over = [k for k, limit in cell.limits.items() if reading[k] > limit]
    assert over, reading
