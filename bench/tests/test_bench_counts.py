"""Operation and byte counts, the peaks table, and resolving every cell."""

import json
import os

import pytest

import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _module(name):
    path = os.path.join(BENCH, "configs", name + ".json")
    with open(path) as f:
        cfg = json.load(f)
    return cfg, harness._load_module(path[:-5] + ".py", f"t_{name}")


def test_forest_counts_by_hand():
    cfg, mod = _module("forest_covtype")
    # 4 d operations per row and lane; (d + 1) float32 per row, read once
    assert mod.epoch_work(cfg, "logreg", 1) == (4 * 581012 * 54,
                                                581012 * 55 * 4)
    assert mod.epoch_work(dict(cfg, rows=10, features=3), "svm", 8) == (
        4 * 10 * 3 * 8, 10 * 4 * 4)


@pytest.mark.parametrize("task", ["logreg", "svm"])
def test_forest_counts_use_the_published_width(task):
    """The kernel pads 54 features to 128 lanes; the count reads the
    configuration's 54, so it is the same whatever lowering ran."""
    cfg, mod = _module("forest_covtype")
    assert cfg["features"] == 54
    flops, nbytes = mod.epoch_work(cfg, task, 3)
    assert flops == 4 * cfg["rows"] * 54 * 3
    assert nbytes == cfg["rows"] * 55 * 4


def test_peaks_are_keyed_by_device_kind():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")


def test_every_workload_resolves_to_its_files():
    spec = harness.load_spec()
    names = [w["name"] for w in spec["workloads"]]
    assert names == ["forest_logreg_fit", "forest_select_open"]
    for name in names:
        cell = harness.resolve(name, spec)
        assert cell.chips == 1
        assert cell.traffic["loop"] in harness.DRIVERS
        for m in cell.per_layer:
            assert callable(harness.load_reader(m["name"]))
            assert m["moves"] in {e["name"] for e in cell.end_to_end}
        assert "model_gap" in cell.limits
        assert set(cell.limits) <= {"model_gap", "loss_gap"}
        assert hasattr(cell.module, "reference_fit")
    for c in spec["configs"]:
        path = os.path.join(harness.ROOT, c["file"])
        assert path.startswith(BENCH) and os.path.exists(path[:-5] + ".py")
