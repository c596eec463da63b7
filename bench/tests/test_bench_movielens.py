"""The MovieLens cell at a size the CPU holds: its files resolve, its
table has the published structure, a sound run is correct against the
reference, and runs with the timed path broken underneath, and the
bfloat16 control, are not."""

import dataclasses
import importlib.util
import json
import os
import time
from unittest import mock

import numpy as np
import pytest

import harness
from test_bench_harness import (_answer_altered, _half_the_rows,
                                _state_unchanged)

CELL = "movielens_lmf_fit"
# a twentieth of the users and movies, the published rank and degree
# floor; the top degree is capped by the movies there are
SMALL = {"users": 302, "movies": 185, "ratings": 20000}
SERIAL = {"scheme": "serial", "parallelism": "singleton"}


def _cell():
    return harness.resolve(CELL, config_override=SMALL)


def _run(seed=2**33 + 41):
    query = harness.Driver.query

    def serial_query(self, s):
        return dataclasses.replace(query(self, s), hints=SERIAL)

    with mock.patch.object(harness.Driver, "query", serial_query):
        return harness.run(_cell(), seed, 0.5, False,
                           started=time.perf_counter(), require_chip=False)


def test_the_cell_resolves_to_its_files():
    cell = harness.resolve(CELL)
    assert cell.chips == 1 and cell.traffic["task"] == "lmf"
    assert [m["name"] for m in cell.end_to_end] == ["fit_s", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert names == ["epoch_ms.lmf", "update_bytes_per_row"]
    for name in names:
        assert os.path.exists(harness.reader_path(name))
    assert cell.config["reduced"] == []
    assert cell.module.task_args(cell.config, "lmf") == {
        "n_rows": 6040, "n_cols": 3706, "rank": 50, "mu": 0.01,
        "alpha0": 0.01}


def test_the_published_degrees():
    cfg = harness.resolve(CELL).config
    deg = _cell().module.user_degrees(cfg)
    assert deg.shape == (6040,) and int(deg.sum()) == 1000209
    assert (deg.min(), int(np.median(deg)), deg.max()) == (
        20, 96, 2314)


def test_the_table_is_sorted_by_user_with_distinct_pairs():
    cell = _cell()
    data = cell.module.generate(cell.config, harness.Seeds(7).table_key())
    i, j, v = (np.asarray(data[k]) for k in "ijv")
    assert i.shape == (SMALL["ratings"],)
    assert np.all(np.diff(i) >= 0)
    pairs = i.astype(np.int64) * SMALL["movies"] + j
    assert len(np.unique(pairs)) == len(pairs)
    assert np.bincount(i, minlength=SMALL["users"]).min() >= 20
    assert set(np.unique(v)) <= {1.0, 2.0, 3.0, 4.0, 5.0}
    assert 3.3 < v.mean() < 3.9


def test_epoch_work_at_the_published_rank():
    cfg = harness.resolve(CELL).config
    mod = _cell().module
    ops, nbytes = mod.epoch_work(cfg, "lmf", 1)
    assert ops == 600 * 1000209 and nbytes == 812 * 1000209
    ops2, nbytes2 = mod.epoch_work(cfg, "lmf", 2)
    assert ops2 == 2 * ops and nbytes2 == (12 + 1600) * 1000209


def test_a_sound_run_is_correct_and_writes_two_rows_per_rating():
    result, reasons = _run()
    assert reasons == [] and result["correct"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    for c in result["checks"].values():
        assert c["value"] <= c["limit"]
    assert set(result["metrics"]) == {"fit_s", "setup_s"}
    reader = harness.load_reader("update_bytes_per_row")
    assert reader(None) == 2 * 50 * 4  # one row of L and of R, float32
    json.dumps(result)


@pytest.mark.parametrize(
    "fault", [_state_unchanged, _half_the_rows, _answer_altered],
    ids=lambda f: f.__name__)
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    result, reasons = _run(seed=2**32 + 9)
    assert not result["correct"] and reasons
    over = [k for k, c in result["checks"].items() if c["value"] > c["limit"]]
    assert over, result["checks"]


def test_the_control_is_not_correct():
    path = os.path.join(harness.BENCH, "control.py")
    spec = importlib.util.spec_from_file_location("bench_control", path)
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    cell = _cell()
    reading = control.readings(harness, cell, 2**33 + 29)
    over = [k for k, limit in cell.limits.items() if reading[k] > limit]
    assert over, reading
