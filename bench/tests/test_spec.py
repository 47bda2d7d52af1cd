"""Cells, mixes and metric readers are found by name; derived counts.

Run by hand: ``python -m pytest bench/tests -q`` (not part of tier-1).
"""

from __future__ import annotations

import json
import math

import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
import spec as bspec


def test_every_cell_resolves_to_its_files():
    bench = bspec.benchmark()
    for w in bench["workloads"]:
        c = bspec.cell(w["name"], bench)
        assert c["config"]["name"] == w["config"]
        assert c["traffic"]["name"] == w["traffic"]
        assert "devices" not in c["cell"]
        assert any(m["name"] == "setup_s" for m in c["end_to_end"])
        assert c["per_layer"]


def test_configuration_file_is_the_one_benchmark_names():
    bench = bspec.benchmark()
    for cfg in bench["configs"]:
        data = bspec.load_json(bspec.REPO / cfg["file"])
        assert data["name"] == cfg["name"]
        assert data["reduced"] == cfg["reduced"]


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        bspec.cell("no_such.cell")


def test_a_new_cell_needs_only_new_files(tmp_path, monkeypatch):
    bench = bspec.benchmark()
    bench["workloads"].append({"name": "sec5a_5k.extra", "config": "sec5a_5k",
                               "traffic": "steady_nomem", "chips": 1, "why": "test"})
    cells = tmp_path / "cells"
    cells.mkdir()
    (cells / "sec5a_5k.extra.json").write_text(json.dumps(
        {"engine": "flat", "probe_capacity": 1024,
         "max_arrivals_per_tick": 128, "trace_chunks": 1}))
    for sub in ("configs", "traffic", "metrics"):
        (tmp_path / sub).symlink_to(bspec.HERE / sub)
    monkeypatch.setattr(bspec, "HERE", tmp_path)
    c = bspec.cell("sec5a_5k.extra", bench)
    assert c["cell"]["probe_capacity"] == 1024
    assert bspec.laminar_kwargs(c)["airlock"] is False


def test_every_per_layer_metric_has_a_reader():
    for m in bspec.benchmark()["per_layer"]:
        assert callable(bspec.reader(m["name"]))


def test_readers_read_nothing_where_nothing_ran():
    ctx = {"init_state_s": 0.8, "compile_s": 3.0, "ticks": 40,
           "trace": {"busy_s": 0.0, "collective_s": 0.0, "collectives": 0}}
    assert bspec.reader("device_ms_per_tick")(ctx) is None
    ctx["trace"] = {"busy_s": 2.0, "collective_s": 0.04, "collectives": 8}
    assert bspec.reader("device_ms_per_tick")(ctx) == pytest.approx(50.0)


@pytest.mark.parametrize("lam,cap", [(1436.416023935276, 1664), (9191.0, 9856), (1.0, 128)])
def test_cap_rule(lam, cap):
    assert bspec.cap_floor(lam) == cap
    bspec.check_cap(cap, lam)
    with pytest.raises(ValueError):
        bspec.check_cap(cap - 128, lam)


def test_cap_rule_is_six_sigma_above_the_rate():
    x = 1436.416023935276 * 3.0
    assert bspec.cap_floor(x) == math.ceil((x + 6 * math.sqrt(x)) / 128) * 128


def test_cells_as_committed_meet_the_cap_rule_at_the_issue_rates():
    assert bspec.cell("exp5_5k.steady_airlock")["cell"]["max_arrivals_per_tick"] >= 1664


def test_attempted_and_failed_from_counter_deltas():
    before = {"arrived": 100, "dropped_capacity": 5}
    after = {"arrived": 1600, "dropped_capacity": 12}
    assert bspec.window_counts(before, after) == (1507, 7)


def test_seed_keys_are_distinct_past_32_bits():
    import numpy as np

    keys = {tuple(np.asarray(bspec.seed_keys(s)[0]).tolist())
            for s in (5, 2**32 + 5, 2**31 + 5, 2**33 + 5)}
    assert len(keys) == 4
