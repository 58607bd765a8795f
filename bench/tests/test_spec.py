"""Every cell's configuration, traffic mix and metric readers are files
found by name, and each cell reports what the contract asks of it."""

import json
import os

import pytest

import spec

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_pieces_found_by_name(name):
    cell = spec.cell(BENCH, name)
    cfg = spec.config(BENCH, cell)
    assert cfg["name"] == cell["config"]
    mix = spec.traffic(cell)
    assert mix["clients"] > 0 and mix["max_queries"] > mix["clients"]
    for trace in (0, 1):
        ms = spec.metrics(BENCH, cell, trace)
        assert ms, f"{name} reports no metric with --trace {trace}"
        for m in ms:
            assert callable(spec.reader(m, trace))
    e2e = {m["name"] for m in spec.metrics(BENCH, cell, 0)}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in spec.metrics(BENCH, cell, 1):
        assert m["moves"] in e2e


def test_config_files_state_what_is_reduced():
    for c in BENCH["configs"]:
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert cfg[key] != cfg["source_values"][key]
        assert cfg["limits"]["bad_answers"] == 0


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        spec.cell(BENCH, "no-such.cell")
    with pytest.raises(FileNotFoundError):
        spec.reader({"name": "no_such_metric"}, 1)
