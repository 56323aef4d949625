"""A configuration, a traffic mix, a cell and a per-layer metric added as
files and entries are found by name; nothing else is edited."""
import json
import os

import pytest

import tinycell  # noqa: F401  (puts the checkout on sys.path)
from bench import harness


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def test_new_cell_found_by_name(tmp_path):
    root = str(tmp_path)
    spec = {
        "configs": [{"name": "m", "source": "x", "reduced": [], "why": "x",
                     "file": "bench/configs/m.json"}],
        "workloads": [{"name": "m.t", "config": "m", "traffic": "t",
                       "chips": 1, "why": "x"}],
        "end_to_end": [{"name": "e2e", "unit": "s"},
                       {"name": "other_e2e", "unit": "s",
                        "workloads": ["elsewhere"]}],
        "per_layer": [{"name": "x_pct", "unit": "%",
                       "workloads": ["m.t"]},
                      {"name": "y_pct", "unit": "%"},
                      {"name": "z_pct", "unit": "%",
                       "workloads": ["elsewhere"]}]}
    _write(os.path.join(root, "BENCHMARK.json"), json.dumps(spec))
    _write(os.path.join(root, "bench/configs/m.json"), '{"hidden_dim": 7}')
    _write(os.path.join(root, "bench/traffic/t.json"), '{"k": 3}')
    _write(os.path.join(root, "bench/limits/m.t.json"),
           '{"loss_gap": {"limit": 1}}')
    _write(os.path.join(root, "bench/metrics/x_pct.py"),
           "def read(ctx):\n    return 42.0\n")
    _write(os.path.join(root, "bench/metrics/y_pct.py"),
           "def read(ctx):\n    return None\n")
    cell = harness.load_cell("m.t", root=root)
    assert cell.config == {"hidden_dim": 7}
    assert cell.traffic == {"k": 3}
    assert cell.limits == {"loss_gap": {"limit": 1}}
    assert [m["name"] for m in cell.end_to_end] == ["e2e"]
    assert [m["name"] for m in cell.per_layer] == ["x_pct", "y_pct"]
    assert harness.load_reader(cell, "x_pct")(None) == 42.0
    assert harness.load_reader(cell, "y_pct")(None) is None
    with pytest.raises(KeyError):
        harness.load_cell("m.absent", root=root)


def test_real_benchmark_parts_exist():
    spec = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        for m in cell.per_layer:
            assert callable(harness.load_reader(cell, m["name"]))
        assert set(harness.NUMBERS) <= set(cell.limits)


def test_unknown_device_has_no_peaks():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")
