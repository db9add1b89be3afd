"""BENCHMARK.json against its contract, and cells found by name alone."""
import json
import os
import re
import shutil

import pytest
from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BM = json.load(f)
CELLS = [w["name"] for w in BM["workloads"]]


def test_top_level_keys_and_paths():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"][1].startswith(BM["paths"][0] + "/")
    assert 1 <= BM["run_seconds"] <= 51
    for c in BM["configs"]:
        assert c["file"].startswith(BM["paths"][0] + "/")


def test_names_units_and_sources():
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in BM[key]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for w in BM["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BM["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    import run
    c = run.load_cell(ROOT, cell)
    assert c["config"]["name"] == next(
        w["config"] for w in BM["workloads"] if w["name"] == cell)
    for m in c["per_layer"]:
        assert callable(run.metric_reader(ROOT, m["name"]))
    reported = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert c["per_layer"]


@pytest.mark.parametrize("cell", CELLS)
def test_config_lists_its_cuts(cell):
    import run
    c = run.load_cell(ROOT, cell)
    (entry,) = [x for x in BM["configs"] if x["name"] == c["config"]["name"]]
    assert set(entry["reduced"]) == set(c["config"]["reduced"])
    for key, cut in c["config"]["reduced"].items():
        assert c["config"][key] == cut["run"] != cut["source"]
    assert c["config"]["guarantees"]["dropped_events"] == 0


def test_per_layer_moves_one_reported_metric():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in moved.get("workloads", CELLS)


def test_new_cell_is_new_files_only(tmp_path):
    """A configuration, a traffic mix with a stimulus kind and a fault kind
    of its own, and a per-layer metric, added as new files plus
    BENCHMARK.json entries, resolve with no file edited."""
    import run
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    bm = json.loads(json.dumps(BM))
    with open(os.path.join(ROOT, BM["configs"][0]["file"])) as f:
        cfg = dict(json.load(f), name="new-config", k=32)
    (tmp_path / "bench/configs/new-config.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/new-traffic.json").write_text(json.dumps(
        {"kind": "new-kind", "params": {"rate": 2.0},
         "knobs": {"dn_th": [4]}, "stimulus_seeds": [1, 2, 3, 4, 5, 6, 7, 8],
         "faults": {"kind": "new-fault", "params": {"g": 3},
                    "at_fraction_of_sim_len": {"t": 0.5}}}))
    (tmp_path / "bench/stimulus/new-kind.py").write_text(
        "def generate(max_apps, n_childs, k, *, sim_len, seed, rate):\n"
        "    return k, seed, rate * sim_len\n")
    (tmp_path / "bench/faults/new-fault.py").write_text(
        "def generate(k, *, t, g):\n"
        "    return [(t, 2, g, 0)]\n")
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(run):\n    return 1.0\n")
    bm["configs"].append(dict(BM["configs"][0], name="new-config",
                              file="bench/configs/new-config.json"))
    bm["workloads"].append({"name": "new-cell", "config": "new-config",
                            "traffic": "new-traffic", "chips": 1,
                            "why": "x"})
    bm["per_layer"].append({"name": "new_metric", "unit": "%",
                            "better": "lower", "source": "device_trace",
                            "layer": "sim loop", "moves": "sim_events_per_s",
                            "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    c = run.load_cell(str(tmp_path), "new-cell")
    assert c["config"]["k"] == 32 and len(c["traffic"]["stimulus_seeds"]) == 8
    assert "new_metric" in [m["name"] for m in c["per_layer"]]
    assert run.metric_reader(str(tmp_path), "new_metric")(None) == 1.0
    (shape,) = run.group_shapes(c["config"])
    *stim, faults = run.reference_inputs(c, shape, 5)
    assert stim == [32, 5, 2.0 * c["config"]["sim_len"]]
    assert faults == [(0.5 * c["config"]["sim_len"], 2, 3, 0)]
    old = run.load_cell(str(tmp_path), CELLS[0])
    assert "new_metric" not in [m["name"] for m in old["per_layer"]]
    arr, _, _, _ = run.reference_inputs(
        old, run.group_shapes(old["config"])[0], 5)
    assert arr.shape == (old["config"]["max_apps"],)
