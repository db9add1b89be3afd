"""A configuration on the 2D torus: a tiny torus cell checked against its
own reference (``bench/reference_mesh.py``) through the harness, sound
and under the control, and the readers of the torus cell's per-layer
metrics on hand-made grids and the scope fixture's trace."""
import json
import os

import numpy as np
import pytest
from conftest import ROOT, steered_devices, tiny_cell

import control
import scopes as S

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_scopes.json")


def torus_cell():
    """A tiny cell on a 3x3 torus of 8 GMNs, where wraparound shortens
    some routes (on a 2x2 grid the torus is the mesh)."""
    cell = tiny_cell()
    cell["config"] |= {"name": "tiny-torus", "m": 32, "k": 8,
                       "topology": "torus2d", "reference": "reference_mesh"}
    return cell


def _run(run_module, cell):
    return run_module.run_cell(cell, seed=2**31 + 11, seconds=0.1,
                               traced=False, devices=steered_devices)


def test_torus_cell_reads_correct(run_module):
    out = _run(run_module, torus_cell())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["events_dropped"]["value"] == 0


def test_torus_cell_control_reads_not_correct(run_module, monkeypatch):
    from repro.core.experiment import ExperimentSpec
    cell = torus_cell()
    monkeypatch.setattr(ExperimentSpec, "run",
                        control.control_run(run_module, cell))
    out = _run(run_module, cell)
    assert not out["correct"]
    assert out["checks"]["events_dropped"]["value"] > 0
    assert out["checks"]["lanes_failed"]["value"] > 0


class FakeRun:
    def __init__(self, grids, trace=None):
        self.grids, self.trace = grids, trace


def _grid(beacons_rx, rx_trips):
    return {"groups": [{"chip": 0, "state": {
        "beacons_rx": np.array(beacons_rx, np.int32),
        "rx_trips": np.array(rx_trips, np.int32),
        "iterations": np.array([[1, 2]], np.int32)}}]}


def test_rx_events_per_trip(run_module):
    """(6 + 9 + 4 + 4) deliveries over (2 + 3 + 1 + 2) trips, every lane
    of both grids."""
    read = run_module.metric_reader(ROOT, "rx_events_per_trip")
    run = FakeRun([_grid([[6, 9]], [[2, 3]]), _grid([[4, 4]], [[1, 2]])])
    assert read(run) == pytest.approx(23 / 8)
    assert read(FakeRun([_grid([[0, 0]], [[0, 0]])])) is None
    parent = FakeRun([{"groups": [{"state": {
        "beacons_rx": np.array([[6, 9]], np.int32)}}]}])
    assert read(parent) is None


def test_rx_us_per_delivery_on_the_fixture(run_module, monkeypatch):
    """The fixture's grid: 2 trips of 75 ns in ``sim.pop`` and 50 ns in
    ``sim.rx`` each, over 5 deliveries, is 0.05 us a delivery."""
    with open(FIXTURE) as f:
        fx = json.load(f)
    ev = {"ops": fx["ops"], "modules": fx["modules"],
          "spans": fx["spans"] + fx["host_spans"]}
    red = S.summarize(ev, S.instruction_scopes(fx["hlo"]), fx["trips"])
    monkeypatch.setattr(S, "reading", lambda run: red)
    read = run_module.metric_reader(ROOT, "rx_us_per_delivery")
    run = FakeRun([_grid([[3, 2]], [[1, 1]])])
    assert read(run) == pytest.approx(1e6 * (75e-9 + 50e-9) * 2 / 5)
    assert read(FakeRun([_grid([[0, 0]], [[0, 0]])])) is None
    monkeypatch.setattr(S, "reading", lambda run: None)
    assert read(run) is None


def test_readers_name_only_the_torus_cell(run_module):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    for name in ("rx_events_per_trip", "rx_us_per_delivery"):
        (m,) = [x for x in bm["per_layer"] if x["name"] == name]
        assert m["workloads"] == ["mppa-torus-interference"]
    c = run_module.load_cell(ROOT, "mppa-torus-interference")
    assert c["config"]["topology"] == "torus2d" and c["chips"] == 1
    assert run_module.reference(c).__name__ == "bench__reference_mesh"
    with open(os.path.join(ROOT, "bench", "configs",
                           "m256-k16-hier.json")) as f:
        hier = json.load(f)
    differ = {k for k in run_module.SIM_KEYS + ("sim_len",)
              if c["config"][k] != hier[k]}
    assert differ == {"topology"}
    assert c["config"]["reference"] == "reference_mesh"
