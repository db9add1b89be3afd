"""The split of a grid's device time by the program's named loop scopes,
its host steps, and the readers of the per-layer metrics built on them."""
import json
import os

import numpy as np
import pytest
from conftest import ROOT, tiny_cell

import scopes as S
import trace_reduce as TR

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_scopes.json")
TRIP_METRICS = ("loop_us_per_iter", "pop_us_per_iter", "rx_us_per_iter",
                "handlers_us_per_iter", "commit_us_per_iter",
                "loop_copy_us_per_iter")
NEW_METRICS = TRIP_METRICS + ("grid_prepare_ms", "grid_fetch_ms")


@pytest.fixture(scope="module")
def fixture():
    with open(FIXTURE) as f:
        return json.load(f)


def harness_trace(fixture):
    """The fixture as the harness's own trace holds it: the program's
    steps among the host spans."""
    return {"ops": fixture["ops"], "modules": fixture["modules"],
            "spans": fixture["spans"] + fixture["host_spans"]}


class FakeRun:
    def __init__(self, state, trace=None):
        self.grids = [{"groups": [{"state": state, "chip": 0}],
                       "seeds": [0]}]
        self.trace = trace


def test_instruction_scopes(fixture):
    sc = S.instruction_scopes(fixture["hlo"])
    assert sc["fusion.1"] == ("fusion", "sim.commit")    # named inside
    assert sc["fusion.2"] == ("fusion", "sim.pop")       # its own name
    assert sc["copy.1"] == ("copy", "sim.commit")
    assert sc["reduce-window.1"] == ("reduce-window", "sim.commit")
    assert sc["fusion.3"] == ("fusion", None)            # lane select
    assert sc["while.1"] == ("while", None)
    assert sc["fusion.4"] == ("fusion", "sim.handlers")  # the outermost
    assert sc["fusion.5"] == ("fusion", "sim.rx")
    assert sc["fusion.6"] == ("fusion", "sim.setup")


def test_summarize_partitions_the_sweep_program(fixture):
    ev = harness_trace(fixture)
    out = S.summarize(ev, S.instruction_scopes(fixture["hlo"]),
                      fixture["trips"])
    # per trip of 2: the loop's self time (200) and the lane select (50)
    # go to None; the other program's copy is left out
    assert out["per_trip_s"] == pytest.approx({
        "sim.commit": 75e-9, "sim.pop": 75e-9, "copy": 25e-9,
        "sim.handlers": 100e-9, "sim.rx": 50e-9, None: 125e-9,
        "sim.setup": 25e-9})
    assert TR.summarize(ev)["steps_s"] == pytest.approx({
        "experiment.group": 1250e-9, "experiment.build": 40e-9,
        "experiment.dispatch": 60e-9, "experiment.execute": 1000e-9,
        "experiment.fetch": 150e-9})
    # the parts add up to the program's busy time, within the program's
    # time that the harness reads (1000, of which 50 with no operation)
    assert sum(out["per_trip_s"].values()) * 2 == pytest.approx(950e-9)
    assert TR.summarize(ev)["module_s"]["jit__sweep"] == \
        pytest.approx(1000e-9)


def test_metric_readers_on_the_fixture(fixture, run_module, monkeypatch):
    ev = harness_trace(fixture)
    red = S.summarize(ev, S.instruction_scopes(fixture["hlo"]),
                      fixture["trips"])
    monkeypatch.setattr(S, "reading", lambda run: red)
    run = FakeRun({"iterations": np.array([[1, 2]], np.int32)},
                  TR.summarize(ev))
    got = {m: run_module.metric_reader(ROOT, m)(run) for m in NEW_METRICS}
    assert got == pytest.approx({
        "loop_us_per_iter": 0.5, "pop_us_per_iter": 0.075,
        "rx_us_per_iter": 0.05, "handlers_us_per_iter": 0.1,
        "commit_us_per_iter": 0.075, "loop_copy_us_per_iter": 0.025,
        "grid_prepare_ms": 1e-4, "grid_fetch_ms": 1.5e-4})


def test_readers_read_nothing_without_the_counter(run_module):
    """A program that keeps no ``iterations`` leaf gives no reading per
    trip, and nothing is profiled."""
    S.reading.cache_clear()
    run = FakeRun({"events_processed": np.array([[3]])},
                  {"module_s": {"jit__sweep": 1.0}})
    for m in TRIP_METRICS:
        assert run_module.metric_reader(ROOT, m)(run) is None


def test_reading_profiles_the_grid_on_the_cpu(run_module, tmp_path):
    """The window's profile of a tiny grid on the CPU holds the program's
    five steps; it has no chip operation, so no scope metric reads; an
    untraced run reads nothing."""
    cell = tiny_cell()
    run_module.warm_up(cell, 3)
    win = run_module.window(cell, seed=3, seconds=0.0,
                            profile_dir=str(tmp_path))
    ev = TR.extract(str(tmp_path), 1)
    (w0, w1), = [(s, s + d) for n, s, d in ev["spans"]
                 if n == TR.WINDOW_SPAN]
    steps = TR.step_seconds(ev["spans"], w0, w1)
    for step in ("experiment.group", "experiment.build",
                 "experiment.dispatch", "experiment.execute",
                 "experiment.fetch"):
        assert steps[step] > 0
    run = run_module.Reading(cell, win, None, 0.0, ev)
    S.reading.cache_clear()
    red = S.reading(run)
    S.reading.cache_clear()
    (group,) = win["grids"][0]["groups"]
    assert red["trips"] == S.max_iterations(group["state"]) > 0
    assert red["per_trip_s"] == {}
    assert S.reading(run_module.Reading(cell, win, None, 0.0)) is None
    S.reading.cache_clear()
