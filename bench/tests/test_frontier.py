"""A configuration of several groups: a tiny frontier on two host devices
driven through the harness, sound and broken; the one-group specs as
they were built before configurations could list shapes; the readers of
the frontier's per-layer metrics; and a reference found by name."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest
from conftest import BENCH, ROOT, TINY_CONFIG, tiny_cell

import trace_reduce as TR

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures")
BROKEN = ("control", "state_unchanged", "half_lanes", "answer_altered",
          "groups_swapped", "group_missing")
SCOPE_METRICS = ("pop_us_per_iter", "rx_us_per_iter", "handlers_us_per_iter",
                 "commit_us_per_iter", "loop_copy_us_per_iter")

with open(os.path.join(FIXTURES, "specs_one_group.json")) as f:
    ONE_GROUP_SPECS = json.load(f)["specs"]


@pytest.fixture(scope="module")
def frontier_cases():
    """Every case of ``frontier_cases.py`` in one process that sees two
    host devices, so the programs compile once."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "frontier_cases.py"), "sound",
         "steps", *BROKEN], env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line[len("CASE "):])
             for line in out.stdout.splitlines() if line.startswith("CASE ")]
    return {line["case"]: line for line in lines}


def test_tiny_frontier_is_correct_on_distinct_devices(frontier_cases):
    c = frontier_cases["sound"]
    assert c["correct"] and c["failed"] == 0, c
    assert c["attempted"] % 8 == 0 and c["attempted"] > 0
    assert c["k"] == [1, 4] and sorted(c["chips"]) == [0, 1]


def test_tiny_frontier_host_steps_read(frontier_cases):
    """The harness's profile of a grid of two groups holds each group's
    four steps (pmap: no group span), and the grid's readers read them;
    a scope metric reads nothing: there are two programs on two chips."""
    from collections import Counter
    c = frontier_cases["steps"]
    assert c["steps"] == ["experiment.build", "experiment.dispatch",
                          "experiment.execute", "experiment.fetch"]
    assert set(Counter(c["counts"]).values()) == {2}
    assert c["metrics"]["grid_prepare_ms"] > 0
    assert c["metrics"]["grid_fetch_ms"] > 0
    assert c["metrics"]["pop_us_per_iter"] is None


@pytest.mark.parametrize("case", BROKEN)
def test_broken_frontier_is_not_correct(frontier_cases, case):
    c = frontier_cases[case]
    assert not c["correct"], c
    assert c["checks"]["lanes_failed"] > 0


@pytest.mark.parametrize("pair", sorted(ONE_GROUP_SPECS))
def test_one_group_spec_is_unchanged(run_module, pair):
    cfg, traffic = pair.split("/")
    with open(os.path.join(BENCH, "configs", cfg + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", traffic + ".json")) as f:
        traffic = json.load(f)
    spec = run_module.make_spec(config, traffic,
                                list(traffic["stimulus_seeds"]),
                                config["sim_len"])
    assert json.loads(json.dumps(spec.to_dict())) == ONE_GROUP_SPECS[pair]


def test_frontier_spec_has_one_group_per_shape(run_module):
    c = run_module.load_cell(ROOT, "frontier-4chip")
    with open(os.path.join(BENCH, "configs", "m256-k256-hier.json")) as f:
        k256 = json.load(f)
    assert all(c["config"][k] == k256[k] for k in run_module.SIM_KEYS)
    spec = run_module.make_spec(c["config"], c["traffic"],
                                c["traffic"]["stimulus_seeds"],
                                c["config"]["sim_len"])
    shapes = [x.shape for x in spec.plan().combos]
    assert [(s.k, s.queue_cap) for s in shapes] == [
        (1, 8192), (16, 8192), (32, 8192), (256, 32768)]
    assert {(s.m, s.n_childs, s.max_apps, s.queue_impl, s.batch_pop)
            for s in shapes} == {(256, 100, 64, "tree", 64)}
    assert spec.mode == "pmap" and c["chips"] == 4


def test_shapes_entry_sets_only_shape_keys(run_module):
    with pytest.raises(ValueError):
        run_module.group_shapes(dict(TINY_CONFIG,
                                     shapes=[{"k": 2, "c_b": 4.0}]))


class FakeRun:
    def __init__(self, trace, groups):
        self.trace, self.grids = trace, [{"groups": groups}]


def test_frontier_readers_on_a_two_chip_trace(run_module):
    """Chip 0 busy 200 of 1000 ns, chip 1 850: idle 47.5% over both.
    Chip 1, the busier, sets the pace: its sweep program's 800 ns over its
    group's 4 trips and 16 events, whatever chip 0's group did."""
    with open(os.path.join(FIXTURES, "trace_two_chips.json")) as f:
        ev = json.load(f)
    trace = TR.summarize({k: ev[k] for k in ("ops", "modules", "spans")})
    run = FakeRun(trace, [
        {"chip": 0, "events": 3,
         "state": {"iterations": np.array([[1, 2]], np.int32)}},
        {"chip": 1, "events": 16,
         "state": {"iterations": np.array([[3, 4]], np.int32)}}])
    read = lambda m: run_module.metric_reader(ROOT, m)(run)  # noqa: E731
    assert read("dispatch_idle_pct") == pytest.approx(47.5)
    assert read("loop_us_per_iter") == pytest.approx(0.2)
    assert read("loop_us_per_event") == pytest.approx(0.05)
    empty = FakeRun(None, [])
    for m in ("dispatch_idle_pct", "loop_us_per_iter", "loop_us_per_event"):
        assert run_module.metric_reader(ROOT, m)(empty) is None
    lost = FakeRun(trace, run.grids[0]["groups"][:1] * 2)   # none on chip 1
    for m in ("loop_us_per_iter", "loop_us_per_event"):
        assert run_module.metric_reader(ROOT, m)(lost) is None


def test_several_groups_read_host_steps_alone(run_module):
    """On the two-chip trace, each step summed over both groups; no second
    profile, so no scope metric reads."""
    import scopes as S
    with open(os.path.join(FIXTURES, "trace_two_chips.json")) as f:
        ev = json.load(f)
    trace = TR.summarize({k: ev[k] for k in ("ops", "modules", "spans")})
    state = {"iterations": np.array([[3, 4]], np.int32)}
    run = FakeRun(trace, [{"chip": 0, "events": 3, "state": state},
                          {"chip": 1, "events": 16, "state": state}])
    S.reading.cache_clear()
    got = {m: run_module.metric_reader(ROOT, m)(run)
           for m in ("grid_prepare_ms", "grid_fetch_ms", *SCOPE_METRICS)}
    S.reading.cache_clear()
    assert got == {"grid_prepare_ms": pytest.approx(60e-6),
                   "grid_fetch_ms": pytest.approx(130e-6),
                   **{m: None for m in SCOPE_METRICS}}


STANDIN = '''\
import numpy as np
ORDER_DEPENDENT = ("x",)


def simulate(cfg, arrivals, gmns, lengths, sim_len, faults=None,
             queue_cap=None):
    return {"x": np.float32(100.0), "dropped": np.int32(0),
            "events_processed": np.int32(1), "k": np.int32(cfg["k"]),
            "apps": np.int32(len(arrivals))}
'''


def test_configuration_names_its_reference(run_module, tmp_path):
    """A configuration that names a module under ``bench/`` gets it, with
    its ``ORDER_DEPENDENT``: ``x`` within the gap's limit passes; one
    without the key gets ``bench/reference.py``."""
    shutil.copytree(os.path.join(BENCH, "stimulus"),
                    tmp_path / "bench" / "stimulus")
    (tmp_path / "bench" / "standin_reference.py").write_text(STANDIN)
    cell = tiny_cell(root=str(tmp_path))
    cell["config"]["reference"] = "standin_reference"
    assert run_module.reference(cell).ORDER_DEPENDENT == ("x",)
    assert run_module.reference(tiny_cell()).ORDER_DEPENDENT == (
        "mgmt_latency",)
    (shape,) = run_module.group_shapes(cell["config"])
    want = run_module.reference_lane(cell, shape, {"dn_th": 1}, 3)
    assert (want["k"], want["apps"]) == (4, TINY_CONFIG["max_apps"])
    lane = {k: np.array([[v]]) for k, v in want.items()}
    lane["x"] = np.array([[100.0005]], np.float32)
    grid = {"seeds": [3], "knobs": [{"dn_th": 1}], "groups": [
        {"shape": {k: shape[k] for k in run_module.SHAPE_KEYS},
         "state": lane}]}
    out = run_module.check(cell, [grid])
    assert out["failed"] == 0 and out["lanes"] == 1
    assert 0 < out["numbers"]["mgmt_latency_rel_gap"] < 1e-5
