"""The window loop and the check, driven on the CPU at a tiny size."""
import numpy as np
import pytest
from conftest import TINY_OUTAGE, TINY_TRAFFIC, steered_devices, tiny_cell


@pytest.mark.parametrize("traffic", [TINY_TRAFFIC, TINY_OUTAGE],
                         ids=["interference", "gmn_outage"])
def test_window_counts_events_and_grids(run_module, traffic):
    cell = tiny_cell(traffic)
    out = run_module.run_cell(cell, seed=2**31 + 7, seconds=0.5,
                              traced=False, devices=steered_devices)
    assert out["correct"], out["checks"]
    lanes_per_grid = 2 * len(traffic["stimulus_seeds"])
    assert out["attempted"] % lanes_per_grid == 0 and out["attempted"] > 0
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"sim_events_per_s", "setup_s"}
    assert list(out)[-1] == "checks"


def test_window_events_match_the_reference(run_module):
    """The events the window counts are the reference's events of the
    same lanes, grid by grid, every grid on the traffic's stimulus set."""
    cell = tiny_cell()
    (shape,) = run_module.group_shapes(cell["config"])
    win = run_module.window(cell, seed=5, seconds=0.2)
    for grid in win["grids"]:
        assert sorted(grid["seeds"]) == TINY_TRAFFIC["stimulus_seeds"]
        (group,) = grid["groups"]
        want = 0
        for knobs, seed, lane in run_module.lane_states(grid, group):
            ref = run_module.reference_lane(cell, shape, knobs, seed)
            want += ref["events_processed"]
            assert int(lane["events_processed"]) == ref["events_processed"]
        assert grid["events"] == want
    assert win["wall_s"] >= 0.2


def test_fixed_stimulus_set_is_permuted(run_module):
    traffic = {"stimulus_seeds": [3, 1, 4, 5]}
    rng = np.random.default_rng(1)
    draws = [run_module.grid_seeds(traffic, rng) for _ in range(6)]
    assert all(sorted(d) == [1, 3, 4, 5] for d in draws)
    assert len({tuple(d) for d in draws}) > 1


def test_no_accelerator_exits_nonzero(run_module):
    with pytest.raises(SystemExit):
        run_module.find_devices(1)
