"""The check must fail the control and every planted fault."""
import pytest
from conftest import TINY_OUTAGE, TINY_TRAFFIC, steered_devices, tiny_cell

import control


def _run(run_module, cell, monkeypatch, patched):
    from repro.core.experiment import ExperimentSpec
    monkeypatch.setattr(ExperimentSpec, "run", patched)
    return run_module.run_cell(cell, seed=11, seconds=0.1, traced=False,
                               devices=steered_devices)


@pytest.mark.parametrize("traffic", [TINY_TRAFFIC, TINY_OUTAGE],
                         ids=["interference", "gmn_outage"])
def test_control_is_not_correct(run_module, monkeypatch, traffic):
    cell = tiny_cell(traffic)
    out = _run(run_module, cell, monkeypatch,
               control.control_run(run_module, cell))
    assert not out["correct"]
    assert out["checks"]["events_dropped"]["value"] > 0
    assert out["checks"]["lanes_failed"]["value"] > 0


@pytest.mark.parametrize("kind", ["state_unchanged", "half_lanes",
                                  "answer_altered", "plane_bf16",
                                  "plane_bf16_rounded"])
def test_planted_fault_is_not_correct(run_module, monkeypatch, kind):
    cell = tiny_cell()
    out = _run(run_module, cell, monkeypatch, control.fault_run(kind))
    assert not out["correct"]
    assert out["failed"] > 0
