"""A tiny frontier run through the harness, sound and broken, in one
process that sees two host devices.

    XLA_FLAGS=--xla_force_host_platform_device_count=2 \\
        python bench/tests/frontier_cases.py <case> ...

Each case prints one line, ``CASE <json>``: the run's ``correct``,
``failed`` and check numbers and, for ``sound``, the chip each group of
a grid ran on.  ``control`` puts the control's lanes in the program's
place, and the other cases are ``control.fault_run``'s faults but
``steps``, which reads a grid's host steps as ``--trace 1`` does.
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

from conftest import (TINY_CONFIG, TINY_TRAFFIC,  # noqa: E402
                      steered_devices, tiny_cell)

import control  # noqa: E402
import run  # noqa: E402

FRONTIER = dict(TINY_CONFIG, shapes=[{"k": 1, "queue_cap": 256},
                                     {"k": 4, "queue_cap": 256}],
                mode="pmap")


def frontier_cell() -> dict:
    return dict(tiny_cell(TINY_TRAFFIC), chips=2, config=dict(FRONTIER))


def steps(cell: dict) -> dict:
    """The program's steps in the harness's profile of a grid of the
    frontier, and the readers that read them."""
    import tempfile
    import trace_reduce as TR
    run.warm_up(cell, 7)
    with tempfile.TemporaryDirectory() as profile_dir:
        win = run.window(cell, seed=7, seconds=0.0, profile_dir=profile_dir)
        ev = TR.extract(profile_dir, 2)
    (w0, w1), = [(s, s + d) for n, s, d in ev["spans"]
                 if n == TR.WINDOW_SPAN]
    steps = TR.step_seconds(ev["spans"], w0, w1)
    reading = run.Reading(cell, win, {"steps_s": steps}, 0.0)
    return {"steps": sorted(steps),
            "counts": [n for n, s, _ in ev["spans"]
                       if w0 <= s < w1 and n.startswith(TR.STEP_PREFIX)],
            "metrics": {m: run.metric_reader(run.ROOT, m)(reading)
                        for m in ("grid_prepare_ms", "grid_fetch_ms",
                                  "pop_us_per_iter")}}


def case(name: str) -> dict:
    from repro.core.experiment import ExperimentSpec
    cell = frontier_cell()
    if name == "steps":
        return {"case": name} | steps(cell)
    real = ExperimentSpec.run
    line = {"case": name}
    if name == "sound":
        run.warm_up(cell, 5)
        win = run.window(cell, seed=2**31 + 5, seconds=0.0)
        line["chips"] = [g["chip"] for g in win["grids"][0]["groups"]]
        line["k"] = [g["shape"]["k"] for g in win["grids"][0]["groups"]]
    elif name == "control":
        ExperimentSpec.run = control.control_run(run, cell)
    else:
        ExperimentSpec.run = control.fault_run(name)
    try:
        out = run.run_cell(cell, seed=2**31 + 11, seconds=0.1, traced=False,
                           devices=steered_devices)
    finally:
        ExperimentSpec.run = real
    return line | {"correct": out["correct"], "failed": out["failed"],
                   "attempted": out["attempted"],
                   "checks": {k: v["value"] for k, v in out["checks"].items()}}


def main(argv=None) -> None:
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    for name in (argv if argv is not None else sys.argv[1:]):
        print("CASE " + json.dumps(case(name)), flush=True)


if __name__ == "__main__":
    main()
