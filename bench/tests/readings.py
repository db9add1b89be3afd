"""Readings that set the check's limits, in one process on the chip.

    python bench/tests/readings.py --workload <cell> --seconds <s> \
        --seeds 101,102,... [--control-seeds 3]

For each seed: one window of the cell at its own load and its check,
printed as one JSON line of the numbers compared.  Then the control
(``control.py``) on the first ``--control-seeds`` seeds: the same window
and check with the control's lanes put in the program's place, through the
harness's own path.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import control  # noqa: E402
import run  # noqa: E402


def reading(cell, seed, seconds, devs=None) -> dict:
    win = run.window(cell, seed, seconds)
    res = run.check(cell, win["grids"])
    line = {"seed": seed, "lanes": res["lanes"], "grids": len(win["grids"]),
            "wall_s": win["wall_s"],
            "events": sum(g["events"] for g in win["grids"]),
            "numbers": res["numbers"]}
    if devs is not None:
        line["memory_peak_bytes"] = run.device_info(
            devs, cell["chips"])["memory_peak_bytes"]
    return line


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    from repro.core.experiment import ExperimentSpec
    cell = run.load_cell(run.ROOT, args.workload)
    devs = run.find_devices(cell["chips"])
    run.enable_compile_cache(cell["root"])
    seeds = [int(s) for s in args.seeds.split(",")]
    run.warm_up(cell, seeds[0])
    for seed in seeds:
        print("READING " + json.dumps(
            {"side": "program"} | reading(cell, seed, args.seconds, devs)),
            flush=True)
    real = ExperimentSpec.run
    ExperimentSpec.run = control.control_run(run, cell)
    try:
        for seed in seeds[:args.control_seeds]:
            print("READING " + json.dumps(
                {"side": "control"} | reading(cell, seed, args.seconds)),
                flush=True)
    finally:
        ExperimentSpec.run = real


if __name__ == "__main__":
    main()
