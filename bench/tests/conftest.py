"""Harness tests, run explicitly: ``python -m pytest bench/tests``.

They run on the CPU at a tiny size.  The harness's look for an
accelerator is steered in each test that drives a run."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

TINY_CONFIG = {
    "name": "tiny", "m": 16, "k": 4, "n_childs": 16, "max_apps": 32,
    "c_b": 8.0, "c_s": 8.0, "c_join": 8.0, "T_b": 1000.0, "c_hop": 2.0,
    "susp_mult": 3.0, "retry_after": 0.0, "topology": "hier_tree",
    "mapping": "min_search", "beacon": "threshold", "queue_impl": "tree",
    "queue_cap": 256, "batch_pop": 8, "sim_len": 300000.0,
}
TINY_TRAFFIC = {"kind": "interference",
                "params": {"pair_period": 33000.0, "lam": 7999.0},
                "knobs": {"dn_th": [1, 4]}, "stimulus_seeds": [0, 1]}
TINY_OUTAGE = dict(TINY_TRAFFIC, faults={
    "kind": "gmn_outage", "params": {"frac": 0.5},
    "at_fraction_of_sim_len": {"t_down": 0.3, "t_heal": 0.6}})


def tiny_cell(traffic=TINY_TRAFFIC, root=ROOT):
    return {"name": "tiny", "chips": 1, "config": dict(TINY_CONFIG),
            "traffic": dict(traffic), "root": root,
            "end_to_end": [{"name": n, "unit": u} for n, u in (
                ("sim_events_per_s", "events/s"), ("setup_s", "s"))],
            "per_layer": []}


class FakeDevice:
    """Stands in for a chip: the CPU device's name, a memory reading."""
    platform, device_kind = "cpu", "cpu"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


def steered_devices(chips):
    return [FakeDevice()] * chips


@pytest.fixture(scope="session")
def run_module():
    import run
    return run
