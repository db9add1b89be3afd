"""Run the simulator's main path on a TPU and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the pmap frontier only

One chip: the paper's m=256 deployment (the ``paper`` tier of
benchmarks/topology_frontier.py at k=16 and k=256, hier_tree fabric, tree
queue with batch_pop=64, 4 beacon thresholds x 2 seeds = 8 lanes per group)
runs through ``ExperimentSpec.run`` twice, cold then warm, in the mode the
planner picks, which must be vmap.  One lane per group is then rerun as the
plain reference, the singleton program (batch_pop=1, one lane at a time),
and must match it bitwise on every leaf both programs hold.  The frozen CPU
goldens of tests/test_sweep.py run as well, and whether they match is
printed.

Four chips: the k in {1, 16, 32, 256} frontier runs in pmap mode, one group
per device, and must match the same spec run in vmap mode on device 0,
bitwise on every leaf.  No other phase runs.

Lines that start with "smoke:" are this check's own timings and counts, not
benchmark metrics.  The last line is one JSON object naming the device,
printed only when every phase passed.  Without a TPU the script exits
nonzero and prints no result.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import numpy as np  # noqa: E402

DN_TH = (1, 2, 4, 8)
# the paper tier's horizon (sim_len=1e6, 31 application pairs) took 924.6 s
# cold on one TPU v5e for the two groups alone, over the 1200 s the whole
# smoke may take; the horizon is cut 20x to 3 pairs, every shape is kept
SIM_LEN = 5e4
REF_DN_TH, REF_SEED = 4, 1          # the lane rerun as the reference
# the reference uses the linear queue, the golden anchor, up to this many
# slots; past it the O(Q)-per-event scan is too slow and the tree queue,
# which tests/test_sweep.py pins bitwise to linear, stands in
LINEAR_REF_MAX_Q = 8192
# frozen XLA:CPU goldens, copied from tests/test_sweep.py
GOLDENS = {
    "pre_refactor_grid": (
        [[600, 600], [351, 360], [202, 232], [72, 78]],
        "72576e858be248d11e21055618ff6a1aba89ebd7f7f4ea3419d9384b59cd3efa"),
    "fig3b_spot": (
        [[7178], [4254], [2224], [766], [297], [144]],
        "aabc517cabec6be6779f643aad59e0294c19eb29d2799a0eb8484beb88ab1cf2"),
}


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def frontier_spec(g: dict, ks, mode: str = "auto"):
    """The grid tier ``g`` of topology_frontier at cluster counts ``ks``
    on the hier_tree fabric, over DN_TH x the tier's seeds."""
    from benchmarks.topology_frontier import _shape_for
    from repro.core.experiment import ExperimentSpec, WorkloadSpec
    from repro.core.sim import SimParams
    return ExperimentSpec(
        base=SimParams(m=g["m"], n_childs=g["n_childs"],
                       max_apps=g["max_apps"], c_s=g["c_s"],
                       queue_impl=g["queue_impl"],
                       batch_pop=g["batch_pop"]),
        shapes=tuple(_shape_for(g, k) for k in ks),
        topologies=("hier_tree",), knobs={"dn_th": DN_TH},
        workloads=(WorkloadSpec.make(
            "interference", seeds=g["seeds"],
            pair_periods=tuple(g["pair_periods"])),),
        sim_len=g["sim_len"], mode=mode)


def diff_leaves(a: dict, b: dict, keys=None) -> list:
    """Names of the leaves (of ``keys``, default all of ``a``) on which
    two state dicts are not bitwise equal."""
    keys = sorted(a) if keys is None else keys
    return [k for k in keys
            if not np.array_equal(np.asarray(a[k]), np.asarray(b[k]))]


def report_groups(cold, warm) -> None:
    for gc, gw, man in zip(cold.groups, warm.groups,
                           warm.manifest()["groups"]):
        st = gw.state
        say(f"k={gc.combo.shape.k} Q={gc.combo.shape.queue_cap} "
            f"lanes={np.asarray(st['events_processed']).size} "
            f"device={man['device']} "
            f"compile_s_est={gc.wall_s - gw.wall_s:.3f} "
            f"(cold {gc.wall_s:.3f} - warm) warm_wall_s={gw.wall_s:.3f} "
            f"events={int(np.asarray(st['events_processed']).sum())} "
            f"evq_peak={int(np.asarray(st['evq_peak']).max())} "
            f"dropped={int(np.asarray(st['dropped']).sum())}")


def reference_check(spec, frame) -> None:
    """Rerun one lane of every group as the singleton program and require
    bitwise equality on every leaf both programs hold (queue-internal
    leaves differ by name between queue structures)."""
    from repro.core.experiment import ExperimentSpec, WorkloadSpec
    wl = spec.workloads[0]
    ki = DN_TH.index(REF_DN_TH)
    for g in frame.groups:
        si = [lane["seed"] for lane in g.lanes].index(REF_SEED)
        shape = g.combo.shape
        qi = "linear" if shape.queue_cap <= LINEAR_REF_MAX_Q else "tree"
        ref = ExperimentSpec(
            base=spec.base,
            shapes=(dataclasses.replace(shape, queue_impl=qi, batch_pop=1),),
            topologies=(g.combo.topology,), knobs={"dn_th": (REF_DN_TH,)},
            workloads=(WorkloadSpec.make(
                wl.kind, seeds=(REF_SEED,), **wl.param_dict),),
            sim_len=spec.sim_len, mode="seq").run()
        (rg,) = ref.groups
        want = {k: v[0, 0] for k, v in rg.state.items()}
        got = {k: v[ki, si] for k, v in g.state.items()}
        # the loop's trip count differs by design: batch_pop groups a
        # same-time BEACON_RX cohort into one trip
        common = sorted(set(want) & set(got) - {"iterations"})
        bad = diff_leaves(want, got, common)
        say(f"reference k={shape.k}: {qi} queue, batch_pop=1, seq mode, "
            f"wall_s={rg.wall_s:.3f}, {len(common)} leaves compared, "
            f"not compared (queue internals, loop trips): "
            f"{sorted(set(want) ^ set(got) | {'iterations'})}, "
            f"mismatched: {bad}")
        if bad:
            raise SystemExit(f"k={shape.k}: vmap lane differs from the "
                             f"singleton reference on {bad}")


def golden_check() -> dict:
    """Rerun the frozen goldens of tests/test_sweep.py; name -> (beacons
    match, app_done sha256 match)."""
    from repro.core import sweep as SW
    from repro.core import workloads as W
    from repro.core.sim import SimParams
    grids = {
        "pre_refactor_grid": (SimParams(m=16, k=4, n_childs=16,
                                        max_apps=32, queue_cap=512),
                              (0, 1), 3e5, (1, 2, 4, 8)),
        "fig3b_spot": (SimParams(m=64, k=16, n_childs=50, max_apps=128,
                                 queue_cap=2048), (1,), 1e6,
                       (1, 2, 4, 8, 16, 32)),
    }
    out = {}
    for name, (p, seeds, sim_len, ths) in grids.items():
        wl = W.interference_batch(p, seeds=seeds, sim_len=sim_len)
        st = SW.sweep(p.shape, SW.knob_batch(dn_th=ths), wl, sim_len)
        beacons, sha = GOLDENS[name]
        done = np.asarray(st["app_done"], np.float32)
        out[name] = (np.asarray(st["beacons_tx"]).tolist() == beacons,
                     hashlib.sha256(done.tobytes()).hexdigest() == sha)
    return out


def one_chip(g: dict, ks) -> None:
    spec = frontier_spec(g, ks)
    cold = spec.run()
    say(f"mode={cold.mode} compiles={cold.compiles} "
        f"wall_s={cold.wall_s:.3f} (cold)")
    for gr in cold.groups:
        say(f"k={gr.combo.shape.k} cold_wall_s={gr.wall_s:.3f} "
            "(compile included)")
    if cold.mode != "vmap":
        raise SystemExit(f"auto mode picked {cold.mode!r} on the chip, "
                         "not vmap")
    warm = spec.run()
    say(f"mode={warm.mode} compiles={warm.compiles} "
        f"wall_s={warm.wall_s:.3f} (warm)")
    if warm.compiles:
        raise SystemExit(f"warm rerun compiled {warm.compiles} programs")
    for gc, gw in zip(cold.groups, warm.groups):
        bad = diff_leaves(gc.state, gw.state)
        if bad:
            raise SystemExit(f"k={gc.combo.shape.k}: cold and warm runs "
                             f"differ on {bad}")
    report_groups(cold, warm)
    reference_check(spec, warm)
    for name, (beacons_ok, sha_ok) in golden_check().items():
        say(f"golden {name}: beacons_tx match={beacons_ok} "
            f"app_done sha256 match={sha_ok}")


def four_chips(g: dict, ks) -> None:
    if jax.device_count() != 4:
        raise SystemExit(f"--chips 4 needs 4 devices, JAX has "
                         f"{jax.device_count()}")
    spec = frontier_spec(g, ks)
    sharded = spec.run(mode="pmap")
    say(f"mode={sharded.mode} compiles={sharded.compiles} "
        f"wall_s={sharded.wall_s:.3f} (pmap, compile included)")
    if sharded.mode != "pmap":
        raise SystemExit(f"pmap requested, {sharded.mode!r} ran")
    devs = [m["device"] for m in sharded.manifest()["groups"]]
    if len(set(devs)) != len(ks):
        raise SystemExit(f"{len(ks)} groups ran on devices {devs}")
    one = spec.run(mode="vmap")
    say(f"mode={one.mode} compiles={one.compiles} "
        f"wall_s={one.wall_s:.3f} (vmap on one device, compile included)")
    for gp, gv, dev in zip(sharded.groups, one.groups, devs):
        bad = diff_leaves(gp.state, gv.state)
        say(f"k={gp.combo.shape.k} pmap device={dev} vmap device="
            f"{gv.device} wall_s={gv.wall_s:.3f} "
            f"events={int(np.asarray(gp.state['events_processed']).sum())} "
            f"{len(gp.state)} leaves, mismatched: {bad}")
        if bad:
            raise SystemExit(f"k={gp.combo.shape.k}: pmap differs from "
                             f"vmap on {bad}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the paper deployment, its reference and the "
                         "goldens; 4: only the pmap frontier against vmap")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    say(f"devices={devices} platform={dev.platform} "
        f"kind={dev.device_kind} count={len(devices)}")
    if dev.platform != "tpu":
        sys.exit(f"no TPU: JAX found {dev.platform!r} devices")

    from benchmarks.common import enable_compile_cache
    from benchmarks.topology_frontier import GRIDS
    enable_compile_cache()
    say("timings below are this check's own, not benchmark metrics")
    g = dict(GRIDS["paper"], sim_len=SIM_LEN)
    say(f"paper tier at sim_len={SIM_LEN:g}, "
        f"cut from {GRIDS['paper']['sim_len']:g}")
    if args.chips == 4:
        four_chips(g, g["ks"])
    else:
        one_chip(g, (16, 256))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
