"""Topology frontier: the paper's centralized / clustered / distributed
comparison with the management-communication overhead broken out per
interconnect fabric (paper Sec 5.4 + Table 5; DESIGN.md §10).

``baseline_compare`` reproduces the response-time ordering; this
benchmark explains *why* by routing all management messages through the
explicit transport model (``core/transport.py``) and separating

  comm  — transport latency: sum of (delivery - ready) over every
          management message (task-starts, join-exits + forwards,
          per-receiver beacon deliveries),
  proc  — manager latency: GMN queueing + service for fork expansion,
          stage-2 decision batches, and barrier decrements.

The paper's claim decomposes cleanly: the centralized k=1 manager drowns
in ``proc`` (decision serialization) *and* in ``comm`` (one local bus
carries every task-start/join of m PEs); the fully-distributed k=m
configuration pays ``comm`` for the all-to-all beacon/spawn traffic; a
clustered configuration (1 < k < m) minimizes the total on the paper's
own ``hier_tree`` fabric.  Per-receiver beacon skew (``bcn_skew_*``)
is reported per topology — zero under ``ideal`` by construction,
strictly positive under the non-ideal fabrics.

The whole (k x topology x seed) grid is TWO declarative experiments
(core/experiment.py): one spanning every k > 1 across every fabric, and
a single-fabric spec for k=1 (with one cluster no inter-GMN traffic
exists, so every fabric is identical — the other fabrics' rows are
replicas).  The planner compiles one XLA program per (shape incl.
queue_cap/queue_impl, topology) group; seq dispatch times every lane
individually, so the per-seed warm/marginal cost fields survive the
port.  The tree-vs-linear bitwise gate rides the declarative
``queue_impls`` axis of a third tiny spec.

Grid tiers (schema v4, benchmarks/README.md):

  tiny        CI smoke at m=16, every fabric, linear queue.
  paper_tiny  CI proxy for the paper grid at m=64 with the tree
              queue (``queue_impl="tree"``, core/eventq.py) and
              the fused same-timestamp batch window (batch_pop=64).
  default     the PR-3 m=64 saturation-regime grid (c_s raised
              uniformly), unchanged for trajectory continuity.
  paper       the true paper scale: m=256, k ∈ {1, 16, 32, 256} across
              ideal/hier_tree/mesh2d on the tree queue with
              batch_pop=64 (DESIGN.md §11's measured sweet spot).

Every row reports ``events`` / ``events_per_sec`` / ``wall_s`` (total
for the point, first seed carries the XLA compile), ``compile_s``
(first-lane wall minus the warm mean — the XLA compile share) and
``marginal_wall_s`` (mean of the warm per-seed runs — the steady-state
cost of one more grid point, the number PR 1 tracked).

On the tree tiers the bitwise gate spec runs every queue_impl
(linear / tree / calendar) crossed with batch_pop ∈ {1, grid bp} on
the clustered point's hier_tree fabric, asserts leaf-for-leaf equality
against the linear singleton baseline, and times each combo warm —
the head-to-head that ``BENCH_eventq.json`` (repo root) accumulates
per grid tier beside the PR-4 anchor.

Usage:  PYTHONPATH=src python -m benchmarks.topology_frontier \
            [--grid tiny|paper_tiny|default|paper]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

import numpy as np

from repro.core import workloads as W
from repro.core.experiment import ExperimentSpec, WorkloadSpec
from repro.core.sim import SimParams
from repro.core.sim import run as sim_run

from benchmarks.common import (csv_row, enable_compile_cache, save,
                               timed, topology_meta)

# PR 1 measured the sweep engine's marginal cost per design-space point
# at 2.4 s (m=256, 4e6 ticks, ideal fabric, linear queue; CHANGES.md).
# The paper grid reports its marginal_wall_s per row beside this anchor.
PR1_MARGINAL_S_PER_POINT = 2.4

# BENCH_eventq.json (repo root): the event-queue throughput trajectory.
# The PR-4 anchor is the paper point (m=256, k=256, Q=32768, hier_tree,
# interference stimulus) measured with per-handler _bulk_push and
# singleton pops — the baseline the PR-7 fused commit + batch_pop work
# is gated against (>= 10x warm ev/s at the same point).
BENCH_PATH = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_eventq.json")
PR4_ANCHOR = {
    "pr": 4, "k": 256, "topology": "hier_tree", "queue_impl": "tree",
    "batch_pop": 1, "warm_events_per_sec": 1178.0, "us_per_event": 848.8,
    "context": "m=256, Q=32768, interference pair_period=14e3, "
               "per-handler bulk pushes, singleton pops (pre PR 7)",
}


def _emit_bench(grid, rows, head_to_head):
    """Merge this run's throughput rows into BENCH_eventq.json, keyed by
    grid tier so successive tiers accumulate instead of clobbering."""
    try:
        with open(BENCH_PATH) as f:
            data = json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        data = {"schema": 1, "grids": {}}
    data["schema"] = 1
    data["baseline_pr4"] = PR4_ANCHOR
    data.setdefault("grids", {})
    keep = ("k", "topology", "queue_impl", "batch_pop", "events",
            "events_per_sec", "warm_events_per_sec", "compile_s",
            "marginal_wall_s", "copy_bytes_per_iter")
    data["grids"][grid] = {
        "rows": [{kk: r[kk] for kk in keep if kk in r} for r in rows],
        "head_to_head": head_to_head,
    }
    with open(BENCH_PATH, "w") as f:
        json.dump(data, f, indent=1, default=float)
        f.write("\n")

# In the m=64 tiers the c_s knob is raised (uniformly across every
# configuration, so the comparison stays fair) to put the centralized
# manager into the paper's saturation regime at reduced scale; the
# `paper` tier runs the true m=256 scale with the paper's own c_s=8.
GRIDS = {
    # CI smoke: all (k x topology) combos in well under two minutes
    "tiny": dict(m=16, ks=(1, 4, 16), n_childs=16, max_apps=64,
                 queue_cap={16: 2048}, default_queue_cap=1024,
                 c_s=256.0, dn_th=4, sim_len=4e5,
                 pair_periods=(33_000.0,), seeds=(0,),
                 queue_impl="linear",
                 topologies=("ideal", "shared_bus", "hier_tree", "mesh2d")),
    # CI proxy for the paper grid: small Q, m=64, tree queue
    "paper_tiny": dict(m=64, ks=(1, 8, 64), n_childs=50, max_apps=128,
                       queue_cap={64: 4096}, default_queue_cap=2048,
                       c_s=40.0, dn_th=4, sim_len=4e5,
                       pair_periods=(26_000.0,), seeds=(0, 1),
                       queue_impl="tree", batch_pop=64,
                       topologies=("ideal", "hier_tree", "mesh2d")),
    "default": dict(m=64, ks=(1, 8, 64), n_childs=50, max_apps=256,
                    queue_cap={64: 8192}, default_queue_cap=4096,
                    c_s=40.0, dn_th=4, sim_len=2e6,
                    pair_periods=(26_000.0,), seeds=(1, 2),
                    queue_impl="linear",
                    topologies=("ideal", "shared_bus", "hier_tree",
                                "mesh2d")),
    # the true paper scale (Sec 5 / Table 5): m=256 with the calibrated
    # interference stimulus; k=256 is the fully-distributed extreme whose
    # 255-wide beacon fan-out (hundreds of thousands of BEACON_RX
    # events through a 32k-slot queue) needs the tree queue
    "paper": dict(m=256, ks=(1, 16, 32, 256), n_childs=100, max_apps=64,
                  queue_cap={256: 32768}, default_queue_cap=8192,
                  c_s=8.0, dn_th=4, sim_len=1e6,
                  pair_periods=(14_000.0,), seeds=(1, 2),
                  queue_impl="tree", batch_pop=64,
                  topologies=("ideal", "hier_tree", "mesh2d")),
}


def _copy_bytes_for(g, k, queue_impl=None, batch_pop=None,
                    _memo={}):
    """Per-iteration loop-body copy bytes of the compiled program at
    (k, queue_impl, batch_pop) on the paper fabric — the static §11
    metric ``check_regression`` gates alongside throughput.  Queue-
    commit copies do not depend on the fabric or stimulus, so one AOT
    compile per combo (at the analysis driver's default sim_len)
    covers every topology row."""
    from repro.analysis.drivers import loop_copy_bytes
    qi = queue_impl if queue_impl is not None else g["queue_impl"]
    bp = batch_pop if batch_pop is not None else g.get("batch_pop", 1)
    cap = g["queue_cap"].get(k, g["default_queue_cap"])
    key = (g["m"], k, cap, qi, bp, g["n_childs"], g["max_apps"])
    if key not in _memo:
        p = SimParams(m=g["m"], k=k, n_childs=g["n_childs"],
                      max_apps=g["max_apps"], c_s=g["c_s"],
                      dn_th=g["dn_th"], queue_cap=cap, queue_impl=qi,
                      batch_pop=bp, topology="hier_tree")
        _memo[key] = loop_copy_bytes(p)
    return _memo[key]


def _shape_for(g, k):
    return SimParams(m=g["m"], k=k, n_childs=g["n_childs"],
                     max_apps=g["max_apps"], queue_impl=g["queue_impl"],
                     batch_pop=g.get("batch_pop", 1),
                     queue_cap=g["queue_cap"].get(k, g["default_queue_cap"])
                     ).shape


def run(verbose: bool = True, grid: str = "default",
        topologies=None) -> dict:
    g = GRIDS[grid]
    topologies = tuple(topologies if topologies is not None
                       else g["topologies"])
    missing = {"ideal", "hier_tree"} - set(topologies)
    if missing:
        raise ValueError(f"the headline claims need the {sorted(missing)} "
                         "fabric(s) in `topologies`")
    m, qi = g["m"], g["queue_impl"]
    clustered_ks = [k for k in g["ks"] if 1 < k < m]
    n_lanes = len(g["pair_periods"]) * len(g["seeds"])
    workload = WorkloadSpec.make("interference", seeds=g["seeds"],
                                 pair_periods=tuple(g["pair_periods"]))
    knobs = {"dn_th": g["dn_th"], "c_s": g["c_s"]}

    # with a single cluster no inter-GMN traffic exists, so every fabric
    # produces identical results: run k=1 on the first fabric only and
    # replicate its row across the rest
    specs = []
    if 1 in g["ks"]:
        specs.append(ExperimentSpec(shapes=(_shape_for(g, 1),),
                                    topologies=topologies[:1],
                                    knobs=knobs, workloads=(workload,),
                                    sim_len=g["sim_len"], mode="seq"))
    ks_multi = tuple(k for k in g["ks"] if k > 1)
    if ks_multi:
        specs.append(ExperimentSpec(
            shapes=tuple(_shape_for(g, k) for k in ks_multi),
            topologies=topologies, knobs=knobs, workloads=(workload,),
            sim_len=g["sim_len"], mode="seq"))

    frames, t_total = [], 0.0
    for spec in specs:
        frame, dt = timed(spec.run)
        frames.append(frame)
        t_total += dt
    # single-lane grids: the lone lane of each group carried the XLA
    # compile, so re-run the whole (now warm) spec once to measure the
    # steady-state marginal cost.  Results are deterministic and
    # discarded, and the re-run stays OFF t_total — the historical
    # series times only the actually-reported points
    warm_lane = {}
    if n_lanes == 1:
        for spec in specs:
            wf = spec.run()
            for gr in wf.groups:
                key = (gr.combo.shape.k, gr.combo.topology.kind)
                warm_lane[key] = list(gr.lane_wall_s)

    rows = []
    events_run = 0                # events from actually-run points only
                                  # (k=1 replicas excluded)
    for frame in frames:
        for gr in frame.groups:
            k, topo = gr.combo.shape.k, gr.combo.topology.kind
            st = gr.state
            events = int(np.asarray(st["events_processed"]).sum())
            events_run += events
            comm = np.asarray(st["mgmt_latency"], np.float64)[0]   # (S,)
            proc = np.asarray(st["mgmt_proc"], np.float64)[0]
            msgs = np.asarray(st["mgmt_msgs"], np.int64)[0]
            wall = float(gr.wall_s)
            lane_walls = list(gr.lane_wall_s)
            warm = warm_lane.get((k, topo), lane_walls[1:])
            marginal = float(np.mean(warm))
            rows.append({
                "k": k, "topology": topo, "queue_impl": qi,
                "batch_pop": g.get("batch_pop", 1),
                "mean_response": float(np.nanmean(
                    frame.mean_response(k=k, topology=topo))),
                "beacons_tx": int(np.asarray(st["beacons_tx"]).sum()),
                "beacons_rx": int(np.asarray(st["beacons_rx"]).sum()),
                "mgmt_msgs": int(msgs.sum()),
                "comm_latency": float(comm.sum()),
                "proc_latency": float(proc.sum()),
                "total_mgmt_latency": float((comm + proc).sum()),
                "comm_per_msg": float(comm.sum() / max(msgs.sum(), 1)),
                "bcn_skew_max": float(
                    np.asarray(st["bcn_skew_max"], np.float64).max()),
                "dropped": int(np.asarray(st["dropped"]).sum()),
                "events": events,
                "events_per_sec": events / max(wall, 1e-9),
                "warm_events_per_sec": events / n_lanes
                / max(marginal, 1e-9),
                "wall_s": wall,
                "marginal_wall_s": marginal,
                # first lane carries the XLA compile; its wall minus the
                # warm mean isolates the compile share per point
                "compile_s": max(float(lane_walls[0]) - marginal, 0.0),
            })
    # replicate the fabric-invariant k=1 row across the unrun fabrics,
    # keeping the historical row order (all k=1 rows first)
    if 1 in g["ks"]:
        k1 = next(r for r in rows if r["k"] == 1)
        at = rows.index(k1) + 1
        rows[at:at] = [dict(k1, topology=topo) for topo in topologies[1:]]
    # static §11 metric: per-iteration loop-body copy bytes, one AOT
    # compile per k (repro.analysis.drivers); gated must-not-grow by
    # benchmarks/check_regression.py beside the throughput floor
    for r in rows:
        r["copy_bytes_per_iter"] = _copy_bytes_for(g, r["k"])

    def row(k, topo):
        return next(r for r in rows if r["k"] == k and r["topology"] == topo)

    # headline: on the paper's own fabric, a clustered configuration
    # carries lower total management latency than both extremes
    hier = {k: row(k, "hier_tree") for k in g["ks"]}
    clustered = min(clustered_ks,
                    key=lambda k: hier[k]["total_mgmt_latency"])
    extremes = [k for k in g["ks"] if k == 1 or k == m]
    clustered_wins = all(
        hier[clustered]["total_mgmt_latency"] < hier[k]["total_mgmt_latency"]
        for k in extremes)
    # per-receiver beacon ages are verifiably heterogeneous off-ideal
    skew_hetero = {topo: row(clustered, topo)["bcn_skew_max"] > 0.0
                   for topo in topologies if topo != "ideal"}
    ideal_skew_zero = row(clustered, "ideal")["bcn_skew_max"] == 0.0

    # bitwise anchor: the ideal row's first lane reproduces a direct
    # (topology- and queue-default) sim.run — neither the transport
    # subsystem nor the tree queue is visible until opted into
    pd = SimParams(m=m, k=clustered, n_childs=g["n_childs"],
                   max_apps=g["max_apps"], c_s=g["c_s"], dn_th=g["dn_th"],
                   queue_cap=g["queue_cap"].get(clustered,
                                                g["default_queue_cap"]))
    pp0, seed0 = g["pair_periods"][0], g["seeds"][0]
    wl0 = W.interference(pd, sim_len=g["sim_len"], pair_period=pp0,
                         seed=seed0)
    st0 = sim_run(pd, *wl0, g["sim_len"])
    mframe = frames[-1]
    stI = mframe.state(k=clustered, topology="ideal")
    ideal_bitwise = bool(
        np.array_equal(np.asarray(stI["app_done"])[0, 0],
                       np.asarray(st0["app_done"]))
        and int(np.asarray(stI["beacons_tx"])[0, 0])
        == int(st0["beacons_tx"]))

    n_compiles = sum(f.compiles for f in frames)
    expected = sum(f.expected_programs for f in frames)
    payload = {
        "grid": grid,
        "rows": rows,
        "clustered_k": clustered,
        "queue_impl": qi,
        "meta": topology_meta(topologies=list(topologies), grid=grid, m=m,
                              ks=list(g["ks"]), queue_impl=qi),
        "paper_claim": "clustered management reduces both the computation "
                       "(vs k=1) and communication (vs k=m) overhead of "
                       "run-time management (Sec 5.4, Table 5)",
        "pr1_reference": {
            "marginal_s_per_point": PR1_MARGINAL_S_PER_POINT,
            "context": "m=256, 4e6 ticks, ideal fabric, linear queue "
                       "(CHANGES.md, PR 1)"},
        "n_compiles": n_compiles,
        "claim_one_program_per_group": n_compiles <= expected,
        "claim_ideal_bitwise_vs_run": ideal_bitwise,
        "claim_clustered_lowest_total_mgmt_latency": bool(clustered_wins),
        "claim_skew_heterogeneous_nonideal": bool(all(skew_hetero.values())),
        "claim_skew_zero_ideal": bool(ideal_skew_zero),
        "claim_no_drops": all(r["dropped"] == 0 for r in rows),
        "skew_by_topology": skew_hetero,
    }

    head_to_head = []
    if qi == "tree":
        # the event-queue bitwise contract, exercised where it matters —
        # a non-ideal fabric whose k-1 beacon fan-out stresses the bulk
        # push — through the declarative queue_impls x batch_pops axes:
        # one spec, every queue structure crossed with the singleton and
        # the fused same-timestamp batch window, leaf-for-leaf equality
        # against the linear singleton baseline
        bp = g.get("batch_pop", 1)
        bps = (1, bp) if bp > 1 else (1,)
        qspec = ExperimentSpec(
            shapes=(dataclasses.replace(_shape_for(g, clustered),
                                        queue_impl="linear", batch_pop=1),),
            queue_impls=("linear", "tree", "calendar"), batch_pops=bps,
            topologies=("hier_tree",), knobs=knobs,
            workloads=(WorkloadSpec.make("interference", seeds=(seed0,),
                                         pair_periods=(pp0,)),),
            sim_len=g["sim_len"], mode="seq")
        qframe = qspec.run()
        warm_frame = qspec.run()             # all programs cached: warm

        def bitwise(a, b):
            return bool(all(
                np.array_equal(np.asarray(a[key]), np.asarray(b[key]))
                for key in ("app_done", "app_arrive", "beacons_tx",
                            "beacons_rx", "events_processed", "dropped")))

        stL = qframe.state(queue_impl="linear", batch_pop=1)
        payload["claim_tree_matches_linear_bitwise"] = bitwise(
            stL, qframe.state(queue_impl="tree", batch_pop=1))
        payload["claim_calendar_matches_linear_bitwise"] = bitwise(
            stL, qframe.state(queue_impl="calendar", batch_pop=1))
        # the fused batch window must be a pure wall-clock knob: every
        # (impl, batch_pop > 1) point bitwise equals the linear singleton
        payload["claim_batched_matches_singleton_bitwise"] = bool(all(
            bitwise(stL, qframe.state(queue_impl=q2, batch_pop=b2))
            for q2 in ("linear", "tree", "calendar") for b2 in bps))

        warm_by = {(wg.combo.shape.queue_impl, wg.combo.shape.batch_pop):
                   float(wg.lane_wall_s[0]) for wg in warm_frame.groups}
        for gr2 in qframe.groups:
            q2 = gr2.combo.shape.queue_impl
            b2 = gr2.combo.shape.batch_pop
            ev2 = int(np.asarray(gr2.state["events_processed"]).sum())
            cold = float(gr2.lane_wall_s[0])
            wwall = warm_by[(q2, b2)]
            head_to_head.append({
                "k": clustered, "topology": "hier_tree",
                "queue_impl": q2, "batch_pop": b2, "events": ev2,
                "cold_wall_s": cold, "warm_wall_s": wwall,
                "events_per_sec": ev2 / max(cold, 1e-9),
                "warm_events_per_sec": ev2 / max(wwall, 1e-9),
                "compile_s": max(cold - wwall, 0.0),
                "copy_bytes_per_iter": _copy_bytes_for(
                    g, clustered, q2, b2),
            })
        payload["queue_head_to_head"] = head_to_head

    save("topology_frontier", payload,
         spec=[s.to_dict() for s in specs])
    _emit_bench(grid, rows, head_to_head)
    if verbose:
        csv_row("topology_frontier", t_total * 1e6,
                f"clustered_best={clustered_wins}"
                f"|ideal_bitwise={ideal_bitwise}"
                f"|skew_ok={payload['claim_skew_heterogeneous_nonideal']}"
                f"|queue={qi}"
                f"|events_per_sec={events_run / max(t_total, 1e-9):,.0f}")
        for r in rows:
            print(f"  k={r['k']:4d} {r['topology']:>10}: "
                  f"comm={r['comm_latency']:.3g} proc={r['proc_latency']:.3g} "
                  f"total={r['total_mgmt_latency']:.3g} "
                  f"skew_max={r['bcn_skew_max']:g} "
                  f"resp={r['mean_response']:.0f} "
                  f"ev/s={r['events_per_sec']:,.0f} "
                  f"marg={r['marginal_wall_s']:.2f}s")
        for r in head_to_head:
            print(f"  h2h k={r['k']:4d} {r['queue_impl']:>8} "
                  f"bp={r['batch_pop']:3d}: "
                  f"warm_ev/s={r['warm_events_per_sec']:,.0f} "
                  f"cold_ev/s={r['events_per_sec']:,.0f} "
                  f"compile={r['compile_s']:.1f}s")
    return payload


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--grid", choices=sorted(GRIDS), default="default")
    args = ap.parse_args()
    run(grid=args.grid)
