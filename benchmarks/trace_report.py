"""Trace report: the paper point rendered as per-fabric timelines with
conservation claim gates (DESIGN.md §14).

Runs the ``hier_tree`` column of the topology-frontier grid — at the
``paper`` tier that is the k ∈ {1, 16, 32, 256} / m=256 paper point —
with the in-loop trace enabled (``ExperimentSpec.trace``), then for
every k:

  * decodes the lane's buffers through :class:`repro.core.trace
    .TraceFrame` and gates every conservation law (histogram mass ==
    ``mgmt_msgs``/completed apps, ring counts == ``events_processed``,
    ``trace_dropped`` accounting, monotone timelines);
  * reports p50/p95/p99 management latency and per-app response beside
    the means the frontier already tracks, plus ``evq_peak`` headroom;
  * re-runs the clustered shape with ``trace=None`` and asserts the
    instrumented run left every shared state leaf bitwise untouched
    (the zero-overhead-when-off contract, and — via warm walls — the
    measured overhead when on);
  * exports the clustered k's Perfetto JSON to
    ``results/trace_<grid>_perfetto.json`` (drop it on ui.perfetto.dev)
    and schema-validates it.

Usage:  PYTHONPATH=src python -m benchmarks.trace_report \
            [--grid tiny|paper_tiny|default|paper]
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

from repro.core.experiment import ExperimentSpec, WorkloadSpec
from repro.core.trace import TraceSpec, validate_perfetto

from benchmarks.common import RESULTS_DIR, csv_row, \
    enable_compile_cache, save, timed, topology_meta
from benchmarks.topology_frontier import GRIDS, _shape_for

# ring sized for the CI tiers (tiny ~1k events, paper_tiny ~40k): the
# paper tier intentionally overflows it — trace_dropped accounting is
# part of what the claims gate
TRACE = TraceSpec(ring_cap=16384, sample_every=64, n_samples=512,
                  hist_bins=64, bins_per_octave=4)


def run(verbose: bool = True, grid: str = "paper_tiny") -> dict:
    g = GRIDS[grid]
    m = g["m"]
    seed0, pp0 = g["seeds"][0], g["pair_periods"][0]
    workload = WorkloadSpec.make("interference", seeds=(seed0,),
                                 pair_periods=(pp0,))
    knobs = {"dn_th": g["dn_th"], "c_s": g["c_s"]}
    clustered = next((k for k in g["ks"] if 1 < k < m), g["ks"][-1])

    spec_on = ExperimentSpec(
        shapes=tuple(_shape_for(g, k) for k in g["ks"]),
        topologies=("hier_tree",), knobs=knobs, workloads=(workload,),
        trace=TRACE, sim_len=g["sim_len"], mode="seq")
    frame, t_on = timed(spec_on.run)

    # zero-overhead-when-off: the same clustered shape without a
    # TraceSpec must produce bitwise-identical shared leaves (run twice
    # so the second, warm wall measures the uninstrumented cost)
    spec_off = ExperimentSpec(
        shapes=(_shape_for(g, clustered),), topologies=("hier_tree",),
        knobs=knobs, workloads=(workload,), sim_len=g["sim_len"],
        mode="seq")
    frame_off = spec_off.run()
    warm_off = float(np.mean(spec_off.run().groups[0].lane_wall_s))
    warm_on = float(np.mean(
        ExperimentSpec(shapes=(_shape_for(g, clustered),),
                       topologies=("hier_tree",), knobs=knobs,
                       workloads=(workload,), trace=TRACE,
                       sim_len=g["sim_len"],
                       mode="seq").run().groups[0].lane_wall_s))
    st_on = frame.state(k=clustered, topology="hier_tree")
    st_off = frame_off.state(k=clustered, topology="hier_tree")
    off_bitwise = all(
        np.array_equal(np.asarray(st_off[key]), np.asarray(st_on[key]))
        for key in st_off if key != "evq_len")
    # evq_len drains to zero either way; compare it too, explicitly
    off_bitwise &= np.array_equal(np.asarray(st_off.get("evq_len", 0)),
                                  np.asarray(st_on.get("evq_len", 0)))

    rows, checks = [], {}
    for k in g["ks"]:
        tf = frame.trace_frame(k=k, topology="hier_tree")
        chk = tf.check()
        checks[k] = chk
        pm = tf.percentiles("mgmt")
        pr = tf.percentiles("resp")
        tl = tf.timeline()
        stk = frame.state(k=k, topology="hier_tree")
        rows.append({
            "k": k, "topology": "hier_tree",
            "events": tf.n_events,
            "trace_recorded": tf.n_recorded,
            "trace_dropped": tf.trace_dropped,
            "evq_peak": int(np.asarray(stk["evq_peak"]).max()),
            "timeline_samples": len(tl["t"]),
            "p50_mgmt_latency": pm["p50"],
            "p95_mgmt_latency": pm["p95"],
            "p99_mgmt_latency": pm["p99"],
            "p50_response": pr["p50"],
            "p95_response": pr["p95"],
            "p99_response": pr["p99"],
            "conservation_ok": chk["ok"],
        })

    # Perfetto export for the clustered paper point
    tf_c = frame.trace_frame(k=clustered, topology="hier_tree")
    perfetto = tf_c.to_perfetto()
    errs = validate_perfetto(perfetto)
    os.makedirs(RESULTS_DIR, exist_ok=True)
    perfetto_path = os.path.join(RESULTS_DIR,
                                 f"trace_{grid}_perfetto.json")
    with open(perfetto_path, "w") as f:
        json.dump(perfetto, f)

    def allk(key):
        return bool(all(checks[k][key] for k in g["ks"]))

    pct_ordered = all(
        r["p50_mgmt_latency"] <= r["p95_mgmt_latency"]
        <= r["p99_mgmt_latency"]
        for r in rows if not np.isnan(r["p50_mgmt_latency"]))
    payload = {
        "grid": grid,
        "clustered_k": clustered,
        "rows": rows,
        "meta": topology_meta(topologies=["hier_tree"], grid=grid, m=m,
                              ks=list(g["ks"]),
                              trace=TRACE.to_dict()),
        "overhead": {
            "warm_wall_s_trace_off": warm_off,
            "warm_wall_s_trace_on": warm_on,
            "on_over_off": warm_on / max(warm_off, 1e-9),
        },
        "perfetto_path": os.path.relpath(perfetto_path,
                                         os.path.dirname(RESULTS_DIR)),
        "perfetto_events": len(perfetto["traceEvents"]),
        "claim_hist_mass_equals_mgmt_msgs": allk("hist_mass_mgmt"),
        "claim_response_mass_equals_completed":
            allk("hist_mass_response"),
        "claim_ring_conservation": allk("ring_counts"),
        "claim_timeline_monotone": allk("timeline_monotone"),
        "claim_evq_peak_bound": allk("evq_peak_bound"),
        "claim_percentiles_ordered": bool(pct_ordered),
        "claim_trace_invisible_bitwise": bool(off_bitwise),
        "claim_perfetto_valid": not errs,
        "perfetto_errors": errs,
        "n_compiles": frame.compiles,
        "expected_programs": frame.expected_programs,
        "claim_one_program_per_group":
            frame.compiles <= frame.expected_programs,
    }
    payload["claims_all_pass"] = bool(all(
        v for kk, v in payload.items() if kk.startswith("claim_")))
    save("trace_report", payload, spec=spec_on)
    if verbose:
        csv_row("trace_report", t_on * 1e6,
                f"claims={'PASS' if payload['claims_all_pass'] else 'FAIL'}"
                f"|overhead={payload['overhead']['on_over_off']:.2f}x"
                f"|perfetto_events={payload['perfetto_events']}")
        for r in rows:
            print(f"  k={r['k']:4d}: events={r['events']:7d} "
                  f"dropped={r['trace_dropped']:6d} "
                  f"evq_peak={r['evq_peak']:6d} "
                  f"p50/p95/p99_mgmt={r['p50_mgmt_latency']:.1f}"
                  f"/{r['p95_mgmt_latency']:.1f}"
                  f"/{r['p99_mgmt_latency']:.1f} "
                  f"p95_resp={r['p95_response']:.0f} "
                  f"ok={r['conservation_ok']}")
    return payload


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--grid", choices=sorted(GRIDS), default="paper_tiny")
    args = ap.parse_args()
    payload = run(grid=args.grid)
    if not payload["claims_all_pass"]:
        raise SystemExit(1)
