"""Fault frontier: which (k, policy, topology) points of the clustered
task manager degrade gracefully when the management fabric fails
(core/faults.py, DESIGN.md §13).

The paper evaluates the manager on a static fabric; this benchmark
stresses the same design space under fault injection — seeded Poisson
link failures, a scheduled partition-and-heal, and GMN churn with
hot-spare takeover — and reports, per (k, policy, topology, fault)
point, the availability counters (``msgs_lost`` / ``reroutes`` /
``downtime``) beside the usual management-overhead metrics.  The whole
grid is ONE declarative experiment riding the ``faults`` axis of
``ExperimentSpec``; fault schedules are traced, so the entire fault
axis adds exactly one XLA program per static group and a *second* spec
with fresh fault seeds compiles nothing at all (the no-recompile claim
below).

Every payload gates these claims:

  claim_nofault_bitwise_anchor   the PR-2 frozen golden grid reproduces
                                 bitwise (same beacons_tx, same app_done
                                 sha256) when run WITH the fault
                                 machinery compiled in and zero events —
                                 the fault subsystem is invisible until
                                 a fault actually fires.
  claim_msgs_lost_under_faults   lossy scenarios actually lose beacons
                                 (msgs_lost > 0 on every partition row).
  claim_conservation             beacons_rx + msgs_lost ==
                                 (k-1) * beacons_tx on every row — no
                                 message is double-counted or leaks.
  claim_all_apps_complete        the control plane is reliable: every
                                 arrived application completes under
                                 every fault scenario (work re-homes and
                                 detours, it is never lost).
  claim_one_program_per_group    compiles == expected_programs for the
                                 grid (fault axis adds one program per
                                 group, not one per scenario).
  claim_fault_grid_no_recompile  a second spec with different fault
                                 seeds compiles zero new programs.
  claim_graceful_degradation     mean response under every fault
                                 scenario stays within GRACEFUL_FACTOR
                                 of the same point's no-fault response.
  claim_downtime_accounted       partition rows carry exactly the
                                 scheduled outage in ``downtime``.

The **detector tier** (DESIGN.md §15) re-runs the grid's churn and
partition scenarios with the failure detector driving the mapping —
``avoid_suspected`` / ``suspect_weighted`` vs the stale-view
``min_search`` baseline under periodic beacons — and gates the
availability-aware claims:

  claim_detector_resp_partition  avoid_suspected beats min_search on
                                 mean response at every (k, topology)
                                 under partition-and-heal.
  claim_detector_resp_churn      same dominance under GMN churn.
  claim_detector_availability    downtime-weighted availability (the
                                 fraction of outage-window arrivals
                                 that respond within
                                 DETECTOR_AVAIL_FACTOR of the no-fault
                                 anchor) is never worse, and strictly
                                 better somewhere, with the detector on.
  claim_detector_conservation    the retry-generalized identity
                                 beacons_rx + msgs_lost ==
                                 (k-1) * beacons_tx + retries_tx holds
                                 on every detector-tier row.
  claim_detector_retries         the bounded re-beacon path actually
                                 fires (retries_tx > 0 with
                                 retry_after > 0 under faults).
  claim_detector_active          every faulty row records suspicion
                                 onsets (the detector is live).
  claim_detector_off_bitwise     sweeping susp_mult / retry_after under
                                 a zero-event schedule reproduces the
                                 PR-2 frozen goldens bitwise — the
                                 detector is invisible until a fault
                                 fires.
  claim_detector_no_recompile    a susp_mult / retry_after knob grid on
                                 the already-compiled detector programs
                                 adds zero XLA compilations
                                 (``sweep.cache_size()`` delta).

plus ``determinism_digest`` — a sha256 over the deterministic row
fields (wall-clock excluded) that the CI fault-smoke job computes twice
with the same seeds and diffs (schema v8, benchmarks/README.md).

Usage:  PYTHONPATH=src python -m benchmarks.fault_frontier \
            [--grid tiny|default]
"""
from __future__ import annotations

import argparse
import hashlib

import numpy as np

from repro.core import sweep as SW
from repro.core import workloads as W
from repro.core.experiment import ExperimentSpec, WorkloadSpec
from repro.core.faults import FaultSpec, gmn_outages
from repro.core.sim import SimParams

from benchmarks.common import (csv_row, determinism_digest,
                               enable_compile_cache, save, timed,
                               topology_meta)

# The PR-2 frozen goldens (tests/test_sweep.py): the (dn_th x seed) grid
# at m=16/k=4 captured at commit 0872ddc.  The fault-aware program with
# an empty schedule must keep reproducing them bitwise.
_GOLDEN_BEACONS = [[600, 600], [351, 360], [202, 232], [72, 78]]
_GOLDEN_APP_DONE_SHA = \
    "72576e858be248d11e21055618ff6a1aba89ebd7f7f4ea3419d9384b59cd3efa"

# Mean response under faults may exceed the no-fault response by at most
# this factor for the point to count as degrading gracefully.  The
# reliable control plane (detours + takeover, never loss) keeps the
# measured worst case well under 2x on both tiers; see results JSON.
GRACEFUL_FACTOR = 2.0

# Downtime-weighted availability: an outage-window arrival counts as
# "available" when its response stays within this factor of the same
# point's no-fault min_search mean response.
DETECTOR_AVAIL_FACTOR = 2.0

GRIDS = {
    # CI smoke: the full claim set in about a minute
    "tiny": dict(m=16, ks=(2, 4), n_childs=16, max_apps=32, queue_cap=512,
                 policies=(("min_search", "threshold"),
                           ("round_robin", "periodic")),
                 topologies=("hier_tree", "mesh2d"),
                 dn_th=2, sim_len=2e5, seeds=(0,),
                 poisson_rate=4e-4, poisson_repair=2e4,
                 poisson_seeds=(0,), churn_rate=4e-5, churn_repair=3e4,
                 detector=dict(ks=(2, 4), topologies=("hier_tree",),
                               T_b=2000.0, susp_mult=8.0, retry_after=250.0,
                               t_down_frac=0.3, t_heal_frac=0.8,
                               churn_rate=3e-5, churn_repair=4e4,
                               seeds=(0, 1), knobs=dict(c_b=80.0))),
    "default": dict(m=16, ks=(2, 4, 8, 16), n_childs=16, max_apps=64,
                    queue_cap=2048,
                    policies=(("min_search", "threshold"),
                              ("round_robin", "periodic")),
                    topologies=("hier_tree", "mesh2d"),
                    dn_th=2, sim_len=4e5, seeds=(0, 1),
                    poisson_rate=4e-4, poisson_repair=3e4,
                    poisson_seeds=(0, 1), churn_rate=2e-5,
                    churn_repair=5e4,
                    detector=dict(ks=(4, 8), topologies=("hier_tree",),
                                  T_b=2000.0, susp_mult=8.0,
                                  retry_after=250.0,
                                  t_down_frac=0.3, t_heal_frac=0.8,
                                  churn_rate=1.5e-5, churn_repair=8e4,
                                  seeds=(0, 1), knobs=dict(c_b=80.0))),
}


def _fault_axis(g, seed_offset=0):
    """The fault-scenario axis: the zero-event anchor, a seed grid of
    Poisson link failures, one partition-and-heal, and GMN churn.

    ``seed_offset`` shifts every stochastic generator's seed while
    keeping the axis structure — and therefore every padded schedule
    capacity — identical, which is what the no-recompile claim reuses."""
    t_down, t_heal = 0.3 * g["sim_len"], 0.6 * g["sim_len"]
    axis = [FaultSpec.none()]
    axis += [FaultSpec.poisson_links(rate=g["poisson_rate"],
                                     repair=g["poisson_repair"],
                                     seed=s + seed_offset,
                                     name=f"poisson_s{s + seed_offset}")
             for s in g["poisson_seeds"]]
    axis.append(FaultSpec.partition(t_down=t_down, t_heal=t_heal,
                                    name="partition"))
    axis.append(FaultSpec.gmn_churn(rate=g["churn_rate"],
                                    repair=g["churn_repair"],
                                    seed=seed_offset))
    return tuple(axis), (t_down, t_heal)


def _golden_anchor() -> bool:
    """The PR-2 golden grid through the fault-aware program (empty
    schedule): bitwise equality is the subsystem's no-fault contract."""
    p = SimParams(m=16, k=4, n_childs=16, max_apps=32, queue_cap=512)
    wl = W.interference_batch(p, seeds=(0, 1), sim_len=3e5)
    st = SW.sweep(p.shape, SW.knob_batch(dn_th=(1, 2, 4, 8)), wl, 3e5,
                  faults=FaultSpec.none())
    done = np.asarray(st["app_done"], np.float32)
    return (np.asarray(st["beacons_tx"]).tolist() == _GOLDEN_BEACONS
            and hashlib.sha256(done.tobytes()).hexdigest()
            == _GOLDEN_APP_DONE_SHA
            and int(np.asarray(st["msgs_lost"]).sum()) == 0)


def _golden_detector_off() -> bool:
    """Detector-off bitwise contract (DESIGN.md §15): a susp_mult /
    retry_after knob grid under a zero-event schedule must reproduce the
    PR-2 frozen goldens bitwise — on an all-up fabric no beacon is ever
    lost, so no retry fires, and min_search ignores the suspicion
    horizon entirely.  Detector telemetry may tick; behavior may not."""
    p = SimParams(m=16, k=4, n_childs=16, max_apps=32, queue_cap=512)
    wl = W.interference_batch(p, seeds=(0, 1), sim_len=3e5)
    st = SW.sweep(p.shape,
                  SW.knob_batch(dn_th=(1, 2, 4, 8),
                                susp_mult=(0.5, 1.5, 3.0, 6.0),
                                retry_after=(0.0, 60.0, 120.0, 240.0)),
                  wl, 3e5, faults=FaultSpec.none())
    done = np.asarray(st["app_done"], np.float32)
    return (np.asarray(st["beacons_tx"]).tolist() == _GOLDEN_BEACONS
            and hashlib.sha256(done.tobytes()).hexdigest()
            == _GOLDEN_APP_DONE_SHA
            and int(np.asarray(st["msgs_lost"]).sum()) == 0
            and int(np.asarray(st["retries_tx"]).sum()) == 0)


def _dw_availability(st, windows, threshold):
    """Downtime-weighted availability of one group state: the fraction
    of apps arriving inside an outage window whose response stays within
    ``threshold``; also the mean response over those apps.  (1.0, nan)
    when no arrival lands in a window."""
    arr = np.asarray(st["app_arrive"]).ravel()
    done = np.asarray(st["app_done"]).ravel()
    ok = (arr < 1e17) & (done < 1e17)
    inw = np.zeros(arr.shape, bool)
    for lo, hi in windows:
        inw |= (arr >= lo) & (arr < hi)
    m = ok & inw
    if not m.any():
        return 1.0, float("nan"), 0
    resp = done[m] - arr[m]
    return (float(np.mean(resp <= threshold)), float(resp.mean()),
            int(m.sum()))


def _detector_tier(g) -> dict:
    """Run the availability-aware mapping tier and gate its claims."""
    d = g["detector"]
    t_down = d["t_down_frac"] * g["sim_len"]
    t_heal = d["t_heal_frac"] * g["sim_len"]
    churn = FaultSpec.gmn_churn(rate=d["churn_rate"],
                                repair=d["churn_repair"], seed=0)
    # the detector tier's partition-and-heal is a power-domain outage —
    # a block of managers failing and healing together
    # (FaultSpec.gmn_outage), the scenario a failure detector exists
    # for.  The main tier's link partition is deliberately NOT reused
    # here: reliable messages detour through downed links for ~2*c_b
    # (DESIGN.md §13), so the partitioned half stays usable capacity and
    # liveness-avoidance would be the wrong move.
    faults = (FaultSpec.none(),
              FaultSpec.gmn_outage(t_down=t_down, t_heal=t_heal,
                                   name="partition"),
              churn)
    policies = tuple((m_, "periodic") for m_ in
                     ("min_search", "avoid_suspected", "suspect_weighted"))
    base = SimParams(m=g["m"], n_childs=g["n_childs"],
                     max_apps=g["max_apps"], queue_cap=g["queue_cap"])
    # extra traced-knob overrides (e.g. a raised c_b making management
    # messages to dead/partitioned managers a first-order cost — the
    # paper's communication-overhead argument) ride the same knob dict
    knobs = {"dn_th": g["dn_th"], "T_b": d["T_b"],
             "susp_mult": d["susp_mult"], "retry_after": d["retry_after"],
             **d.get("knobs", {})}
    seeds = d.get("seeds", g["seeds"])
    spec = ExperimentSpec(
        base=base, shapes=d["ks"], policies=policies,
        topologies=d["topologies"], knobs=knobs, workloads=(
            WorkloadSpec.make("interference", seeds=seeds),),
        faults=faults, sim_len=g["sim_len"], mode="seq")
    frame = spec.run()

    # post-heal views stay stale until the next periodic beacon lands,
    # so the damage window extends one beacon period past each heal
    stale = d["T_b"]
    windows = {}
    for k in d["ks"]:
        windows[("partition", k)] = [(t_down, t_heal + stale)]
        sched = churn.build(k, g["sim_len"])
        windows[("gmn_churn", k)] = [
            (lo, min(hi, g["sim_len"]) + stale)
            for per_gmn in gmn_outages(sched, k) for lo, hi in per_gmn]

    rows = []
    for gr in frame.groups:
        k, topo = gr.combo.shape.k, gr.combo.topology.kind
        pol = gr.combo.policy.mapping
        fl = gr.fault_label
        sel = dict(k=k, topology=topo, mapping=pol, fault=fl)
        st = gr.state
        row = {
            "k": k, "topology": topo, "mapping": pol, "fault": fl,
            "mean_response": float(np.nanmean(frame.mean_response(**sel))),
            "beacons_tx": int(np.asarray(st["beacons_tx"]).sum()),
            "beacons_rx": int(np.asarray(st["beacons_rx"]).sum()),
            "msgs_lost": int(frame.msgs_lost(**sel).sum()),
            "retries_tx": int(frame.retries_tx(**sel).sum()),
            "susp_onsets": int(frame.susp_onsets(**sel).sum()),
            "susp_clears": int(frame.susp_clears(**sel).sum()),
            "susp_false_pos": int(frame.susp_false_pos(**sel).sum()),
            "downtime": float(frame.downtime(**sel).sum()),
        }
        rows.append(row)

    def row_of(k, topo, pol, fl):
        return next(r for r in rows if (r["k"], r["topology"], r["mapping"],
                                        r["fault"]) == (k, topo, pol, fl))

    # downtime-weighted availability against the common no-fault anchor
    for r in rows:
        if r["fault"] == "none":
            r["dw_availability"], r["dw_response"], r["dw_apps"] = \
                1.0, None, 0
            continue
        ref = row_of(r["k"], r["topology"], "min_search",
                     "none")["mean_response"]
        avail, dw_resp, n = _dw_availability(
            frame.state(k=r["k"], topology=r["topology"],
                        mapping=r["mapping"], fault=r["fault"]),
            windows[(r["fault"], r["k"])], DETECTOR_AVAIL_FACTOR * ref)
        r["dw_availability"] = avail
        r["dw_response"] = dw_resp if np.isfinite(dw_resp) else None
        r["dw_apps"] = n

    faulty = [r for r in rows if r["fault"] != "none"]
    cells = [(k, topo) for k in d["ks"] for topo in d["topologies"]]
    resp_dom = {
        fl: all(row_of(k, topo, "avoid_suspected", fl)["mean_response"]
                < row_of(k, topo, "min_search", fl)["mean_response"]
                for k, topo in cells)
        for fl in ("partition", "gmn_churn")}
    avail_pairs = [(row_of(k, topo, "avoid_suspected", fl),
                    row_of(k, topo, "min_search", fl))
                   for fl in ("partition", "gmn_churn")
                   for k, topo in cells]
    avail_ok = (all(a["dw_availability"] >= b["dw_availability"]
                    for a, b in avail_pairs)
                and any(a["dw_availability"] > b["dw_availability"]
                        for a, b in avail_pairs))
    conservation = all(
        r["beacons_rx"] + r["msgs_lost"]
        == (r["k"] - 1) * r["beacons_tx"] + r["retries_tx"] for r in rows)

    # the already-compiled detector programs swallow a fresh
    # susp_mult / retry_after knob grid with zero new XLA programs
    c0 = SW.cache_size()
    reuse = ExperimentSpec(
        base=base, shapes=d["ks"][-1:],
        policies=(("avoid_suspected", "periodic"),),
        topologies=d["topologies"],
        knobs={"dn_th": g["dn_th"], "T_b": d["T_b"],
               "susp_mult": (d["susp_mult"] / 2, d["susp_mult"] * 2),
               "retry_after": d["retry_after"] + 50.0,
               **d.get("knobs", {})},
        workloads=(WorkloadSpec.make("interference", seeds=seeds),),
        faults=faults, sim_len=g["sim_len"], mode="seq")
    reuse_frame = reuse.run()
    no_recompile = (reuse_frame.compiles == 0
                    and SW.cache_size() - c0 == 0)

    return {
        "detector_rows": rows,
        "detector_grid": dict(d),
        "detector_avail_factor": DETECTOR_AVAIL_FACTOR,
        "detector_compiles": frame.compiles,
        "detector_expected_programs": frame.expected_programs,
        "claim_detector_resp_partition": bool(resp_dom["partition"]),
        "claim_detector_resp_churn": bool(resp_dom["gmn_churn"]),
        "claim_detector_availability": bool(avail_ok),
        "claim_detector_conservation": bool(conservation),
        "claim_detector_retries": bool(
            sum(r["retries_tx"] for r in faulty) > 0),
        "claim_detector_active": bool(
            all(r["susp_onsets"] > 0 for r in faulty)),
        "claim_detector_off_bitwise": bool(_golden_detector_off()),
        "claim_detector_no_recompile": bool(no_recompile),
    }


def run(verbose: bool = True, grid: str = "tiny") -> dict:
    g = GRIDS[grid]
    faults, (t_down, t_heal) = _fault_axis(g)
    workload = WorkloadSpec.make("interference", seeds=g["seeds"])
    base = SimParams(m=g["m"], n_childs=g["n_childs"],
                     max_apps=g["max_apps"], queue_cap=g["queue_cap"])

    spec = ExperimentSpec(
        base=base, shapes=g["ks"], policies=g["policies"],
        topologies=g["topologies"], knobs={"dn_th": g["dn_th"]},
        workloads=(workload,), faults=faults,
        sim_len=g["sim_len"], mode="seq")
    frame, t_total = timed(spec.run)

    fault_labels = [f.label for f in faults]
    faulty_labels = [l for l in fault_labels if l != "none"]
    rows = []
    complete_ok = True
    for gr in frame.groups:
        st = gr.state
        arr = np.asarray(st["app_arrive"])
        done = np.asarray(st["app_done"])
        complete_ok &= bool((done[arr < 1e17] < 1e17).all())
        k, topo = gr.combo.shape.k, gr.combo.topology.kind
        pol = gr.combo.policy.mapping
        sel = dict(k=k, topology=topo, mapping=pol, fault=gr.fault_label)
        rows.append({
            "k": k, "topology": topo, "mapping": pol,
            "fault": gr.fault_label,
            "mean_response": float(np.nanmean(frame.mean_response(**sel))),
            "beacons_tx": int(np.asarray(st["beacons_tx"]).sum()),
            "beacons_rx": int(np.asarray(st["beacons_rx"]).sum()),
            "msgs_lost": int(frame.msgs_lost(**sel).sum()),
            "reroutes": int(frame.reroutes(**sel).sum()),
            "downtime": float(frame.downtime(**sel).sum()),
            "dropped": int(np.asarray(st["dropped"]).sum()),
            "events": int(np.asarray(st["events_processed"]).sum()),
            "wall_s": float(gr.wall_s),
        })

    def point_rows(k, topo, pol):
        return {r["fault"]: r for r in rows
                if r["k"] == k and r["topology"] == topo
                and r["mapping"] == pol}

    # conservation per row (every grid fabric is non-ideal): each lane
    # obeys it individually, so the group-summed counters do too
    conservation = all(
        r["beacons_rx"] + r["msgs_lost"] == (r["k"] - 1) * r["beacons_tx"]
        for r in rows)
    lost_under_partition = all(r["msgs_lost"] > 0 for r in rows
                               if r["fault"] == "partition")
    lanes = len(g["seeds"])
    downtime_ok = all(
        r["downtime"] == _partition_links(r["k"]) * (t_heal - t_down) * lanes
        for r in rows if r["fault"] == "partition")

    # graceful degradation: response under every scenario vs the same
    # point's no-fault anchor
    degradation = []
    for k in g["ks"]:
        for topo in g["topologies"]:
            for pol, _ in g["policies"]:
                by_fault = point_rows(k, topo, pol)
                anchor = by_fault["none"]["mean_response"]
                worst = max(by_fault[l]["mean_response"]
                            for l in faulty_labels)
                degradation.append({
                    "k": k, "topology": topo, "mapping": pol,
                    "worst_over_none": float(worst / anchor)})
    worst_degradation = max(d["worst_over_none"] for d in degradation)

    # a second spec, every stochastic fault seed shifted, same axis
    # structure (so every per-k padded schedule capacity matches): the
    # fault-aware programs are already compiled, so zero new XLA programs
    reuse = ExperimentSpec(
        base=base, shapes=g["ks"], policies=g["policies"],
        topologies=g["topologies"], knobs={"dn_th": g["dn_th"]},
        workloads=(workload,), faults=_fault_axis(g, seed_offset=100)[0],
        sim_len=g["sim_len"], mode="seq")
    reuse_frame = reuse.run()

    anchor_ok = _golden_anchor()
    det = _detector_tier(g)

    payload = {
        "grid": grid,
        "rows": rows,
        "degradation": degradation,
        "worst_degradation": float(worst_degradation),
        "graceful_factor": GRACEFUL_FACTOR,
        "fault_axis": [f.to_dict() for f in faults],
        "meta": topology_meta(topologies=list(g["topologies"]), grid=grid,
                              m=g["m"], ks=list(g["ks"])),
        "paper_claim": "the clustered manager's message-passing protocol "
                       "is analyzed on a static fabric (Sec 5.4); this "
                       "frontier extends the analysis to a faulty one",
        "n_compiles": frame.compiles,
        "expected_programs": frame.expected_programs,
        "claim_nofault_bitwise_anchor": bool(anchor_ok),
        "claim_msgs_lost_under_faults": bool(lost_under_partition),
        "claim_conservation": bool(conservation),
        "claim_all_apps_complete": bool(
            complete_ok and all(r["dropped"] == 0 for r in rows)),
        "claim_one_program_per_group": bool(
            frame.compiles == frame.expected_programs),
        "claim_fault_grid_no_recompile": bool(reuse_frame.compiles == 0),
        "claim_graceful_degradation": bool(
            worst_degradation <= GRACEFUL_FACTOR),
        "claim_downtime_accounted": bool(downtime_ok),
    }
    payload.update(det)
    payload["determinism_digest"] = determinism_digest(
        rows + det["detector_rows"])
    payload["claims_all_pass"] = all(
        v for key, v in payload.items() if key.startswith("claim_"))

    save("fault_frontier", payload, spec=spec)
    if verbose:
        csv_row("fault_frontier", t_total * 1e6,
                f"claims_all_pass={payload['claims_all_pass']}"
                f"|worst_degradation={worst_degradation:.3f}"
                f"|compiles={frame.compiles}/{frame.expected_programs}"
                f"|digest={payload['determinism_digest'][:12]}")
        for r in rows:
            print(f"  k={r['k']:3d} {r['topology']:>9} {r['mapping']:>11} "
                  f"{r['fault']:>12}: resp={r['mean_response']:.0f} "
                  f"lost={r['msgs_lost']:4d} reroutes={r['reroutes']:4d} "
                  f"downtime={r['downtime']:.3g}")
        print("  -- detector tier --")
        for r in det["detector_rows"]:
            print(f"  k={r['k']:3d} {r['topology']:>9} "
                  f"{r['mapping']:>16} {r['fault']:>9}: "
                  f"resp={r['mean_response']:.0f} "
                  f"avail={r['dw_availability']:.2f} "
                  f"onsets={r['susp_onsets']:4d} fp={r['susp_false_pos']:3d} "
                  f"retries={r['retries_tx']:3d}")
    return payload


def _partition_links(k: int) -> int:
    """Directed links crossing the default frac=0.5 cut of a k-fabric."""
    a = int(np.ceil(k * 0.5))
    return 2 * a * (k - a)


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--grid", choices=sorted(GRIDS), default="tiny")
    args = ap.parse_args()
    run(grid=args.grid)
