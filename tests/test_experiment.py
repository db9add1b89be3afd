"""Unified declarative Experiment API (core/experiment.py, DESIGN.md §12):
planner partitioning, compile accounting, dispatch fallback, bitwise
reproduction of every frozen golden through ExperimentSpec.run(), spec
provenance round-trips, and the single-implementation metric contract."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import experiment as E
from repro.core import metrics as M
from repro.core import sim as SIM
from repro.core import sweep as SW
from repro.core import workloads as W
from repro.core.experiment import ExperimentSpec, WorkloadSpec
from repro.core.sim import SimParams, SimPolicy, run

from test_sweep import (_FIG3B_SPOT_BEACONS, _FIG3B_SPOT_SHA,
                        _GOLDEN_APP_DONE_SHA, _GOLDEN_BEACONS)


def _params(k=4, **kw):
    kw.setdefault("m", 16)
    kw.setdefault("n_childs", 16)
    kw.setdefault("max_apps", 32)
    kw.setdefault("queue_cap", 512)
    return SimParams(k=k, **kw)


# --------------------------------------------------------------------------
# Satellite: single metric implementation, re-exported
# --------------------------------------------------------------------------

def test_metric_import_paths_resolve_to_same_function():
    """sim.py and sweep.py re-export the metrics module's functions —
    one implementation, not three drifting copies."""
    for name in ("response_times", "speedup", "mean_response", "beacons",
                 "beacons_rx", "mgmt_msgs", "mgmt_latency", "mgmt_proc"):
        assert getattr(SIM, name) is getattr(M, name), name
        assert getattr(SW, name) is getattr(M, name), name


def test_metrics_shape_polymorphic():
    """The unified metrics accept both unbatched run() states and
    batched sweep states."""
    p = _params()
    arr, gmns, lens = W.independent_tasks(p, n_apps=1)
    st = run(p, arr, gmns, lens, 1e7)
    s_scalar = M.speedup(st, lens)
    assert s_scalar.shape == ()
    assert 1.0 < float(s_scalar) <= p.m
    wl = W.independent_batch(p, seeds=(0,), n_apps=1)
    stb = SW.sweep(p.shape, SW.knob_batch(dn_th=(4, 8)), wl, 1e7)
    s_grid = M.speedup(stb, wl[2])
    assert s_grid.shape == (2, 1)
    assert float(s_grid[0, 0]) == float(s_scalar)


# --------------------------------------------------------------------------
# Planner
# --------------------------------------------------------------------------

def test_planner_grouping_is_minimal():
    """No two groups share a static combo, even when the axes contain
    duplicates; order is first-seen."""
    p = _params()
    spec = ExperimentSpec(base=p,
                          topologies=("ideal", "hier_tree", "ideal"),
                          policies=(("min_search", "threshold"),
                                    ("round_robin", "periodic"),
                                    ("min_search", "threshold")),
                          sim_len=1e5)
    plan = spec.plan()
    combos = [(c.shape, c.policy, c.topology) for c in plan.combos]
    assert len(combos) == len(set(combos)) == 4   # 2 policies x 2 topologies
    assert plan.combos[0].policy.mapping == "min_search"
    assert plan.combos[0].topology.kind == "ideal"


def test_planner_queue_impl_axis_folds_into_shape():
    spec = ExperimentSpec(base=_params(), queue_impls=("linear", "tree"),
                          sim_len=1e5)
    plan = spec.plan()
    assert [c.shape.queue_impl for c in plan.combos] == ["linear", "tree"]
    assert plan.n_groups == 2


def test_planner_expected_programs():
    spec = ExperimentSpec(base=_params(),
                          topologies=("ideal", "mesh2d"),
                          knobs={"dn_th": (1, 2, 4)},
                          workloads=(WorkloadSpec("interference",
                                                  seeds=(0, 1)),
                                     WorkloadSpec("bursty", seeds=(0,))),
                          sim_len=1e5)
    plan = spec.plan()
    assert plan.n_groups == 2
    assert plan.expected_programs("seq") == 2
    # vmap specializes on the lane count too: S=2 and S=1 each compile
    assert plan.expected_programs("vmap") == 4


def test_cache_grows_by_exactly_group_count_on_fresh_cache():
    """The one-XLA-program-per-group guarantee, measured: a spec over
    never-before-compiled shapes adds exactly n_groups cache entries."""
    # m=12/k=3 with queue_cap=384 is used nowhere else in the suite, so
    # the jit cache cannot have these combos warm
    base = SimParams(m=12, k=3, n_childs=6, max_apps=16, queue_cap=384)
    spec = ExperimentSpec(base=base,
                          topologies=("ideal", "hier_tree"),
                          policies=(("hashed_random", "periodic"),
                                    ("round_robin", "threshold")),
                          knobs={"dn_th": (2, 4)},
                          workloads=(WorkloadSpec("interference",
                                                  seeds=(0,)),),
                          sim_len=1e5)
    c0 = SW.cache_size()
    frame = spec.run(mode="seq")
    assert SW.cache_size() - c0 == spec.plan().n_groups == 4
    assert frame.compiles == 4
    # re-running the same spec compiles nothing new
    frame2 = spec.run(mode="seq")
    assert frame2.compiles == 0


def test_pmap_falls_back_cleanly_on_single_device():
    """dispatch="pmap" on a single-device backend degrades to the auto
    choice (seq on CPU) with identical results."""
    import jax
    if jax.device_count() > 1:
        pytest.skip("host unexpectedly exposes multiple devices")
    p = _params()
    spec = ExperimentSpec(base=p, knobs={"dn_th": (1, 4)},
                          workloads=(WorkloadSpec("interference",
                                                  seeds=(0,)),),
                          sim_len=2e5)
    fp = spec.run(mode="pmap")
    fs = spec.run(mode="seq")
    assert fp.mode_requested == "pmap"
    assert fp.mode in ("seq", "vmap")
    a, b = fp.state(), fs.state()
    assert all(np.array_equal(a[k], b[k]) for k in a)


def test_pmap_dispatches_across_forced_host_devices():
    """With XLA forced to expose 2 host devices, pmap dispatch really
    places groups on distinct devices and stays bitwise with seq."""
    code = textwrap.dedent("""
        import numpy as np, jax
        from repro.core.experiment import ExperimentSpec, WorkloadSpec
        from repro.core.sim import SimParams
        assert jax.device_count() == 2, jax.device_count()
        p = SimParams(m=16, k=4, n_childs=16, max_apps=32, queue_cap=512)
        spec = ExperimentSpec(base=p, topologies=("ideal", "hier_tree"),
                              knobs={"dn_th": (1, 4)},
                              workloads=(WorkloadSpec("interference",
                                                      seeds=(0,)),),
                              sim_len=2e5)
        fp = spec.run(mode="pmap")
        fs = spec.run(mode="seq")
        assert fp.mode == "pmap"
        devs = [g["device"] for g in fp.manifest()["groups"]]
        assert len(set(devs)) == 2, devs
        for topo in ("ideal", "hier_tree"):
            a, b = fp.state(topology=topo), fs.state(topology=topo)
            assert all(np.array_equal(a[k], b[k]) for k in a), topo
        print("PMAP_BITWISE_OK")
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "PMAP_BITWISE_OK" in out.stdout


# --------------------------------------------------------------------------
# Bitwise golden gates through ExperimentSpec.run()
# --------------------------------------------------------------------------

def test_spec_reproduces_pr2_golden_grid_bitwise():
    """The frozen PR-2 golden grid (beacons + app_done sha) through the
    declarative surface."""
    import hashlib
    spec = ExperimentSpec(base=_params(), knobs={"dn_th": (1, 2, 4, 8)},
                          workloads=(WorkloadSpec("interference",
                                                  seeds=(0, 1)),),
                          sim_len=3e5)
    frame = spec.run()
    st = frame.state()
    assert np.asarray(st["beacons_tx"]).tolist() == _GOLDEN_BEACONS
    done = np.asarray(st["app_done"], np.float32)
    assert hashlib.sha256(done.tobytes()).hexdigest() == _GOLDEN_APP_DONE_SHA


def test_spec_reproduces_fig3b_spot_golden_bitwise():
    """The fig3b-shaped spot grid (captured at 137008a) through the
    declarative surface."""
    import hashlib
    spec = ExperimentSpec(
        base=SimParams(m=64, k=16, n_childs=50, max_apps=128,
                       queue_cap=2048),
        knobs={"dn_th": (1, 2, 4, 8, 16, 32)},
        workloads=(WorkloadSpec("interference", seeds=(1,)),),
        sim_len=1e6)
    frame = spec.run()
    st = frame.state()
    assert np.asarray(st["beacons_tx"]).tolist() == _FIG3B_SPOT_BEACONS
    done = np.asarray(st["app_done"], np.float32)
    assert hashlib.sha256(done.tobytes()).hexdigest() == _FIG3B_SPOT_SHA


def test_spec_tree_matches_linear_bitwise_via_queue_axis():
    """The tree==linear contract through the declarative queue_impls
    axis, on a non-ideal fabric that stresses the bulk push."""
    spec = ExperimentSpec(base=_params(), queue_impls=("linear", "tree"),
                          topologies=("hier_tree",),
                          knobs={"dn_th": (1, 4)},
                          workloads=(WorkloadSpec("interference",
                                                  seeds=(0,)),),
                          sim_len=3e5)
    frame = spec.run()
    lin = frame.state(queue_impl="linear")
    tre = frame.state(queue_impl="tree")
    for key in ("app_done", "app_arrive", "beacons_tx", "beacons_rx",
                "events_processed", "dropped", "mgmt_msgs", "mgmt_latency",
                "mgmt_proc"):
        assert np.array_equal(lin[key], tre[key]), key


def test_spec_matches_legacy_sweep_entry_points_bitwise():
    """A cross-axis spec agrees leaf-for-leaf with the deprecated
    sweep_policies/sweep_topologies shims fed the same grid."""
    p = _params()
    pols = (SimPolicy("min_search", "threshold"),
            SimPolicy("round_robin", "periodic"))
    spec = ExperimentSpec(base=p, policies=pols,
                          topologies=("ideal", "hier_tree"),
                          knobs={"dn_th": (2, 8)},
                          workloads=(WorkloadSpec("interference",
                                                  seeds=(0,)),),
                          sim_len=2e5)
    frame = spec.run()
    wl = W.interference_batch(p, seeds=(0,), sim_len=2e5)
    kn = SW.knob_batch(dn_th=(2, 8))
    with pytest.deprecated_call():
        by_pol = SW.sweep_policies(p.shape, kn, wl, policies=pols,
                                   sim_len=2e5, topology="hier_tree")
    with pytest.deprecated_call():
        by_topo = SW.sweep_topologies(p.shape, kn, wl,
                                      topologies=("ideal", "hier_tree"),
                                      sim_len=2e5)
    for pol in pols:
        a = frame.state(mapping=pol.mapping, beacon=pol.beacon,
                        topology="hier_tree")
        b = by_pol[(pol.mapping, pol.beacon)]
        assert all(np.array_equal(a[k], np.asarray(b[k])) for k in a)
    for kind in ("ideal", "hier_tree"):
        a = frame.state(mapping="min_search", beacon="threshold",
                        topology=kind)
        b = by_topo[kind]
        assert all(np.array_equal(a[k], np.asarray(b[k])) for k in a)


# --------------------------------------------------------------------------
# ResultFrame: columns, rows, provenance round-trip
# --------------------------------------------------------------------------

def test_resultframe_columns_aligned_and_ordered():
    spec = ExperimentSpec(base=_params(), shapes=(2, 4),
                          knobs={"dn_th": (1, 4)},
                          workloads=(WorkloadSpec("interference",
                                                  seeds=(0, 1)),),
                          sim_len=2e5)
    frame = spec.run()
    assert len(frame) == 2 * 2 * 2                # shapes x B x S
    assert frame.col("k").tolist() == [2] * 4 + [4] * 4
    assert frame.col("dn_th").tolist() == [1, 1, 4, 4] * 2
    assert frame.col("seed").tolist() == [0, 1] * 4
    # selection sugar matches manual masking
    sel = frame.mean_response(k=4, dn_th=4)
    man = frame.col("mean_response")[(frame.col("k") == 4)
                                     & (frame.col("dn_th") == 4)]
    assert np.array_equal(sel, man, equal_nan=True)
    # every metric accessor returns an aligned (N,) column
    for acc in (frame.beacons_tx, frame.beacons_rx, frame.mgmt_msgs,
                frame.mgmt_latency, frame.mgmt_proc, frame.speedup):
        assert acc().shape == (len(frame),)


def test_mask_rounds_float_knob_selectors_through_float32():
    """Knob columns hold float32 values; a float selector not exactly
    representable in f32 (e.g. 0.1) must still match its lane."""
    spec = ExperimentSpec(base=_params(), knobs={"c_s": (0.1, 8.0)},
                          workloads=(WorkloadSpec("interference",
                                                  seeds=(0,)),),
                          sim_len=1e5)
    frame = spec.run()
    assert frame.mask(c_s=0.1).sum() == 1
    assert frame.speedup(c_s=0.1).shape == (1,)
    # generated accessors cover every metric column
    assert frame.dropped().shape == (2,)
    assert frame.events(c_s=8.0).shape == (1,)
    assert np.array_equal(frame.metric("beacons_tx"), frame.beacons_tx())


def test_resultframe_payload_json_roundtrip():
    spec = ExperimentSpec(base=_params(), knobs={"dn_th": (2,)},
                          workloads=(WorkloadSpec("interference",
                                                  seeds=(0,)),),
                          sim_len=1e5)
    frame = spec.run()
    payload = frame.to_payload()
    back = json.loads(json.dumps(payload, default=float))
    assert back["rows"] == json.loads(json.dumps(frame.rows(),
                                                 default=float))
    assert back["experiment"]["n_groups"] == 1
    spec2 = E.spec_from_dict(back["spec"])
    assert spec2.to_dict() == json.loads(json.dumps(spec.to_dict()))
    # the reconstructed spec reproduces the same results bitwise
    st2 = spec2.run().state()
    st = frame.state()
    assert all(np.array_equal(st[k], st2[k]) for k in st)


def test_raw_workload_spec_provenance_and_errors():
    p = _params()
    wl = W.interference_batch(p, seeds=(0,), sim_len=1e5)
    w = WorkloadSpec.raw(wl)
    d = w.to_dict()
    assert d["raw"]["shapes"][0] == [1, p.max_apps]
    assert len(d["raw"]["sha256"]) == 64
    with pytest.raises(ValueError, match="cannot be reconstructed"):
        E.spec_from_dict({"workloads": [d], "base": {}, "shapes": [],
                          "policies": [], "topologies": [], "knobs": {},
                          "sim_len": 1e5, "mode": "auto"})
    with pytest.raises(ValueError, match="unknown workload kind"):
        WorkloadSpec("nope")
    with pytest.raises(ValueError, match="unknown knob axes"):
        ExperimentSpec(base=p, knobs={"warp": (1,)})
    with pytest.raises(ValueError, match="unknown mode"):
        ExperimentSpec(base=p, mode="warp")


def test_scenario_axis_multiple_workload_specs():
    """Several WorkloadSpecs ride one spec as the scenario axis; lanes
    keep their per-scenario metadata."""
    spec = ExperimentSpec(
        base=_params(),
        knobs={"dn_th": (2,)},
        workloads=(WorkloadSpec("interference", seeds=(0,)),
                   WorkloadSpec.make("hotspot", seeds=(0,), hot_frac=0.9)),
        sim_len=2e5)
    frame = spec.run()
    assert len(frame) == 2
    assert frame.col("workload").tolist() == ["interference", "hotspot"]
    st_hot = frame.state(workload_index=1)
    assert np.asarray(st_hot["events_processed"]).sum() > 0


# --------------------------------------------------------------------------
# Faults axis (DESIGN.md §13) and the strict schema-v5 reader
# --------------------------------------------------------------------------

def test_faults_axis_crosses_groups_and_fills_metrics():
    """The faults axis crosses every group, adds at most one extra
    program per group (schedules padded to one length per k), labels the
    ``fault`` coordinate, and zero-fills the availability metrics on
    no-fault rows."""
    from repro.core.faults import FaultSpec
    # m=12/k=4 with queue_cap=320 is used nowhere else in the suite, so
    # the jit cache cannot have the no-fault program for this combo warm
    p = SimParams(m=12, k=4, n_childs=6, max_apps=16, queue_cap=320)
    spec = ExperimentSpec(
        base=p, shapes=(4,), topologies=("hier_tree",),
        knobs={"dn_th": (2,)},
        workloads=(WorkloadSpec(seeds=(0,)),),
        faults=(None,
                FaultSpec.poisson_links(rate=3e-4, repair=3e4, seed=2),
                FaultSpec.partition(t_down=8e4, t_heal=1.5e5, name="part")),
        sim_len=2e5, mode="seq")
    frame = spec.run()
    assert frame.compiles == frame.expected_programs == 2
    assert sorted(set(frame.col("fault"))) \
        == ["none", "part", "poisson_links"]
    assert (frame.msgs_lost(fault="none") == 0).all()
    assert frame.msgs_lost(fault="poisson_links").sum() > 0
    assert (frame.downtime(fault="part") > 0).all()
    # the no-fault group is the bitwise anchor: same leaves as a bare run
    wl = W.interference_batch(p, seeds=(0,), sim_len=2e5)
    st = SW.sweep(p.shape, SW.knob_batch(dn_th=(2,)), wl, 2e5,
                  topology="hier_tree")
    anchor = frame.state(topology="hier_tree", fault="none")
    for key in ("app_done", "beacons_tx", "beacons_rx"):
        assert np.array_equal(np.asarray(st[key]), anchor[key]), key


def test_faults_axis_roundtrips_and_validates():
    from repro.core.faults import FaultSpec
    spec = ExperimentSpec(
        base=_params(), shapes=(4,), knobs={"dn_th": (2,)},
        faults=(None, FaultSpec.gmn_churn(rate=1e-5, seed=3)),
        sim_len=1e5)
    spec2 = E.spec_from_dict(spec.to_dict())
    assert spec2.faults == spec.faults
    with pytest.raises(TypeError):
        ExperimentSpec(base=_params(), faults=("poisson_links",))
    # v1 payloads (no faults key) default to the no-fault axis
    d = spec.to_dict()
    del d["faults"]
    assert E.spec_from_dict(d).faults == (None,)


def test_spec_from_dict_rejects_unknown_fields():
    """Regression (ISSUE 6 satellite): a payload written by a newer
    schema — e.g. a v5 results file with an axis this reader does not
    know — must error loudly, not silently reconstruct a spec that runs
    different experiments than the payload records."""
    spec = ExperimentSpec(base=_params(), shapes=(4,),
                          knobs={"dn_th": (2,)}, sim_len=1e5)
    d = spec.to_dict()
    assert E.spec_from_dict(d) is not None          # clean payload reads
    with pytest.raises(ValueError, match="thermal_model"):
        E.spec_from_dict(dict(d, thermal_model="on"))
    with pytest.raises(ValueError, match="version"):
        E.spec_from_dict(dict(d, version=E.SPEC_VERSION + 1))
