"""The 2D-torus fabric (``torus2d``, core/transport.py, DESIGN.md §10)
and the ``rx_trips`` counter (DESIGN.md §14): the torus hop geometry,
the grid-fabric programs against the plain reference
``bench/reference_mesh.py`` through ``ExperimentSpec``, a planted fault
that the comparison must catch, and the counter against the reference's
own count of BEACON_RX trips."""
import importlib.util
import os

import numpy as np
import pytest

from repro.core import transport as T
from repro.core.experiment import ExperimentSpec, WorkloadSpec
from repro.core.sim import SimParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GAP = 1e-5          # the benchmark's limit on the order-dependent sum
CFG_KEYS = ("m", "k", "n_childs", "max_apps", "queue_cap", "batch_pop",
            "c_b", "c_s", "c_join", "c_hop", "dn_th", "T_b", "susp_mult",
            "retry_after", "topology", "mapping", "beacon")


def _reference(name):
    path = os.path.join(ROOT, "bench", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref_mesh():
    return _reference("reference_mesh")


# -- hop geometry -----------------------------------------------------------

def test_torus_hops_on_four_by_four():
    h = T.hop_table("torus2d", 16)
    mesh = T.hop_table("mesh2d", 16)
    assert h.dtype == np.int32 and h.shape == (16, 16)
    assert (h == h.T).all() and (np.diag(h) == 0).all()
    assert h[0, 3] == 1 and h[0, 12] == 1          # wraparound links
    assert h.max() == 4
    assert (h <= mesh).all()
    # from any node: 4, 6, 4 and 1 receivers at 1..4 hops
    assert np.bincount(h[5], minlength=5).tolist() == [1, 4, 6, 4, 1]


@pytest.mark.parametrize("k", [1, 2, 4, 7, 9, 16, 30])
def test_hop_tables_by_kind(k, ref_mesh):
    """mesh2d keeps its Manhattan table; the torus wraps each dimension
    at its own length; the program and the reference build the same
    tables independently; every other kind takes the mesh's."""
    side = T.grid_side(k)
    x, y = np.arange(k) // side, np.arange(k) % side
    manhattan = np.abs(x[:, None] - x) + np.abs(y[:, None] - y)
    assert np.array_equal(T.mesh_hops(k), manhattan)
    for kind in T.TOPOLOGIES:
        h = T.hop_table(kind, k)
        if kind == "torus2d":
            assert (h == h.T).all() and (h <= manhattan).all()
            assert np.array_equal(h, ref_mesh.hops("torus2d", k))
        else:
            assert np.array_equal(h, manhattan)
    assert np.array_equal(ref_mesh.hops("mesh2d", k), manhattan)


def test_torus_host_delays_follow_its_hops():
    d = T.host_beacon_delays("torus2d", 16, src=0, c_b=1.0, c_hop=0.5)
    want = 1.0 + 0.5 * T.hop_table("torus2d", 16)[0]
    want[0] = 0.0
    assert d.tolist() == want.tolist()
    assert T.max_delivery_delay("torus2d", 16, c_b=1.0, c_hop=0.5) == 3.0
    assert T.max_delivery_delay("mesh2d", 16, c_b=1.0, c_hop=0.5) == 4.0


# -- the program against the plain reference --------------------------------

def _spec(topology, m, k, seeds, dn_th=(1, 4), sim_len=2e5, **kw):
    base = dict(m=m, k=k, n_childs=16, max_apps=32, queue_cap=512,
                topology=topology, queue_impl="tree", batch_pop=8) | kw
    return ExperimentSpec(base=SimParams(**base), knobs={"dn_th": dn_th},
                          workloads=(WorkloadSpec("interference",
                                                  seeds=seeds),),
                          sim_len=sim_len)


def _lanes(spec):
    """(reference config, stimulus arrays, program state) of every lane."""
    (group,) = spec.run().groups
    base = spec.base
    _, (arr, gmns, lens) = spec.workloads[0].build(group.combo.shape,
                                                   spec.sim_len)
    for b, th in enumerate(np.asarray(spec.knobs.dn_th)):
        cfg = {f: getattr(base, f) for f in CFG_KEYS} | {"dn_th": int(th)}
        for s in range(len(spec.workloads[0].seeds)):
            got = {key: np.asarray(v)[b, s] for key, v in group.state.items()}
            yield cfg, (arr[s], gmns[s], lens[s]), got


def _differ(got, want):
    """Leaves that differ in dtype, shape or a bit, and the relative gap
    of the order-dependent latency sum: the benchmark's comparison
    (``bench/run.py::compare_lane``), with ``iterations`` compared too."""
    bad, gap = [], 0.0
    for key, w in want.items():
        g = np.asarray(got[key])
        w = np.asarray(w)
        if g.dtype != w.dtype or g.shape != w.shape:
            bad.append(key)
        elif key == "mgmt_latency":
            gap = abs(float(g) - float(w)) / max(abs(float(w)), 1.0)
        elif not np.array_equal(g, w):
            bad.append(key)
    return bad, gap


@pytest.mark.parametrize("topology,m,k,seeds", [
    ("torus2d", 16, 4, (0, 1)), ("torus2d", 64, 16, (0, 1)),
    ("mesh2d", 64, 16, (0,))])
def test_program_equals_reference_mesh(ref_mesh, topology, m, k, seeds):
    spec = _spec(topology, m, k, seeds)
    n = 0
    for cfg, stim, got in _lanes(spec):
        want, rx_trips = ref_mesh.simulate_counted(cfg, *stim, spec.sim_len)
        bad, gap = _differ(got, want)
        assert bad == [] and gap <= GAP, (cfg["dn_th"], bad, gap)
        assert int(want["beacons_rx"]) > 0 and int(want["dropped"]) == 0
        assert "rx_trips" not in want
        assert int(got["rx_trips"]) == rx_trips
        assert 0 < rx_trips <= int(got["iterations"])
        n += 1
    assert n == 2 * len(seeds)


def test_hier_tree_program_equals_its_reference():
    """The hierarchical bus keeps every leaf the reference returns, and
    its trips of BEACON_RX cohorts stay within the loop's trips."""
    ref = _reference("reference")
    spec = _spec("hier_tree", 16, 4, (0,))
    for cfg, stim, got in _lanes(spec):
        bad, gap = _differ(got, ref.simulate(cfg, *stim, spec.sim_len))
        assert bad == [] and gap <= GAP, (cfg["dn_th"], bad, gap)
        assert 0 < int(got["rx_trips"]) <= int(got["iterations"])


def test_mesh_hops_in_the_reference_read_not_correct(ref_mesh,
                                                     monkeypatch):
    """A planted fault: the reference given mesh2d's hops where the
    program runs the torus differs on some lane."""
    spec = _spec("torus2d", 64, 16, (0,))
    real = ref_mesh.hops
    monkeypatch.setattr(ref_mesh, "hops",
                        lambda fabric, k: real("mesh2d", k))
    failed = 0
    for cfg, stim, got in _lanes(spec):
        bad, gap = _differ(got, ref_mesh.simulate(cfg, *stim, spec.sim_len))
        failed += bool(bad) or gap > GAP
    assert failed > 0


def test_reference_mesh_refuses_what_it_does_not_model(ref_mesh):
    cfg = {f: getattr(SimParams(topology="hier_tree"), f) for f in CFG_KEYS}
    with pytest.raises(NotImplementedError):
        ref_mesh.simulate(cfg, np.zeros(1), np.zeros(1), np.zeros((1, 1)),
                          1.0)
    cfg["topology"] = "torus2d"
    with pytest.raises(NotImplementedError):
        ref_mesh.simulate(cfg, np.zeros(1), np.zeros(1), np.zeros((1, 1)),
                          1.0, faults=[(0.0, 2, 0, 0)])


def test_rx_trips_zero_with_one_gmn():
    """One GMN sends no beacon, so no trip pops a delivery."""
    spec = _spec("torus2d", 16, 1, (0,), dn_th=(1,))
    (group,) = spec.run().groups
    st = group.state
    assert int(np.asarray(st["rx_trips"]).max()) == 0
    assert int(np.asarray(st["iterations"]).min()) > 0
    assert np.asarray(st["rx_trips"]).dtype == np.int32
