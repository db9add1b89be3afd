"""The program's own instrumentation: the named scopes of the event loop
(``core/sim.py``), the ``iterations`` counter leaf, and the host spans
of ``ExperimentSpec.run`` (``core/experiment.py``).

Scopes only add metadata to the compiled program, so a device trace can
be split by part of the loop; spans put each step of a run on the
profiler's clock.  Neither may change a simulated number.
"""
import glob
import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hlo_text as HT
from repro.core import sweep as SW
from repro.core import workloads as W
from repro.core.experiment import ExperimentSpec, WorkloadSpec
from repro.core.sim import SimParams, run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOOP_SCOPES = ("sim.pop", "sim.rx", "sim.handlers", "sim.commit",
               "sim.fanout")
PLUMBING = ("parameter", "tuple", "get-tuple-element", "constant", "copy",
            "copy-start", "copy-done", "bitcast")
STEPS = ("experiment.build", "experiment.dispatch", "experiment.execute",
         "experiment.fetch")


def _params(**kw):
    base = dict(m=16, k=4, n_childs=16, max_apps=32, queue_cap=256,
                topology="hier_tree", queue_impl="tree", batch_pop=8)
    return SimParams(**(base | kw))


def _main_body(comps: dict) -> str:
    """The body of the while op with the most instructions reachable
    from it: the event loop."""
    memo = {}

    def size(name):
        if name not in memo:
            memo[name] = 0
            ops = comps[name].ops.values()
            memo[name] = len(ops) + sum(size(c) for op in ops
                                        for c in HT.called_computations(op)
                                        if c in comps)
        return memo[name]

    bodies = [c for comp in comps.values() for op in comp.ops.values()
              if op.kind == "while" for c in HT.called_computations(op)]
    return max(bodies, key=size)


def _reachable(comps: dict, name: str) -> list:
    seen, todo = [], [name]
    while todo:
        n = todo.pop()
        if n in seen or n not in comps:
            continue
        seen.append(n)
        for op in comps[n].ops.values():
            todo += HT.called_computations(op)
            todo += HT.COND_RE.findall(op.line)
    return seen


@pytest.fixture(scope="module")
def loop_hlo():
    """The batched program of a small hier_tree / tree-queue /
    batch_pop=8 grid, compiled for the CPU, parsed; and its loop body."""
    p = _params()
    arr, gmns, lens = W.interference_batch(p, seeds=(0, 1), sim_len=3e5)
    txt = SW._sweep.lower(
        p.shape, SW.knob_batch(dn_th=(1, 4)), jnp.asarray(arr, jnp.float32),
        jnp.asarray(gmns, jnp.int32), jnp.asarray(lens, jnp.float32),
        jnp.float32(3e5), p.policy, p.topo, None, None).compile().as_text()
    comps = HT.parse_module(txt)
    return txt, comps, _main_body(comps)


def test_loop_scopes_reach_the_compiled_program(loop_hlo):
    txt, comps, body = loop_hlo
    names = {op.metadata_op_name for c in _reachable(comps, body)
             for op in comps[c].ops.values()}
    for scope in LOOP_SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope
    # the set-up sits outside the loop
    assert "sim.setup" in txt
    assert not any("sim.setup" in n for n in names)


def test_every_instruction_of_the_loop_body_carries_a_scope(loop_hlo):
    """Every instruction that JAX emitted from the loop body (its op_name
    says ``while/body/``) carries a ``sim.`` scope, fused ones included.
    Of the loop body's own instructions, beside plumbing, only two kinds
    may lack one: the batched loop's lane select, which JAX's lowering of
    a vmapped while emits under the loop's own name (``.../while``), and
    instructions a compiler rewrite made with no JAX name at all (the
    reduce-window of a cumsum); each is counted apart in PERF.md."""
    _, comps, body = loop_hlo
    unscoped = [op.line for c in _reachable(comps, body)
                for op in comps[c].ops.values()
                if "/while/body/" in op.metadata_op_name
                and "/sim." not in op.metadata_op_name]
    assert unscoped == []

    def scoped(op):
        names = [op.metadata_op_name] + [
            o.metadata_op_name for c in HT.called_computations(op)
            if c in comps for o in comps[c].ops.values()]
        return any("/sim." in n for n in names)

    lane_select = 0
    for op in comps[body].ops.values():
        name = op.metadata_op_name
        if op.kind in PLUMBING or scoped(op):
            continue
        if re.search(r"/while$", name):
            lane_select += 1
        else:
            assert not name.startswith("jit("), op.line
    assert lane_select > 0


# --------------------------------------------------------------------------
# The iterations counter
# --------------------------------------------------------------------------

def _reference():
    path = os.path.join(ROOT, "bench", "reference.py")
    spec = importlib.util.spec_from_file_location("bench_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("bp,dn_th,seed", [(8, 1, 0), (8, 4, 1), (1, 2, 0)])
def test_iterations_equal_the_reference(bp, dn_th, seed):
    """One pop, or one same-time BEACON_RX cohort, per trip: the count
    the plain reference keeps (bench/reference.py)."""
    p = _params(batch_pop=bp, dn_th=dn_th)
    arr, gmns, lens = W.interference(p, seed=seed, sim_len=3e5)
    st = run(p, arr, gmns, lens, 3e5)
    cfg = {f: getattr(p, f) for f in (
        "m", "k", "n_childs", "max_apps", "queue_cap", "batch_pop", "c_b",
        "c_s", "c_join", "dn_th", "T_b", "c_hop", "susp_mult",
        "retry_after")} | {"topology": "hier_tree", "mapping": "min_search",
                           "beacon": "threshold"}
    ref = _reference().simulate(cfg, arr, gmns, lens, 3e5)
    assert int(st["events_processed"]) == ref["events_processed"]
    assert int(st["iterations"]) == ref["iterations"]


@pytest.mark.parametrize("qi,bp", [("tree", 1), ("tree", 8),
                                   ("calendar", 8), ("linear", 8),
                                   ("tree", 64)])
def test_iterations_bounded_by_events(qi, bp):
    """A trip retires at least one event and at most batch_pop, so
    ceil(events / batch_pop) <= iterations <= events, per lane."""
    p = _params(queue_impl=qi, batch_pop=bp)
    wl = W.interference_batch(p, seeds=(0, 1), sim_len=3e5)
    st = SW.sweep(p.shape, SW.knob_batch(dn_th=(1, 4)), wl, 3e5,
                  mode="vmap")
    ev = np.asarray(st["events_processed"])
    it = np.asarray(st["iterations"])
    assert it.dtype == np.int32 and it.shape == ev.shape
    for e, i in zip(ev.ravel(), it.ravel()):
        assert math.ceil(e / bp) <= i <= e
    if bp == 1:
        assert np.array_equal(it, ev)


# --------------------------------------------------------------------------
# Host spans of ExperimentSpec.run
# --------------------------------------------------------------------------

def _host_spans(profile_dir: str) -> list:
    """(name, start_ns, end_ns) of the ``experiment.`` spans a profile
    recorded, in start order."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                        recursive=True)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines
             for e in line.events if e.name.startswith("experiment.")]
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def _spec(mode, shapes=(4,)):
    return ExperimentSpec(base=_params(), shapes=shapes,
                          knobs={"dn_th": (1, 4)},
                          workloads=(WorkloadSpec("interference",
                                                  seeds=(0, 1)),),
                          sim_len=3e4, mode=mode)


@pytest.mark.parametrize("shapes", [(4,), (4, 2)], ids=["1group",
                                                         "2groups"])
def test_vmap_run_records_one_group_span_per_group(tmp_path, shapes):
    spec = _spec("vmap", shapes)
    spec.run()                                  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        frame = spec.run()
    spans = _host_spans(str(tmp_path))
    assert [n for n, _, _ in spans] == \
        ["experiment.group", *STEPS] * len(shapes)
    for i, g in enumerate(frame.groups):
        group, *steps = spans[5 * i:5 * i + 5]
        assert all(group[1] <= s <= e <= group[2] for _, s, e in steps)
        assert all(a[2] <= b[1] for a, b in zip(steps, steps[1:]))
        # the group's wall time runs from dispatch to fetch
        dispatch, fetch = steps[1], steps[3]
        assert g.wall_s == pytest.approx((fetch[2] - dispatch[1]) / 1e9,
                                         rel=0.05, abs=2e-4)
    assert frame.wall_s >= sum(g.wall_s for g in frame.groups)


def test_seq_run_records_a_span_per_lane_step(tmp_path):
    spec = _spec("seq")
    spec.run()
    with jax.profiler.trace(str(tmp_path)):
        frame = spec.run()
    names = [n for n, _, _ in _host_spans(str(tmp_path))]
    lanes = 4
    assert names == ["experiment.group", "experiment.build",
                     *["experiment.dispatch", "experiment.execute"] * lanes,
                     "experiment.fetch"]
    (g,) = frame.groups
    assert len(g.lane_wall_s) == lanes
    assert g.wall_s >= sum(g.lane_wall_s)
    assert frame.manifest()["groups"][0]["lane_wall_s"] == \
        pytest.approx(g.lane_wall_s)
