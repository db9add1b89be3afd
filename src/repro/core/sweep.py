"""Batched design-space sweeps over the TLM simulator (paper Sec 5).

The paper's evaluation is a design-space exploration: sweep the beacon
threshold ``dn_th`` and the cost coefficients across cluster counts and
workload seeds (Figs 2-3, Table 5).  ``sim.run`` compiles once per
``SimShape`` (m, k, n_childs, queue_cap, max_apps, queue_impl); this
module goes one step further and runs a whole grid of knob configs x
workload seeds in a single compiled program:

    p = SimParams(m=256, k=16)
    knobs = knob_batch(dn_th=(1, 2, 4, 8, 16, 32))        # B = 6 configs
    wl = W.interference_batch(p, seeds=(1, 2), sim_len=4e6)  # S = 2 seeds
    st = sweep(p.shape, knobs, wl, sim_len=4e6)
    beacons(st)          # (6, 2) int array

Every leaf of the returned state dict carries leading axes ``(B, S)``:
axis 0 indexes the knob config, axis 1 the workload.  Results are bitwise
identical to per-config ``sim.run`` calls (tests/test_sweep.py): ``vmap``
batches the very same traced computation, it does not approximate it.

Two execution strategies sit behind one API (see ``sweep``'s ``mode``):
"vmap" runs the grid as one batched XLA program (the accelerator path —
the inner ``lax.while_loop`` batches as run-until-all-lanes-done with
masked updates), "seq" replays the single-config program warm (the CPU
path — zero recompiles across the grid).  Either way the design-space
grid costs one compilation per (m, k) shape instead of one per point.

Sweeping the *static* axes (shapes, policies, topologies, queue impls)
lives one level up in :mod:`repro.core.experiment` (DESIGN.md §12): an
``ExperimentSpec`` composes every axis declaratively and its planner
calls back into this module's jitted programs, so results stay bitwise
identical.  ``sweep_policies``/``sweep_topologies`` below are the
deprecated pre-spec shims.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policies import DEFAULT_POLICY, SimPolicy, policy_grid
# batched metrics live in repro.core.metrics (single implementation,
# re-exported here and from repro.core.sim — tests/test_experiment.py
# asserts both import paths resolve to the same functions)
from repro.core.metrics import (beacons, beacons_rx, evq_peak,
                                mean_response, mgmt_latency, mgmt_msgs,
                                mgmt_proc, response_times, speedup,
                                trace_dropped)
from repro.core.sim import (SimKnobs, SimParams, SimShape, _run,
                            compile_cache_size, simulate)
from repro.core.transport import DEFAULT_TOPOLOGY, Topology, topology_grid

__all__ = ["knob_batch", "knob_product", "sweep", "sweep_policies",
           "sweep_topologies", "policy_grid", "topology_grid", "cache_size",
           "response_times", "speedup", "mean_response", "beacons",
           "beacons_rx", "mgmt_msgs", "mgmt_latency", "mgmt_proc"]


def knob_batch(*, c_b=8.0, c_s=8.0, c_join=8.0, dn_th=4,
               T_b=1000.0, c_hop=2.0, susp_mult=3.0,
               retry_after=0.0) -> SimKnobs:
    """Build a batch of B knob configs.  Each argument is a scalar
    (broadcast) or a length-B sequence; sequences must agree on B."""
    vals = {"c_b": c_b, "c_s": c_s, "c_join": c_join, "dn_th": dn_th,
            "T_b": T_b, "c_hop": c_hop, "susp_mult": susp_mult,
            "retry_after": retry_after}
    sizes = {name: len(v) for name, v in vals.items()
             if np.ndim(v) == 1}
    if len(set(sizes.values())) > 1:
        raise ValueError(f"knob sequences disagree on batch size: {sizes}")
    b = next(iter(sizes.values()), 1)
    def col(v, dtype):
        a = np.asarray(v, dtype)
        return jnp.asarray(np.broadcast_to(a, (b,)))
    return SimKnobs(c_b=col(vals["c_b"], np.float32),
                    c_s=col(vals["c_s"], np.float32),
                    c_join=col(vals["c_join"], np.float32),
                    dn_th=col(vals["dn_th"], np.int32),
                    T_b=col(vals["T_b"], np.float32),
                    c_hop=col(vals["c_hop"], np.float32),
                    susp_mult=col(vals["susp_mult"], np.float32),
                    retry_after=col(vals["retry_after"], np.float32))


def knob_product(*, c_b=(8.0,), c_s=(8.0,), c_join=(8.0,), dn_th=(4,),
                 T_b=(1000.0,), c_hop=(2.0,), susp_mult=(3.0,),
                 retry_after=(0.0,)) -> SimKnobs:
    """Cartesian product of knob axes, flattened to one batch axis in
    ``itertools.product`` order (c_b outermost, retry_after innermost)."""
    rows = list(itertools.product(np.atleast_1d(c_b), np.atleast_1d(c_s),
                                  np.atleast_1d(c_join),
                                  np.atleast_1d(dn_th), np.atleast_1d(T_b),
                                  np.atleast_1d(c_hop),
                                  np.atleast_1d(susp_mult),
                                  np.atleast_1d(retry_after)))
    cb, cs, cj, th, tb, ch, sm, ra = (np.asarray(col) for col in zip(*rows))
    return SimKnobs(c_b=jnp.asarray(cb, jnp.float32),
                    c_s=jnp.asarray(cs, jnp.float32),
                    c_join=jnp.asarray(cj, jnp.float32),
                    dn_th=jnp.asarray(th, jnp.int32),
                    T_b=jnp.asarray(tb, jnp.float32),
                    c_hop=jnp.asarray(ch, jnp.float32),
                    susp_mult=jnp.asarray(sm, jnp.float32),
                    retry_after=jnp.asarray(ra, jnp.float32))


@functools.partial(jax.jit, static_argnums=(0, 6, 7, 9))
def _sweep(shape, knobs, arrivals, gmns, lengths, sim_len,
           policy=DEFAULT_POLICY, topology=DEFAULT_TOPOLOGY, faults=None,
           trace=None):
    # the fault schedule (repro.core.faults) is shared across all lanes:
    # closed over rather than vmapped, like sim_len; the TraceSpec is
    # static (core/trace.py) — one program per spec, None = off
    def per_workload(a, g, l):
        return jax.vmap(
            lambda kn: simulate(shape, kn, a, g, l, sim_len, policy,
                                topology, faults, trace))(knobs)
    # out_axes=1: knob-config axis stays leading, workload axis second
    return jax.vmap(per_workload, in_axes=0, out_axes=1)(
        arrivals, gmns, lengths)


def sweep(shape, knobs: SimKnobs, workload, sim_len: float = 1e7,
          mode: str = "auto", policy: SimPolicy | None = None,
          topology: Topology | None = None,
          queue_impl: str | None = None, batch_pop: int | None = None,
          faults=None, trace=None):
    """Run B knob configs x S workloads with one compilation per
    (shape, policy, topology).

    shape     SimShape, or a full SimParams — then ALL of its static
              axes round-trip: `.shape` (incl. queue_impl), `.policy`
              and `.topo` are taken wherever the corresponding kwarg is
              left unset (explicit kwargs still win).
    knobs     SimKnobs with leading axis (B,) — see knob_batch/knob_product.
    workload  (arrivals (S, A), arrival_gmns (S, A), lengths (S, A, n))
              as produced by workloads.interference_batch / *_grid.
    policy    SimPolicy (mapping x beacon, core/policies.py).  Static —
              every combination is its own XLA program; sweep the policy
              axis declaratively with ``experiment.ExperimentSpec``
              (DESIGN.md §12).
    topology  Topology (fabric model, core/transport.py).  Also static —
              sweep the fabric axis via ``ExperimentSpec`` too; the
              numeric transport knobs (c_b, c_hop) stay traced.
    mode      execution strategy; results are bitwise identical across
              modes (tests/test_sweep.py):
              - "vmap": the whole grid is ONE batched XLA program (one
                compile per (shape, policy, topology, B, S)).  Wins on
                accelerators where lanes vectorize; on CPU the batched
                while-loop pays for every event handler in every lane
                each step.
              - "seq": warm re-runs of the single-config program (one
                compile per (shape, policy, topology), zero recompiles
                across the grid) — the fast path on CPU.
              - "auto" (default): "seq" on CPU, "vmap" elsewhere.
    queue_impl  event-queue structure override (core/eventq.py,
              DESIGN.md §11): "linear", "tree" or "calendar".  Part of
              the static shape; None (default) keeps
              ``shape.queue_impl``.  Results are bitwise identical
              across impls — "tree"/"calendar" replace the per-event
              O(Q log Q) push with one fused commit per iteration; the
              difference is wall-clock only.
    batch_pop  same-timestamp BEACON_RX batching override (DESIGN.md
              §11): pop up to this many root-equal-time RX events per
              body iteration.  Static; None keeps ``shape.batch_pop``.
              Bitwise identical to batch_pop=1 — wall-clock only.
    faults    optional FaultSpec or prebuilt FaultSchedule
              (repro.core.faults, DESIGN.md §13), shared across every
              (knob, workload) lane.  The schedule is traced: a grid of
              fault seeds/intensities of the same length re-uses the
              compiled fault-aware program in both modes (zero
              recompiles, the fault_frontier claim gate).
    trace     optional TraceSpec (repro.core.trace, DESIGN.md §14),
              shared across every lane.  Static like shape/policy: None
              (default) runs the uninstrumented program bitwise equal
              to the pre-trace goldens; a fixed spec adds the ring/
              timeline/histogram leaves to every (B, S, ...) lane with
              zero recompiles across the knob/seed grid.

    Returns the final-state dict with every leaf batched to (B, S, ...).
    """
    if isinstance(shape, SimParams):
        # round-trip every static axis of a full SimParams: policy and
        # topology used to be silently dropped here (ISSUE 5 satellite;
        # regression test in tests/test_sweep.py)
        if policy is None:
            policy = shape.policy
        if topology is None:
            topology = shape.topo
        shape = shape.shape
    if policy is None:
        policy = DEFAULT_POLICY
    if topology is None:
        topology = DEFAULT_TOPOLOGY
    if queue_impl is not None and queue_impl != shape.queue_impl:
        shape = dataclasses.replace(shape, queue_impl=queue_impl)
    if batch_pop is not None and batch_pop != shape.batch_pop:
        shape = dataclasses.replace(shape, batch_pop=batch_pop)
    arrivals, gmns, lengths = workload
    arrivals = jnp.asarray(arrivals, jnp.float32)
    gmns = jnp.asarray(gmns, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.float32)
    if arrivals.ndim != 2 or lengths.ndim != 3:
        raise ValueError("workload arrays need a leading seed axis (S,); "
                         "use workloads.interference_batch")
    if knobs.dn_th.ndim != 1:
        raise ValueError("knobs need a leading batch axis (B,); "
                         "use knob_batch/knob_product")
    if isinstance(topology, str):
        topology = Topology(topology)
    from repro.core.faults import as_schedule
    from repro.core.trace import TraceSpec
    if trace is not None and not isinstance(trace, TraceSpec):
        raise ValueError(f"trace must be a TraceSpec or None, got {trace!r}")
    faults = as_schedule(faults, shape.k, sim_len)
    if mode == "auto":
        mode = "seq" if jax.default_backend() == "cpu" else "vmap"
    if mode == "vmap":
        return _sweep(shape, knobs, arrivals, gmns, lengths,
                      jnp.float32(sim_len), policy, topology, faults,
                      trace)
    if mode != "seq":
        raise ValueError(f"unknown sweep mode: {mode!r}")
    b, s = knobs.dn_th.shape[0], arrivals.shape[0]
    sl = jnp.float32(sim_len)
    outs = [_run(shape, SimKnobs(*(leaf[i] for leaf in knobs)),
                 arrivals[j], gmns[j], lengths[j], sl, policy, topology,
                 faults, trace)
            for i in range(b) for j in range(s)]
    return jax.tree.map(
        lambda *leaves: jnp.stack(leaves).reshape((b, s) + leaves[0].shape),
        *outs)


def sweep_policies(shape, knobs: SimKnobs, workload, policies=None,
                   sim_len: float = 1e7, mode: str = "auto",
                   topology: Topology = DEFAULT_TOPOLOGY) -> dict:
    """DEPRECATED shim over :mod:`repro.core.experiment` — express the
    policy axis declaratively instead::

        ExperimentSpec(shapes=(shape,), policies=policies,
                       knobs=knobs, workloads=(WorkloadSpec.raw(wl),),
                       sim_len=sim_len).run()

    Returns the historical {(mapping, beacon): (B, S, ...) state dict}
    mapping, bitwise identical (the spec path runs the same programs).
    """
    warnings.warn("sweep_policies is deprecated; use "
                  "repro.core.experiment.ExperimentSpec (DESIGN.md §12)",
                  DeprecationWarning, stacklevel=2)
    from repro.core.experiment import ExperimentSpec, WorkloadSpec
    policies = tuple(policies) if policies is not None \
        else tuple(policy_grid())
    frame = ExperimentSpec(
        shapes=(shape,), policies=policies,
        topologies=(Topology(topology) if isinstance(topology, str)
                    else topology,),
        knobs=knobs, workloads=(WorkloadSpec.raw(workload),),
        sim_len=sim_len, mode=mode).run()
    return {(pol.mapping, pol.beacon):
            frame.state(mapping=pol.mapping, beacon=pol.beacon)
            for pol in policies}


def sweep_topologies(shape, knobs: SimKnobs, workload, topologies=None,
                     sim_len: float = 1e7, mode: str = "auto",
                     policy: SimPolicy = DEFAULT_POLICY) -> dict:
    """DEPRECATED shim over :mod:`repro.core.experiment` — express the
    fabric axis declaratively instead::

        ExperimentSpec(shapes=(shape,), topologies=topologies,
                       knobs=knobs, workloads=(WorkloadSpec.raw(wl),),
                       sim_len=sim_len).run()

    Returns the historical {kind: (B, S, ...) state dict} mapping,
    bitwise identical (the spec path runs the same programs).
    """
    warnings.warn("sweep_topologies is deprecated; use "
                  "repro.core.experiment.ExperimentSpec (DESIGN.md §12)",
                  DeprecationWarning, stacklevel=2)
    from repro.core.experiment import ExperimentSpec, WorkloadSpec
    if topologies is None:
        topologies = topology_grid()
    topologies = [Topology(tp) if isinstance(tp, str) else tp
                  for tp in topologies]
    frame = ExperimentSpec(
        shapes=(shape,), policies=(policy,), topologies=tuple(topologies),
        knobs=knobs, workloads=(WorkloadSpec.raw(workload),),
        sim_len=sim_len, mode=mode).run()
    return {tp.kind: frame.state(topology=tp.kind) for tp in topologies}


def cache_size() -> int:
    """Total XLA programs compiled for sweeping: one per
    (SimShape, SimPolicy, B, S) in vmap mode plus one per
    (SimShape, SimPolicy) in seq mode."""
    return _sweep._cache_size() + compile_cache_size()


# Batched metrics (response_times, mean_response, speedup, beacons,
# beacons_rx, mgmt_*) are imported from repro.core.metrics at the top of
# this module — one implementation, re-exported here for compatibility.
