"""Unified declarative experiment API over the TLM design space.

The paper's contribution is a *design-space analysis* — centralized vs
clustered vs distributed, swept over cluster count, beacon thresholds
and fabric — but the sweep surface grew one ad-hoc entry point per axis
as the axes landed (``sweep_policies``, ``sweep_topologies``, the
``queue_impl`` kwarg, hand-rolled per-benchmark loops over k).  This
module replaces all of that with one declarative object
(DESIGN.md §12):

    spec = ExperimentSpec(
        base=SimParams(m=256, n_childs=100, max_apps=512, queue_cap=2048),
        shapes=(1, 8, 16, 32, 256),              # static: cluster counts
        policies=(("min_search", "threshold"),), # static: SimPolicy axis
        topologies=("ideal", "hier_tree"),       # static: Topology axis
        knobs={"dn_th": (1, 2, 4, 8, 16, 32)},   # traced: knob grid
        workloads=(WorkloadSpec("interference", seeds=(1, 2)),),
        sim_len=4e6)
    frame = spec.run()                           # ResultFrame
    frame.mean_response()                        # (N,) named accessors
    frame.col("k"), frame.col("dn_th")           # aligned coordinates

The **planner** (``spec.plan()``) partitions the point set into
*static-combo groups* — one per distinct ``(SimShape incl. queue_impl,
SimPolicy, Topology)`` — and each group compiles exactly one XLA
program (guarded by ``sweep.cache_size()`` deltas;
tests/test_experiment.py).  Everything inside a group (knob configs,
seeds, workload scenarios) rides the traced/vmap axes for free.

**Dispatch** executes each group with one of three strategies, all
bitwise identical (they run the very same traced computation):

  seq    warm replays of the single-config program, one compile per
         group — the CPU path (per-lane wall-clock recorded).
  vmap   one batched XLA program per group — the accelerator path.
  pmap   groups round-robined over devices via committed inputs, the
         whole frontier dispatched asynchronously and gathered once —
         the multi-device path (closes the ROADMAP "policy/topology
         axes on accelerator sweeps" item).  Falls back to seq/vmap
         when ``jax.device_count() == 1``.

The **faults axis** (DESIGN.md §13) rides alongside the workload axis:
``faults=(None, FaultSpec.poisson_links(seed=0), ...)`` crosses every
static combo with each fault scenario.  Fault schedules are *traced*
pytrees — within a spec they are padded to one common length per
cluster count, so a whole grid of fault seeds/intensities adds at most
one extra compilation per group (the fault-aware program; a bare
``None`` entry keeps the legacy no-fault program).  Each scenario
becomes a ``fault`` coordinate column plus ``msgs_lost`` / ``reroutes``
/ ``downtime`` metric columns (zero-filled for no-fault groups).

The returned :class:`ResultFrame` is columnar — every coordinate
(static axis value, knob value, workload lane, fault scenario) and
every metric is a flat aligned column over all points — and serializes
directly to the benchmarks' results-JSON schema v5 with the spec
embedded as provenance (``frame.to_payload()``; benchmarks/README.md).

Bitwise contract with the legacy entry points: a group executes through
the very same jitted programs ``sweep`` uses (``sim._run`` in seq mode,
``sweep._sweep`` in vmap/pmap mode) with identically-constructed
inputs, so every frozen golden (the PR-2 grid, the fig3b spot sha, the
tree==linear claims) reproduces bitwise through ``ExperimentSpec.run()``
(tests/test_experiment.py), and ``sweep_policies``/``sweep_topologies``
survive as thin deprecated shims over this module.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults as FLT
from repro.core import metrics as M
from repro.core import trace as TR
from repro.core import workloads as W
from repro.core.eventq import QUEUE_IMPLS
from repro.core.policies import SimPolicy
from repro.core.sim import SimKnobs, SimParams, SimShape, _run
from repro.core.transport import Topology

__all__ = ["WorkloadSpec", "ExperimentSpec", "ExperimentPlan", "StaticCombo",
           "ResultFrame", "spec_from_dict", "SPEC_VERSION"]

# v3 adds the trace axis (core/trace.py, DESIGN.md §14): an optional
# spec-level TraceSpec serialized under "trace", plus the percentile /
# evq_peak / trace_dropped ResultFrame columns and the run manifest.
# v4 adds the failure-detector / retry surface (DESIGN.md §15): the
# susp_mult and retry_after knob axes, the avoid_suspected /
# suspect_weighted mapping policies, and the susp_onsets / susp_clears /
# susp_false_pos / suspected_final / retries_tx ResultFrame columns.
# Older payloads load unchanged: absent knob keys fall back to the
# SimKnobs defaults (susp_mult=3.0, retry_after=0.0 — detector telemetry
# on, behavior identical).
SPEC_VERSION = 4
MODES = ("auto", "seq", "vmap", "pmap")
WORKLOAD_KINDS = ("interference", "bursty", "hotspot", "independent", "raw")

KNOB_FIELDS = SimKnobs._fields          # (c_b, c_s, c_join, dn_th, T_b,
                                        #  c_hop, susp_mult, retry_after)


# --------------------------------------------------------------------------
# Workload axis
# --------------------------------------------------------------------------

@dataclass(frozen=True, eq=False)
class WorkloadSpec:
    """One traced workload/scenario axis entry, declaratively.

    A spec is *regenerated per shape* (arrival GMNs depend on k, array
    sizes on max_apps/n_childs), which is what the benchmarks always did
    by hand; the generator params are recorded so the spec serializes as
    provenance.  ``kind="raw"`` wraps pre-built ``(arrivals (S, A),
    gmns (S, A), lengths (S, A, n))`` arrays for the legacy shims — raw
    arrays are shape-locked and serialize as shapes + sha256 only.
    """
    kind: str = "interference"
    seeds: tuple = (0,)
    params: tuple = ()                  # sorted (name, value) pairs
    arrays: tuple | None = None         # kind="raw" only

    def __post_init__(self):
        if self.kind not in WORKLOAD_KINDS:
            raise ValueError(f"unknown workload kind {self.kind!r}; "
                             f"choose from {WORKLOAD_KINDS}")
        object.__setattr__(self, "seeds", tuple(int(s) for s in self.seeds))
        params = self.params
        if isinstance(params, dict):
            params = tuple(sorted(params.items()))
        object.__setattr__(self, "params", tuple(
            (str(k), tuple(v) if isinstance(v, (list, tuple)) else v)
            for k, v in params))

    @classmethod
    def make(cls, kind: str = "interference", seeds=(0,), **params):
        return cls(kind=kind, seeds=seeds, params=tuple(sorted(params.items())))

    @classmethod
    def raw(cls, workload) -> "WorkloadSpec":
        arr, gmns, lens = (np.asarray(a) for a in workload)
        if arr.ndim != 2 or lens.ndim != 3:
            raise ValueError("raw workload needs a leading lane axis (S,): "
                             "arrivals (S, A), gmns (S, A), lengths (S, A, n)")
        return cls(kind="raw", seeds=(), arrays=(arr, gmns, lens))

    @property
    def param_dict(self) -> dict:
        return dict(self.params)

    def lane_count(self) -> int:
        """Number of S lanes this spec expands to (known without building)."""
        if self.kind == "raw":
            return int(self.arrays[0].shape[0])
        pps = self.param_dict.get("pair_periods")
        if self.kind == "interference" and pps is not None:
            return len(pps) * len(self.seeds)
        return len(self.seeds)

    def build(self, shape: SimShape, sim_len: float):
        """Materialize ``(lanes, (arrivals, gmns, lengths))`` for one
        static shape.  ``lanes`` is per-S metadata (seed, pair_period)
        that becomes ResultFrame coordinate columns."""
        prm = self.param_dict
        if self.kind == "raw":
            lanes = [{"workload": "raw", "seed": None, "pair_period": None}
                     for _ in range(self.arrays[0].shape[0])]
            return lanes, self.arrays
        if self.kind == "interference":
            pps = prm.pop("pair_periods", None)
            if pps is not None:
                wl = W.interference_grid(shape, pair_periods=pps,
                                         seeds=self.seeds, sim_len=sim_len,
                                         **prm)
                lanes = [{"workload": self.kind, "seed": s,
                          "pair_period": float(pp)}
                         for pp in pps for s in self.seeds]
            else:
                wl = W.interference_batch(shape, seeds=self.seeds,
                                          sim_len=sim_len, **prm)
                pp = prm.get("pair_period")
                if pp is None:
                    pp = W.DEFAULT_PAIR_PERIOD
                lanes = [{"workload": self.kind, "seed": s,
                          "pair_period": float(pp)} for s in self.seeds]
            return lanes, wl
        if self.kind == "bursty":
            wl = W.bursty_batch(shape, seeds=self.seeds, sim_len=sim_len,
                                **prm)
        elif self.kind == "hotspot":
            wl = W.hotspot_batch(shape, seeds=self.seeds, sim_len=sim_len,
                                 **prm)
        else:                                           # independent
            wl = W.independent_batch(shape, seeds=self.seeds, **prm)
        lanes = [{"workload": self.kind, "seed": s, "pair_period": None}
                 for s in self.seeds]
        return lanes, wl

    def to_dict(self) -> dict:
        d = {"kind": self.kind, "seeds": list(self.seeds),
             "params": {k: (list(v) if isinstance(v, tuple) else v)
                        for k, v in self.params}}
        if self.arrays is not None:
            h = hashlib.sha256()
            for a in self.arrays:
                h.update(np.ascontiguousarray(a).tobytes())
            d["raw"] = {"shapes": [list(a.shape) for a in self.arrays],
                        "sha256": h.hexdigest()}
        return d


# --------------------------------------------------------------------------
# Planner
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class StaticCombo:
    """One static-combo group: exactly one XLA program compiles per
    distinct value (``queue_impl`` is folded into ``shape``)."""
    shape: SimShape
    policy: SimPolicy
    topology: Topology

    def coords(self) -> dict:
        return {"m": self.shape.m, "k": self.shape.k,
                "n_childs": self.shape.n_childs,
                "queue_cap": self.shape.queue_cap,
                "max_apps": self.shape.max_apps,
                "queue_impl": self.shape.queue_impl,
                "batch_pop": self.shape.batch_pop,
                "mapping": self.policy.mapping,
                "beacon": self.policy.beacon,
                "topology": self.topology.kind}


@dataclass(frozen=True)
class ExperimentPlan:
    """The compile-aware partition of a spec's point set.

    ``combos`` is the minimal static-combo grouping: the Cartesian
    product of the spec's static axes, deduplicated order-preservingly —
    no two groups share a ``(shape, policy, topology)`` value, so the
    number of XLA compilations is exactly :meth:`expected_programs`
    on a fresh cache (DESIGN.md §12).
    """
    spec: "ExperimentSpec"
    combos: tuple

    @property
    def n_groups(self) -> int:
        return len(self.combos)

    def resolve_mode(self, mode: str | None = None) -> str:
        """Dispatch matrix (DESIGN.md §12): auto picks seq on CPU and
        vmap on accelerators; pmap needs >1 device and falls back to the
        auto choice cleanly on single-device backends."""
        mode = mode or self.spec.mode
        if mode not in MODES:
            raise ValueError(f"unknown mode {mode!r}; choose from {MODES}")
        if mode == "pmap" and jax.device_count() <= 1:
            mode = "auto"
        if mode == "auto":
            mode = "seq" if jax.default_backend() == "cpu" else "vmap"
        return mode

    def expected_programs(self, mode: str | None = None) -> int:
        """XLA programs a fresh cache compiles executing this plan:
        one per group in seq mode; in vmap/pmap mode the batched program
        is additionally specialized on the lane count S, so scenarios
        with distinct lane counts each compile once per group.  The
        faults axis contributes at most a factor of two per group — one
        no-fault program (``None`` entries) and one fault-aware program
        shared by every FaultSpec (schedules are padded to one common
        length per k, so fault-schedule grids never recompile)."""
        mode = self.resolve_mode(mode)
        fault_programs = len({f is None for f in self.spec.faults})
        if mode == "seq":
            return self.n_groups * fault_programs
        lane_shapes = {w.lane_count() for w in self.spec.workloads}
        return self.n_groups * len(lane_shapes) * fault_programs


# --------------------------------------------------------------------------
# The spec
# --------------------------------------------------------------------------

@dataclass(frozen=True, eq=False)
class ExperimentSpec:
    """One declarative object for every design-space axis.

    Static axes (each value = its own XLA program; the planner groups
    by them):

      shapes       SimShape values; also accepts SimParams (its .shape)
                   or a bare int k (``base``'s shape with k replaced).
                   None -> (base.shape,).
      policies     SimPolicy values or (mapping, beacon) tuples.
                   None -> (base.policy,).
      topologies   Topology values or kind strings.  None -> (base.topo,).
      queue_impls  event-queue structures crossed with ``shapes``
                   (folded into each group's SimShape).  None keeps each
                   shape's own ``queue_impl``.
      batch_pops   same-timestamp BEACON_RX batch windows crossed with
                   ``shapes`` (folded into each group's SimShape, like
                   ``queue_impls``).  Bitwise identical to batch_pop=1 —
                   a wall-clock axis only.  None keeps each shape's own
                   ``batch_pop``.

    Traced axes (ride inside each group's compiled program):

      knobs        SimKnobs with a leading (B,) axis, or a dict of knob
                   axes expanded Cartesian-product style
                   (``{"dn_th": (1, 2, 4), "c_s": (8.0,)}``).
                   None -> one config from ``base``.
      workloads    WorkloadSpec tuple — the scenario/seed axis.
      faults       fault-scenario axis (DESIGN.md §13): a tuple of
                   ``None`` (legacy no-fault program) and/or
                   :class:`repro.core.faults.FaultSpec` values, crossed
                   with every group.  Schedules are traced and padded to
                   a common length per k, so the whole axis costs at
                   most one extra program per group.  Default (None,).

    Observability (DESIGN.md §14):

      trace        optional :class:`repro.core.trace.TraceSpec` applied
                   to EVERY group (spec-level, not crossed — it is a
                   static axis, so crossing it would multiply programs).
                   None (default) runs the uninstrumented programs,
                   bitwise the pre-trace behavior; a spec adds the ring/
                   timeline/histogram leaves, the percentile columns and
                   ``ResultFrame.trace_frame``.  A fixed spec never
                   recompiles across knob/seed/fault grids.

    ``run()`` plans, dispatches and returns a :class:`ResultFrame`.
    """
    base: SimParams = SimParams()
    shapes: tuple | None = None
    policies: tuple | None = None
    topologies: tuple | None = None
    queue_impls: tuple | None = None
    batch_pops: tuple | None = None
    knobs: object = None
    workloads: tuple = (WorkloadSpec(),)
    faults: tuple = (None,)
    trace: object = None
    sim_len: float = 1e7
    mode: str = "auto"

    def __post_init__(self):
        base = self.base
        set_ = lambda k, v: object.__setattr__(self, k, v)

        shapes = self.shapes if self.shapes is not None else (base.shape,)
        set_("shapes", tuple(
            dataclasses.replace(base.shape, k=int(s))
            if isinstance(s, (int, np.integer))
            else s.shape if isinstance(s, SimParams) else s
            for s in _as_tuple(shapes)))

        pols = self.policies if self.policies is not None else (base.policy,)
        set_("policies", tuple(
            p if isinstance(p, SimPolicy) else SimPolicy(*p)
            for p in _as_tuple(pols)))

        topos = self.topologies if self.topologies is not None \
            else (base.topo,)
        set_("topologies", tuple(
            Topology(t) if isinstance(t, str) else t
            for t in _as_tuple(topos)))

        if self.queue_impls is not None:
            qis = tuple(_as_tuple(self.queue_impls))
            for qi in qis:
                if qi not in QUEUE_IMPLS:
                    raise ValueError(f"unknown queue_impl {qi!r}; "
                                     f"choose from {QUEUE_IMPLS}")
            set_("queue_impls", qis)

        if self.batch_pops is not None:
            bps = tuple(int(b) for b in _as_tuple(self.batch_pops))
            for b in bps:
                if b < 1:
                    raise ValueError(f"batch_pop {b} must be >= 1 "
                                     "(queue_cap bound checked per shape)")
            set_("batch_pops", bps)

        knobs = self.knobs
        if knobs is None:
            knobs = {}
        if isinstance(knobs, dict):
            defaults = {f: getattr(base, f) for f in KNOB_FIELDS}
            unknown = set(knobs) - set(KNOB_FIELDS)
            if unknown:
                raise ValueError(f"unknown knob axes {sorted(unknown)}; "
                                 f"choose from {KNOB_FIELDS}")
            from repro.core import sweep as SW
            knobs = SW.knob_product(**{
                f: np.atleast_1d(knobs.get(f, defaults[f]))
                for f in KNOB_FIELDS})
        if knobs.dn_th.ndim != 1:
            raise ValueError("knobs need a leading batch axis (B,); "
                             "pass a dict of axes or knob_batch/knob_product")
        set_("knobs", knobs)

        wls = self.workloads
        if isinstance(wls, WorkloadSpec):
            wls = (wls,)
        set_("workloads", tuple(wls))
        if not self.workloads:
            raise ValueError("need at least one WorkloadSpec")

        flts = self.faults
        if flts is None or isinstance(flts, FLT.FaultSpec):
            flts = (flts,)
        flts = tuple(flts)
        for f in flts:
            if f is not None and not isinstance(f, FLT.FaultSpec):
                raise TypeError(f"faults entries must be None or FaultSpec, "
                                f"got {type(f).__name__}")
        if not flts:
            raise ValueError("faults needs at least one entry "
                             "(use (None,) for no faults)")
        set_("faults", flts)
        tr = self.trace
        if isinstance(tr, dict):
            tr = TR.TraceSpec.from_dict(tr)
        if tr is not None and not isinstance(tr, TR.TraceSpec):
            raise TypeError(f"trace must be None or a TraceSpec, "
                            f"got {type(tr).__name__}")
        set_("trace", tr)
        set_("sim_len", float(self.sim_len))
        if self.mode not in MODES:
            raise ValueError(f"unknown mode {self.mode!r}; "
                             f"choose from {MODES}")

    # -- planner ----------------------------------------------------------

    def plan(self) -> ExperimentPlan:
        combos = []
        for shape in self.shapes:
            qis = self.queue_impls or (shape.queue_impl,)
            for qi in qis:
                bps = self.batch_pops or (shape.batch_pop,)
                for bp in bps:
                    sh = shape
                    if (sh.queue_impl, sh.batch_pop) != (qi, bp):
                        sh = dataclasses.replace(sh, queue_impl=qi,
                                                 batch_pop=bp)
                    for pol in self.policies:
                        for topo in self.topologies:
                            combos.append(StaticCombo(sh, pol, topo))
        return ExperimentPlan(self, tuple(dict.fromkeys(combos)))

    # -- execution --------------------------------------------------------

    def run(self, mode: str | None = None) -> "ResultFrame":
        from repro.core import sweep as SW
        plan = self.plan()
        requested = mode or self.mode
        resolved = plan.resolve_mode(requested)
        compiles0 = SW.cache_size()
        sl = jnp.float32(self.sim_len)
        wl_cache = {}
        f_cache = {}

        def built(combo, wi):
            key = (wi, combo.shape.m, combo.shape.k, combo.shape.max_apps,
                   combo.shape.n_childs)
            if key not in wl_cache:
                lanes, wl = self.workloads[wi].build(combo.shape,
                                                     self.sim_len)
                wl_cache[key] = (lanes, (
                    jnp.asarray(wl[0], jnp.float32),
                    jnp.asarray(wl[1], jnp.int32),
                    jnp.asarray(wl[2], jnp.float32)))
            return wl_cache[key]

        def scheds(k):
            # one build per (fault entry, k), padded to the axis-wide
            # common length so every FaultSpec shares one program per
            # group (expected_programs' no-recompile contract)
            if k not in f_cache:
                built_ = [None if f is None else f.build(k, self.sim_len)
                          for f in self.faults]
                cap = max((s.capacity for s in built_ if s is not None),
                          default=0)
                f_cache[k] = [None if s is None else FLT.pad_to(s, cap)
                              for s in built_]
            return f_cache[k]

        t0 = time.perf_counter()
        groups = []
        if resolved == "pmap":
            # groups interleave here (all dispatched, then all gathered),
            # so each step has its span and no group span encloses them
            devs = jax.devices()
            pending = []
            for gi, combo in enumerate(plan.combos):
                dev = devs[gi % len(devs)]
                for wi in range(len(self.workloads)):
                    for fi, f in enumerate(self.faults):
                        with _Span("experiment.build"):
                            lanes, (arr, gmns, lens) = built(combo, wi)
                            kn, ar, gm, ln, sl_d, fs = jax.device_put(
                                (self.knobs, arr, gmns, lens, sl,
                                 scheds(combo.shape.k)[fi]), dev)
                        with _Span("experiment.dispatch"):
                            out = SW._sweep(combo.shape, kn, ar, gm, ln,
                                            sl_d, combo.policy,
                                            combo.topology, fs, self.trace)
                        pending.append((combo, wi, f, lanes, lens, out))
            for combo, wi, f, lanes, lens, out in pending:
                dev = _device_of(out)
                with _Span("experiment.execute"):
                    out = jax.block_until_ready(out)
                with _Span("experiment.fetch"):
                    st = jax.tree.map(np.asarray, out)
                groups.append(_GroupResult(combo, wi, lanes, st,
                                           np.asarray(lens), np.nan, None,
                                           f, dev))
        else:
            for combo in plan.combos:
                for wi in range(len(self.workloads)):
                    for fi, f in enumerate(self.faults):
                        with _Span("experiment.group"):
                            with _Span("experiment.build"):
                                lanes, (arr, gmns, lens) = built(combo, wi)
                                fs = scheds(combo.shape.k)[fi]
                            if resolved == "vmap":
                                st, lane_walls, dev, wall = _exec_vmap(
                                    combo, self.knobs, arr, gmns, lens, sl,
                                    fs, self.trace)
                            else:
                                st, lane_walls, dev, wall = _exec_seq(
                                    combo, self.knobs, arr, gmns, lens, sl,
                                    fs, self.trace)
                        groups.append(_GroupResult(combo, wi, lanes, st,
                                                   np.asarray(lens), wall,
                                                   lane_walls, f, dev))
        wall = time.perf_counter() - t0
        return ResultFrame(self, plan, requested, resolved, groups, wall,
                           SW.cache_size() - compiles0)

    # -- provenance -------------------------------------------------------

    def to_dict(self) -> dict:
        return {
            "version": SPEC_VERSION,
            "base": dataclasses.asdict(self.base),
            "shapes": [dataclasses.asdict(s) for s in self.shapes],
            "policies": [{"mapping": p.mapping, "beacon": p.beacon}
                         for p in self.policies],
            "topologies": [t.kind for t in self.topologies],
            "queue_impls": list(self.queue_impls) if self.queue_impls
            else None,
            "batch_pops": list(self.batch_pops) if self.batch_pops
            else None,
            "knobs": {f: np.asarray(getattr(self.knobs, f)).tolist()
                      for f in KNOB_FIELDS},
            "workloads": [w.to_dict() for w in self.workloads],
            "faults": [None if f is None else f.to_dict()
                       for f in self.faults],
            "trace": None if self.trace is None else self.trace.to_dict(),
            "sim_len": float(self.sim_len),
            "mode": self.mode,
        }


def _as_tuple(v):
    return (v,) if not isinstance(v, (tuple, list)) else tuple(v)


_SPEC_FIELDS = ("version", "base", "shapes", "policies", "topologies",
                "queue_impls", "batch_pops", "knobs", "workloads",
                "faults", "trace", "sim_len", "mode")


def spec_from_dict(d: dict) -> ExperimentSpec:
    """Reconstruct an ExperimentSpec from its ``to_dict()`` payload (the
    provenance round-trip; raw workloads carry only shapes + sha256 and
    cannot be reconstructed).

    Strict: a payload field this reader does not know is an error, not
    a silent drop — a spec written by a newer schema (say a v5 payload
    with an axis this version cannot replay) must fail loudly instead of
    reconstructing a spec that silently runs *different* experiments
    than the payload records (tests/test_experiment.py)."""
    from repro.core import sweep as SW
    unknown = set(d) - set(_SPEC_FIELDS)
    if unknown:
        raise ValueError(
            f"unknown ExperimentSpec fields {sorted(unknown)}; this reader "
            f"(SPEC_VERSION={SPEC_VERSION}) supports {sorted(_SPEC_FIELDS)} "
            "— the payload was likely written by a newer schema and cannot "
            "be replayed faithfully")
    version = int(d.get("version", 1))
    if version > SPEC_VERSION:
        raise ValueError(f"payload has spec version {version}, this reader "
                         f"supports <= {SPEC_VERSION}")
    for w in d["workloads"]:
        if w["kind"] == "raw":
            raise ValueError("raw workloads serialize as provenance only "
                             "and cannot be reconstructed")
    return ExperimentSpec(
        base=SimParams(**d["base"]),
        shapes=tuple(SimShape(**s) for s in d["shapes"]),
        policies=tuple(SimPolicy(**p) for p in d["policies"]),
        topologies=tuple(d["topologies"]),
        queue_impls=tuple(d["queue_impls"]) if d.get("queue_impls")
        else None,
        batch_pops=tuple(d["batch_pops"]) if d.get("batch_pops")
        else None,
        knobs=SW.knob_batch(**{f: tuple(v) if len(v) > 1 else v[0]
                               for f, v in d["knobs"].items()}),
        workloads=tuple(
            WorkloadSpec(kind=w["kind"], seeds=tuple(w["seeds"]),
                         params=tuple(sorted(
                             (k, tuple(v) if isinstance(v, list) else v)
                             for k, v in w["params"].items())))
            for w in d["workloads"]),
        faults=tuple(None if f is None else FLT.FaultSpec.from_dict(f)
                     for f in d.get("faults", [None])),
        trace=d.get("trace"),   # dict → TraceSpec in __post_init__
        sim_len=d["sim_len"],
        mode=d["mode"])


class _Span:
    """One step of a run, timed twice on one path: a
    ``jax.profiler.TraceAnnotation`` (recorded only while the profiler
    traces, on its clock) and ``time.perf_counter`` (``t0``/``t1``, the
    clock of every wall time a ResultFrame reports)."""

    def __init__(self, name: str):
        self._annotation = jax.profiler.TraceAnnotation(name)
        self.t0 = self.t1 = float("nan")

    def __enter__(self) -> "_Span":
        self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        return self._annotation.__exit__(*exc)


def _exec_vmap(combo: StaticCombo, knobs: SimKnobs, arr, gmns, lens, sl,
               faults=None, trace=None):
    """One dispatch of the group's batched program.  Returns the state
    on the host, no lane walls, the device, and the wall seconds from
    dispatch to the state's arrival on the host."""
    from repro.core import sweep as SW
    with _Span("experiment.dispatch") as dispatch:
        out = SW._sweep(combo.shape, knobs, arr, gmns, lens, sl,
                        combo.policy, combo.topology, faults, trace)
    dev = _device_of(out)
    with _Span("experiment.execute"):
        out = jax.block_until_ready(out)
    with _Span("experiment.fetch") as fetch:
        st = jax.tree.map(np.asarray, out)
    return st, None, dev, fetch.t1 - dispatch.t0


def _exec_seq(combo: StaticCombo, knobs: SimKnobs, arr, gmns, lens, sl,
              faults=None, trace=None):
    """Warm replays of the single-config program — the identical
    ``sim._run`` calls and (B, S)-stacking ``sweep(mode="seq")`` performs,
    with per-lane wall-clock recorded (lane 0 of a fresh group carries
    the XLA compile).  Also returns the device the lanes ran on and the
    wall seconds from the first dispatch to the stacked state."""
    b, s = knobs.dn_th.shape[0], arr.shape[0]
    outs, lane_walls, t_start = [], [], None
    for i in range(b):
        for j in range(s):
            with _Span("experiment.dispatch") as dispatch:
                out = _run(combo.shape,
                           SimKnobs(*(leaf[i] for leaf in knobs)), arr[j],
                           gmns[j], lens[j], sl, combo.policy,
                           combo.topology, faults, trace)
            with _Span("experiment.execute") as execute:
                out = jax.block_until_ready(out)
            lane_walls.append(execute.t1 - dispatch.t0)
            t_start = dispatch.t0 if t_start is None else t_start
            outs.append(out)
    dev = _device_of(outs[0])
    with _Span("experiment.fetch") as fetch:
        st = jax.tree.map(
            lambda *leaves: np.stack(leaves).reshape((b, s)
                                                     + leaves[0].shape),
            *[jax.tree.map(np.asarray, o) for o in outs])
    return st, lane_walls, dev, fetch.t1 - t_start


def _device_of(out) -> str:
    """The one device a group's output lives on (read before the leaves
    are copied to the host, where the placement is lost)."""
    (dev,) = jax.tree.leaves(out)[0].devices()
    return str(dev)


# --------------------------------------------------------------------------
# Columnar results
# --------------------------------------------------------------------------

def _opt_leaf(st: dict, name: str, dtype) -> np.ndarray:
    """A (B, S) scalar state leaf, or zeros of the right shape when the
    group's program did not record it (no-fault groups lack the fault
    counters)."""
    v = st.get(name)
    if v is None:
        v = np.zeros(np.asarray(st["dropped"]).shape)
    return np.asarray(v).astype(dtype)


def _sum_pairs(st: dict, name: str) -> np.ndarray:
    """Reduce a per-pair (B, S, k, k) detector matrix (DESIGN.md §15) to
    per-lane totals; zeros when the group ran the no-fault program."""
    v = st.get(name)
    if v is None:
        return np.zeros(np.asarray(st["dropped"]).shape, np.int64)
    v = np.asarray(v)
    return v.reshape(v.shape[:-2] + (-1,)).sum(axis=-1).astype(np.int64)

@dataclass
class _GroupResult:
    combo: StaticCombo
    workload_index: int
    lanes: list                         # per-S metadata dicts
    state: dict                         # np leaves, (B, S, ...)
    lengths: np.ndarray                 # (S, A, n)
    wall_s: float
    lane_wall_s: list | None            # B*S entries (seq mode) or None
    fault: object = None                # FaultSpec or None (no-fault)
    device: str | None = None           # device the group's program ran on

    @property
    def fault_label(self) -> str:
        return self.fault.label if self.fault is not None else "none"


class ResultFrame:
    """Columnar result set: one row per (group x knob-config x lane)
    point, flat aligned columns for every coordinate and metric.

    Point order is group-major (plan order), then workload-spec order,
    then fault-scenario order, then knob-config-major / lane-minor —
    i.e. each group's ``(B, S)`` state leaves flattened C-style,
    matching ``sweep``'s axis contract.
    """

    _METRICS = {
        "mean_response": M.mean_response,
        "beacons_tx": M.beacons,
        "beacons_rx": M.beacons_rx,
        "mgmt_msgs": M.mgmt_msgs,
        "mgmt_latency": M.mgmt_latency,
        "mgmt_proc": M.mgmt_proc,
        "dropped": lambda st: np.asarray(st["dropped"]).astype(np.int64),
        "events": lambda st:
            np.asarray(st["events_processed"]).astype(np.int64),
        "bcn_skew_sum": lambda st: np.asarray(st["bcn_skew_sum"],
                                              np.float64),
        "bcn_skew_max": lambda st: np.asarray(st["bcn_skew_max"],
                                              np.float64),
        # availability counters (DESIGN.md §13) — zero-filled when the
        # group ran the legacy no-fault program and the leaves are absent
        "msgs_lost": lambda st: _opt_leaf(st, "msgs_lost", np.int64),
        "reroutes": lambda st: _opt_leaf(st, "reroutes", np.int64),
        "downtime": lambda st: _opt_leaf(st, "downtime", np.float64),
        # observability counters (DESIGN.md §14); evq_peak is always-on,
        # trace_dropped only exists when the group ran with a TraceSpec
        "evq_peak": lambda st: _opt_leaf(st, "evq_peak", np.int64),
        "trace_dropped": lambda st: _opt_leaf(st, "trace_dropped",
                                              np.int64),
        # failure-detector / retry counters (DESIGN.md §15): per-pair
        # onset/clear matrices reduce to per-lane totals; suspected_final
        # counts pairs still suspected at the end of the run.  All
        # zero-filled for no-fault groups.
        "susp_onsets": lambda st: _sum_pairs(st, "susp_onsets"),
        "susp_clears": lambda st: _sum_pairs(st, "susp_clears"),
        "susp_false_pos": lambda st: _opt_leaf(st, "susp_false_pos",
                                               np.int64),
        "suspected_final": lambda st: _sum_pairs(st, "suspect"),
        "retries_tx": lambda st: _opt_leaf(st, "retries_tx", np.int64),
    }
    # histogram-derived percentile columns (NaN when trace is off):
    # (column, state leaf, quantile)
    _PCT_COLS = (
        ("p50_mgmt_latency", "th_mgmt", 0.50),
        ("p95_mgmt_latency", "th_mgmt", 0.95),
        ("p99_mgmt_latency", "th_mgmt", 0.99),
        ("p50_response", "th_resp", 0.50),
        ("p95_response", "th_resp", 0.95),
        ("p99_response", "th_resp", 0.99),
    )
    COORDS = ("m", "k", "n_childs", "queue_cap", "max_apps", "queue_impl",
              "batch_pop", "mapping", "beacon", "topology", "fault")
    LANE_COORDS = ("workload", "seed", "pair_period")

    def __init__(self, spec, plan, mode_requested, mode, groups, wall_s,
                 compiles):
        self.spec = spec
        self.plan = plan
        self.mode_requested = mode_requested
        self.mode = mode
        self.groups = groups
        self.wall_s = wall_s
        self.compiles = compiles
        self.expected_programs = plan.expected_programs(mode)
        self._cols = None

    def __len__(self):
        b = self.spec.knobs.dn_th.shape[0]
        return sum(b * len(g.lanes) for g in self.groups)

    # -- columns ----------------------------------------------------------

    def _columns(self) -> dict:
        if self._cols is not None:
            return self._cols
        pct_names = tuple(c for c, _, _ in self._PCT_COLS)
        cols = {name: [] for name in
                self.COORDS + self.LANE_COORDS + KNOB_FIELDS
                + tuple(self._METRICS) + pct_names
                + ("speedup", "lane_wall_s")}
        b = self.spec.knobs.dn_th.shape[0]
        knob_rows = {f: np.asarray(getattr(self.spec.knobs, f))
                     for f in KNOB_FIELDS}
        for g in self.groups:
            s = len(g.lanes)
            n = b * s
            met = {name: np.asarray(fn(g.state)).reshape(n)
                   for name, fn in self._METRICS.items()}
            for cname, leaf, q in self._PCT_COLS:
                h = g.state.get(leaf)
                if h is None or self.spec.trace is None:
                    met[cname] = np.full((n,), np.nan)
                else:
                    met[cname] = np.asarray(TR.hist_percentile(
                        h, q, self.spec.trace)).reshape(n)
            met["speedup"] = np.asarray(
                M.speedup(g.state, g.lengths)).reshape(n)
            met["lane_wall_s"] = (np.asarray(g.lane_wall_s)
                                  if g.lane_wall_s is not None
                                  else np.full((n,), np.nan))
            coords = dict(g.combo.coords(), fault=g.fault_label)
            for i in range(b):
                for j in range(s):
                    for c in self.COORDS:
                        cols[c].append(coords[c])
                    lane = g.lanes[j]
                    for c in self.LANE_COORDS:
                        cols[c].append(lane.get(c))
                    for f in KNOB_FIELDS:
                        cols[f].append(knob_rows[f][i].item())
            for name in (tuple(self._METRICS) + pct_names
                         + ("speedup", "lane_wall_s")):
                cols[name].extend(met[name].tolist())
        self._cols = {k: np.asarray(v) for k, v in cols.items()}
        return self._cols

    def col(self, name: str) -> np.ndarray:
        """Flat (N,) column aligned across coordinates and metrics."""
        cols = self._columns()
        if name not in cols:
            raise KeyError(f"unknown column {name!r}; available: "
                           f"{sorted(cols)}")
        return cols[name]

    def mask(self, **sel) -> np.ndarray:
        """Boolean point mask, e.g. ``frame.mask(k=16, topology="ideal")``.

        Knob coordinates are stored at the simulator's float32 precision,
        so float selectors on knob columns are rounded through float32
        before comparing — ``frame.mask(c_s=0.1)`` matches the lane that
        actually ran with ``float32(0.1)``."""
        m = np.ones((len(self),), bool)
        for k, v in sel.items():
            if k in KNOB_FIELDS and isinstance(v, float):
                v = np.float32(v).item()
            m &= self.col(k) == v
        return m

    # -- named metric accessors (generated below the class: one per
    # metric column — mean_response, speedup, beacons_tx, beacons_rx,
    # mgmt_msgs, mgmt_latency, mgmt_proc, dropped, events, bcn_skew_*) --

    def metric(self, name: str, **sel) -> np.ndarray:
        """The (N,) metric column ``name``, optionally filtered by
        coordinate selectors: ``frame.metric("speedup", k=16)``."""
        col = self.col(name)
        return col[self.mask(**sel)] if sel else col

    # -- raw state access (bitwise golden gates) --------------------------

    def state(self, workload_index: int = 0, **sel) -> dict:
        """The raw (B, S, ...) final-state dict of exactly one group —
        select by static coordinates (``k=16, topology="hier_tree",
        mapping="round_robin", queue_impl="tree", fault="none"``...;
        ``fault`` matches the scenario label).  This is the
        bitwise surface: leaves are the very arrays the group's jitted
        program returned."""
        hits = [g for g in self.groups
                if g.workload_index == workload_index
                and all(dict(g.combo.coords(),
                             fault=g.fault_label).get(k) == v
                        for k, v in sel.items())]
        if len(hits) != 1:
            raise KeyError(f"state selector {sel} (workload_index="
                           f"{workload_index}) matched {len(hits)} groups, "
                           "need exactly 1")
        return hits[0].state

    def trace_frame(self, workload_index: int = 0, knob: int = 0,
                    lane: int = 0, **sel) -> "TR.TraceFrame":
        """Decode one lane's trace buffers into a
        :class:`repro.core.trace.TraceFrame` (events, timelines,
        percentiles, Perfetto export).  ``sel`` picks the group exactly
        like :meth:`state`; ``knob``/``lane`` index its (B, S) axes."""
        if self.spec.trace is None:
            raise ValueError("spec ran with trace=None — no trace buffers "
                             "were recorded (set ExperimentSpec.trace)")
        st = self.state(workload_index, **sel)
        sliced = {k: np.asarray(v)[knob, lane] for k, v in st.items()}
        return TR.TraceFrame(sliced, self.spec.trace)

    # -- run manifest (per-group wall telemetry) --------------------------

    def manifest(self) -> dict:
        """Per-group dispatch telemetry: coordinates, the device the group
        ran on, wall seconds, and (seq mode) a compile/execute split
        estimated from lane walls — lane 0 of a fresh group carries the
        XLA compile, so
        ``compile_s_est = lane0 - median(warm lanes)``."""
        groups = []
        for g in self.groups:
            lw = g.lane_wall_s
            entry = {
                "coords": dict(g.combo.coords(), fault=g.fault_label),
                "workload_index": g.workload_index,
                "n_lanes": len(g.lanes),
                "device": g.device,
                "wall_s": None if np.isnan(g.wall_s) else float(g.wall_s),
                "lane_wall_s": None if lw is None else [float(x)
                                                        for x in lw],
            }
            if lw is not None and len(lw) > 1:
                warm = float(np.median(lw[1:]))
                entry["compile_s_est"] = max(float(lw[0]) - warm, 0.0)
                entry["execute_s_est"] = (float(np.sum(lw))
                                          - entry["compile_s_est"])
            else:
                entry["compile_s_est"] = None
                entry["execute_s_est"] = (None if lw is None
                                          else float(np.sum(lw)))
            groups.append(entry)
        return {
            "mode": self.mode,
            "devices": jax.device_count(),
            "wall_s": self.wall_s,
            "n_compiles": self.compiles,
            "expected_programs": self.expected_programs,
            "trace": (None if self.spec.trace is None
                      else self.spec.trace.to_dict()),
            "groups": groups,
        }

    # -- serialization (schema v4) ----------------------------------------

    def rows(self) -> list:
        """One JSON-ready dict per point (coordinates + knobs + metrics)."""
        cols = self._columns()
        out = []
        for i in range(len(self)):
            row = {}
            for k, v in cols.items():
                v = v[i]
                if isinstance(v, np.generic):
                    v = v.item()
                if isinstance(v, float) and np.isnan(v):
                    v = None
                row[k] = v
            out.append(row)
        return out

    def to_payload(self, **extra) -> dict:
        """The benchmarks' results-JSON schema v4 core: embedded spec
        provenance + planner/dispatch accounting + columnar rows."""
        return {
            "spec": self.spec.to_dict(),
            "experiment": {
                "mode_requested": self.mode_requested,
                "mode": self.mode,
                "n_groups": self.plan.n_groups,
                "n_points": len(self),
                "n_compiles": self.compiles,
                "expected_programs": self.expected_programs,
                "wall_s": self.wall_s,
                "devices": jax.device_count(),
            },
            "rows": self.rows(),
            "manifest": self.manifest(),
            **extra,
        }


def _metric_accessor(name):
    def acc(self, **sel):
        return self.metric(name, **sel)
    acc.__name__ = name
    acc.__qualname__ = f"ResultFrame.{name}"
    acc.__doc__ = (f"Aligned (N,) ``{name}`` column; keyword coordinate "
                   f"selectors filter points (``frame.{name}(k=16)``).")
    return acc


for _name in (tuple(ResultFrame._METRICS)
              + tuple(c for c, _, _ in ResultFrame._PCT_COLS)
              + ("speedup",)):
    setattr(ResultFrame, _name, _metric_accessor(_name))
del _name
