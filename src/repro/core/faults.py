"""Fault injection for the management fabric (DESIGN.md §13).

The paper evaluates the clustered manager on a *static* fabric; a
production run-time must also stay up when links and manager nodes fail
(ROADMAP: "Fault and churn scenarios — dynamic topologies").  This
module makes the fabric mutable under events without adding a single
static axis: faults live in two traced state leaves

  ``link_up``    (k, k) f32 directed link mask, 1 = up
  ``gmn_alive``  (k,)  f32 GMN liveness vector, 1 = alive

mutated by four event types the simulator already knows how to order
(``EV_LINK_DOWN`` / ``EV_LINK_UP`` / ``EV_GMN_FAIL`` / ``EV_GMN_HEAL``,
repro.core.sim).  The *schedule* of fault events is a pytree of traced
arrays (:class:`FaultSchedule`), so a grid of failure seeds or fault
intensities re-uses one compiled XLA program exactly like a knob grid —
only the schedule *length* (a shape) recompiles.

Declarative front-end: a :class:`FaultSpec` names a generator and its
parameters (hashable, like ``WorkloadSpec``) and ``build(k, sim_len)``
expands it host-side into a schedule with NumPy determinism — the same
(spec, k, sim_len) always builds the same schedule, which is what the
chaos tests' bitwise-reproducibility contract rides on.

Generators:

  ``none``           empty schedule — the fault machinery compiled in
                     with zero events.  This is the bitwise no-fault
                     anchor: with every link up and every GMN alive, all
                     fault-aware code paths reduce to exact no-ops and
                     the frozen PR-2/PR-4 goldens reproduce bitwise
                     (tests/test_faults.py).
  ``poisson_links``  seeded Poisson directed-link failures, each
                     repaired after ``repair`` ticks.  Schedule length
                     is the static ``max_events`` bound (padded with
                     INF), so a seed grid never recompiles.
  ``partition``      scheduled fabric partition: every link crossing
                     the cut between the first ``ceil(k * frac)`` GMNs
                     and the rest goes down at ``t_down`` and (unless
                     ``t_heal`` is None) heals at ``t_heal`` —
                     partition-and-heal on any topology.
  ``gmn_churn``      seeded Poisson GMN failures with repair; a failed
                     cluster's pending work re-homes to the live GMN
                     with the least total load (``min_search``
                     takeover, repro.core.sim._takeover).  GMN 0 is
                     never churned — it anchors the hot-spare pool so a
                     live takeover target always exists.
  ``scripted``       explicit (t, kind, a0, a1) event tuples for
                     hand-built chaos scenarios and unit tests.

Semantics of an injected fault (full per-topology discussion in
DESIGN.md §13):

  - beacons are *best-effort*: a beacon injected while the (src, rcv)
    link is down or the receiver is dead is dropped and counted in
    ``msgs_lost``; loss is decided at injection time (in-flight
    messages already left the source and complete).
  - task-start groups and join-exit forwards are *reliable*: a down
    link costs a detour/retransmit penalty (2 extra hops: ``2 * c_hop``
    on mesh2d and torus2d, one extra serialized grant pair ``2 * c_b``
    elsewhere)
    counted in ``reroutes`` — management work is never silently lost,
    so every started application still completes under faults.
  - ``downtime`` accumulates the completed outage durations of links
    and GMNs (accounted at the heal event; outages still open at the
    end of the run are not counted).
  - overlapping failures of the same link/GMN merge (handlers are
    idempotent; the first heal re-raises the resource).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

# fault kinds inside a schedule; the simulator maps them onto event
# types EV_LINK_DOWN..EV_GMN_HEAL = 4..7 (repro.core.sim)
F_LINK_DOWN = 0
F_LINK_UP = 1
F_GMN_FAIL = 2
F_GMN_HEAL = 3

FAULT_EVENT_NAMES = ("link_down", "link_up", "gmn_fail", "gmn_heal")

FAULT_KINDS = ("none", "poisson_links", "partition", "gmn_churn",
               "gmn_outage", "scripted")

_INF = np.float32(1e18)          # the shared queue sentinel (eventq.INF)


class FaultSchedule(NamedTuple):
    """Traced fault schedule: four (F,) leaves, INF-padded.  A pytree —
    swapping schedules of the same length re-uses the compiled program
    (the no-recompile contract the fault_frontier claim gates)."""
    times: jnp.ndarray           # (F,) f32 event times, INF = padding
    kinds: jnp.ndarray           # (F,) i32 F_LINK_DOWN..F_GMN_HEAL
    a0: jnp.ndarray              # (F,) i32 link src / failed GMN
    a1: jnp.ndarray              # (F,) i32 link dst / unused

    @property
    def capacity(self) -> int:
        return int(self.times.shape[0])


def _schedule(events, pad: int) -> FaultSchedule:
    """Build an INF-padded FaultSchedule from (t, kind, a0, a1) tuples.

    ``pad`` must be a deterministic function of the *spec* (never of the
    drawn randomness) so every seed in a grid produces the same shapes.
    """
    events = sorted(events, key=lambda e: (e[0], e[1], e[2], e[3]))
    if len(events) > pad:
        raise ValueError(f"fault schedule needs {len(events)} slots but "
                         f"pad={pad}; raise max_events")
    n = max(pad, len(events))
    times = np.full((n,), _INF, np.float32)
    kinds = np.zeros((n,), np.int32)
    a0 = np.zeros((n,), np.int32)
    a1 = np.zeros((n,), np.int32)
    for i, (t, kind, x, y) in enumerate(events):
        times[i] = t
        kinds[i] = kind
        a0[i] = x
        a1[i] = y
    return FaultSchedule(jnp.asarray(times), jnp.asarray(kinds),
                         jnp.asarray(a0), jnp.asarray(a1))


@dataclass(frozen=True)
class FaultSpec:
    """Declarative, hashable fault scenario (the ``faults`` axis of
    ``ExperimentSpec``).  ``params`` is a sorted tuple of (name, value)
    pairs so equal specs hash equal; use the classmethod constructors."""
    kind: str = "none"
    params: tuple = ()
    seed: int = 0
    name: str = ""               # display label; defaults to kind

    def __post_init__(self):
        if self.kind not in FAULT_KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}; "
                             f"choose from {FAULT_KINDS}")

    # -- constructors -------------------------------------------------

    @classmethod
    def none(cls) -> "FaultSpec":
        """Fault machinery compiled in, zero events (the bitwise
        no-fault anchor)."""
        return cls()

    @classmethod
    def poisson_links(cls, rate: float = 1e-4, repair: float = 20_000.0,
                      seed: int = 0, max_events: int = 32,
                      symmetric: bool = True, name: str = "") -> "FaultSpec":
        """Directed links fail as a Poisson process with ``rate``
        failures per tick fabric-wide; each failed link heals after
        ``repair`` ticks.  ``max_events`` bounds the schedule length
        statically (a seed grid keeps one compiled program)."""
        return cls(kind="poisson_links", seed=int(seed),
                   name=name or "poisson_links",
                   params=(("max_events", int(max_events)),
                           ("rate", float(rate)),
                           ("repair", float(repair)),
                           ("symmetric", bool(symmetric))))

    @classmethod
    def partition(cls, t_down: float, t_heal: float | None = None,
                  frac: float = 0.5, name: str = "") -> "FaultSpec":
        """Cut the fabric in two at ``t_down`` (first ``ceil(k * frac)``
        GMNs vs the rest, both link directions), heal at ``t_heal``."""
        return cls(kind="partition", name=name or "partition",
                   params=(("frac", float(frac)),
                           ("t_down", float(t_down)),
                           ("t_heal",
                            None if t_heal is None else float(t_heal))))

    @classmethod
    def gmn_churn(cls, rate: float = 1e-5, repair: float = 30_000.0,
                  seed: int = 0, max_events: int = 8,
                  name: str = "") -> "FaultSpec":
        """GMNs fail as a Poisson process and heal after ``repair``
        ticks.  GMN 0 never fails (hot-spare anchor), so ``min_search``
        takeover always finds a live manager."""
        return cls(kind="gmn_churn", seed=int(seed),
                   name=name or "gmn_churn",
                   params=(("max_events", int(max_events)),
                           ("rate", float(rate)),
                           ("repair", float(repair))))

    @classmethod
    def gmn_outage(cls, t_down: float, t_heal: float,
                   frac: float = 0.5, name: str = "") -> "FaultSpec":
        """Power-domain outage: the last ``k - ceil(k * frac)`` managers
        (never GMN 0) fail together at ``t_down`` and heal together at
        ``t_heal`` — a partition of the *manager set*, the scenario a
        failure detector exists for.  Contrast :meth:`partition`, which
        cuts links while every manager stays alive: there, reliable
        messages still detour through (DESIGN.md §13) and avoiding the
        unreachable half sacrifices live capacity."""
        return cls(kind="gmn_outage", name=name or "gmn_outage",
                   params=(("frac", float(frac)),
                           ("t_down", float(t_down)),
                           ("t_heal", float(t_heal))))

    @classmethod
    def scripted(cls, events, name: str = "") -> "FaultSpec":
        """Explicit schedule: (t, "link_down"|"link_up"|"gmn_fail"|
        "gmn_heal", a0, a1) tuples."""
        norm = []
        for t, kind, x, y in events:
            if kind not in FAULT_EVENT_NAMES:
                raise ValueError(f"unknown fault event {kind!r}; "
                                 f"choose from {FAULT_EVENT_NAMES}")
            norm.append((float(t), str(kind), int(x), int(y)))
        return cls(kind="scripted", name=name or "scripted",
                   params=(("events", tuple(norm)),))

    # -- expansion ----------------------------------------------------

    @property
    def p(self) -> dict:
        return dict(self.params)

    @property
    def label(self) -> str:
        return self.name or self.kind

    def build(self, k: int, sim_len: float) -> FaultSchedule:
        """Expand into a traced schedule for a k-GMN fabric.
        Deterministic: same (spec, k, sim_len) -> same schedule."""
        d = self.p
        if self.kind == "none":
            return _schedule([], 0)
        if self.kind == "poisson_links":
            return self._poisson_links(k, sim_len, d)
        if self.kind == "partition":
            return self._partition(k, d)
        if self.kind == "gmn_churn":
            return self._gmn_churn(k, sim_len, d)
        if self.kind == "gmn_outage":
            return self._gmn_outage(k, d)
        # scripted
        ev = [(t, FAULT_EVENT_NAMES.index(kind), x, y)
              for t, kind, x, y in d["events"]]
        for t, kind, x, y in ev:
            hi = k if kind >= F_GMN_FAIL else k
            if not (0 <= x < k) or not (0 <= y <= hi):
                raise ValueError(f"fault target ({x}, {y}) out of range "
                                 f"for k={k}")
        return _schedule(ev, len(ev))

    def _poisson_links(self, k, sim_len, d):
        per = 4 if d["symmetric"] else 2
        pad = d["max_events"] * per
        if k < 2 or d["rate"] <= 0:
            return _schedule([], pad)
        rng = np.random.RandomState(self.seed)
        events, t = [], 0.0
        for _ in range(d["max_events"]):
            t += rng.exponential(1.0 / d["rate"])
            if t >= sim_len:
                break
            i = int(rng.randint(k))
            j = int(rng.randint(k - 1))
            j += j >= i                              # j != i
            pairs = [(i, j), (j, i)] if d["symmetric"] else [(i, j)]
            for a, b in pairs:
                events.append((t, F_LINK_DOWN, a, b))
                events.append((t + d["repair"], F_LINK_UP, a, b))
        return _schedule(events, pad)

    def _partition(self, k, d):
        a = max(1, int(np.ceil(k * d["frac"])))
        left = range(min(a, k))
        right = range(min(a, k), k)
        events = []
        for i in left:
            for j in right:
                for s, t_ in ((i, j), (j, i)):
                    events.append((d["t_down"], F_LINK_DOWN, s, t_))
                    if d["t_heal"] is not None:
                        events.append((d["t_heal"], F_LINK_UP, s, t_))
        return _schedule(events, len(events))

    def _gmn_outage(self, k, d):
        a = max(1, int(np.ceil(k * d["frac"])))     # survivors incl. GMN 0
        events = []
        for g in range(min(a, k), k):
            events.append((d["t_down"], F_GMN_FAIL, g, 0))
            events.append((d["t_heal"], F_GMN_HEAL, g, 0))
        return _schedule(events, len(events))

    def _gmn_churn(self, k, sim_len, d):
        pad = d["max_events"] * 2
        if k < 2 or d["rate"] <= 0:
            return _schedule([], pad)                # GMN 0 is protected
        rng = np.random.RandomState(self.seed)
        events, t = [], 0.0
        for _ in range(d["max_events"]):
            t += rng.exponential(1.0 / d["rate"])
            if t >= sim_len:
                break
            g = int(rng.randint(1, k))               # never GMN 0
            events.append((t, F_GMN_FAIL, g, 0))
            events.append((t + d["repair"], F_GMN_HEAL, g, 0))
        return _schedule(events, pad)

    # -- serialization (ExperimentSpec payloads, schema v5) -----------

    def to_dict(self) -> dict:
        d = {"kind": self.kind, "seed": self.seed, "name": self.name}
        params = {}
        for key, val in self.params:
            if key == "events":
                val = [list(e) for e in val]
            params[key] = val
        d["params"] = params
        return d

    @staticmethod
    def from_dict(d: dict) -> "FaultSpec":
        unknown = set(d) - {"kind", "seed", "name", "params"}
        if unknown:
            raise ValueError(
                f"unknown FaultSpec fields {sorted(unknown)}; this reader "
                f"supports fields ['kind', 'name', 'params', 'seed']")
        params = []
        for key, val in sorted(dict(d.get("params", {})).items()):
            if key == "events":
                val = tuple(tuple(e) for e in val)
            params.append((key, val))
        return FaultSpec(kind=d.get("kind", "none"),
                         params=tuple(params),
                         seed=int(d.get("seed", 0)),
                         name=d.get("name", ""))


DEFAULT_FAULTS = FaultSpec.none()


def pad_to(sched: FaultSchedule, capacity: int) -> FaultSchedule:
    """INF-pad a schedule out to ``capacity`` slots.

    A shape-only change — padded rows carry ``times = INF`` and are
    masked off before they ever reach the queue (``sim._push_faults``) —
    so an ``ExperimentSpec`` fault axis mixing generators with different
    natural lengths can share one compiled program per static combo."""
    n = sched.capacity
    if capacity < n:
        raise ValueError(f"cannot pad a {n}-slot schedule down to "
                         f"{capacity}")
    if capacity == n:
        return sched
    pad = capacity - n
    return FaultSchedule(
        jnp.concatenate([sched.times, jnp.full((pad,), _INF, jnp.float32)]),
        jnp.concatenate([sched.kinds, jnp.zeros((pad,), jnp.int32)]),
        jnp.concatenate([sched.a0, jnp.zeros((pad,), jnp.int32)]),
        jnp.concatenate([sched.a1, jnp.zeros((pad,), jnp.int32)]))


def gmn_outages(sched: FaultSchedule, k: int) -> list:
    """Host-side ground truth: per-GMN outage intervals from a schedule.

    Returns a k-list of ``[(t_fail, t_heal), ...]`` with ``t_heal =
    inf`` for an outage still open at the end of the schedule (a
    *permanent* failure).  Mirrors the simulator's idempotent handler
    semantics — overlapping fails merge into one interval, a heal
    without an open outage is a no-op — so the failure-detector chaos
    tests (tests/test_failure_detector.py) can derive which peers a
    correct detector must suspect, and when, without re-running the
    simulator."""
    times = np.asarray(sched.times)
    kinds = np.asarray(sched.kinds)
    a0 = np.asarray(sched.a0)
    out = [[] for _ in range(k)]
    open_t = [None] * k
    for idx in np.argsort(times, kind="stable"):
        t = float(times[idx])
        if t >= float(_INF):
            continue
        g = int(a0[idx])
        if not (0 <= g < k):
            continue
        if int(kinds[idx]) == F_GMN_FAIL and open_t[g] is None:
            open_t[g] = t
        elif int(kinds[idx]) == F_GMN_HEAL and open_t[g] is not None:
            out[g].append((open_t[g], t))
            open_t[g] = None
    for g in range(k):
        if open_t[g] is not None:
            out[g].append((open_t[g], float("inf")))
    return out


def as_schedule(faults, k: int, sim_len: float):
    """Normalize None | FaultSpec | FaultSchedule to None | FaultSchedule."""
    if faults is None:
        return None
    if isinstance(faults, FaultSpec):
        return faults.build(k, sim_len)
    return faults
