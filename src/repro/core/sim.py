"""Event-driven transaction-level simulator of the clustered task manager.

Faithful JAX re-implementation of the paper's TLM evaluation (Sec 5):

  entities   k GMNs (serialized mapping compute, c_s per decision level),
             m PEs with FCFS queues, one global bus, k local buses
             (c_b per message, serialized per bus),
  mechanisms two-stage recursive task mapping (Sec 4.1), threshold-based
             status beacons (Sec 4.2, threshold dn_th), join/barrier
             synchronization (Tab 2).

All state lives in fixed-shape arrays; the run is one ``lax.while_loop``
over a bounded event queue.  The queue's format is itself a static
axis (``queue_impl``), owned by core/eventq.py (DESIGN.md §11), which
the loop reaches only through one ``eventq.Queue``: ``"linear"`` pops
with an O(queue_cap) ``jnp.argmin`` scan — the historical code, kept
operation-for-operation as the golden anchor — while ``"tree"`` and
``"calendar"`` keep the next event in a root row refreshed by one fused
commit per iteration, with bitwise-identical results.

Parameters are split into three objects (see DESIGN.md §7/§9):

  ``SimShape``   the shape-determining fields (m, k, n_childs, queue_cap,
                 max_apps).  Static JIT arguments — every distinct value
                 compiles one XLA program.
  ``SimPolicy``  the management strategy (mapping policy x beacon policy,
                 repro.core.policies).  Also static: each combination is
                 its own XLA program, so the untaken policy branches cost
                 nothing at run time.
  ``SimKnobs``   the numeric knobs (c_b, c_s, c_join, dn_th, T_b).  Traced
                 array arguments — changing them re-uses the compiled
                 program, and a batch of knob configs runs under
                 ``jax.vmap`` in a single compilation (repro.core.sweep).

``SimParams`` remains the user-facing bundle of all three; ``run(p, ...)``
is unchanged for callers.  Design-space sweeps over policies, thresholds,
costs and seeds go through ``repro.core.sweep`` which compiles once per
(shape, policy) pair.

All management messages (task-start groups, join-exits and their
forwards, status beacons) route through the interconnect transport model
(``repro.core.transport``, DESIGN.md §10).  The fabric is a fourth
static axis next to shape and policy: ``Topology("ideal")`` reproduces
the historical single-global-bus behavior bitwise, while ``shared_bus``
/ ``hier_tree`` / ``mesh2d`` / ``torus2d`` model contention and
per-receiver beacon skew — a fired beacon becomes k-1 in-flight entries
in the ``(k, k)`` ``bcn_t``/``bcn_val`` delivery matrix plus one
BEACON_RX event per receiver, so each GMN's ``view_t`` (and hence the
staleness ``age`` fed to the mapping policies) is genuinely
heterogeneous.

Event types:
  ARRIVE(app)             application hits its stimulus GMN; the GMN expands
                          the recursive fork tree (stage-1 decisions over its
                          beacon view) and emits LOCAL_SPAWN messages.
  LOCAL_SPAWN(app, g, n)  cluster g maps n child tasks onto its PEs
                          (stage-2 min-search, exact local view), one
                          decision + one local-bus task-start per child.
  JOIN_EXIT(app, g, p)    child finished: local-bus join-exit message,
                          barrier decrement, load decrement, beacon check.
  BEACON_RX(src, rcv, v)  (non-ideal topologies only) the in-flight beacon
                          from GMN src reaches receiver rcv carrying load
                          summary v; rcv's view/view_t update here.  The
                          fault-aware program also uses it for bounded
                          re-beacon *retries* on every fabric (src encoded
                          as src + k; DESIGN.md §15): the delivery is
                          re-checked against the current masks.
  LINK_DOWN(i, j)         fault injection (repro.core.faults, DESIGN.md §13):
  LINK_UP(i, j)           the directed (i, j) entry of the traced ``link_up``
                          mask flips; UP accounts the completed outage into
                          ``downtime``.
  GMN_FAIL(g)             GMN g dies / recovers: the ``gmn_alive`` vector
  GMN_HEAL(g)             flips, and management work addressed to a dead GMN
                          re-homes to the least-loaded live GMN (min_search
                          takeover, ``_takeover``) counting ``reroutes``.

The fault machinery compiles in only when a ``FaultSchedule`` is passed
(``faults`` is a traced pytree argument: a schedule *grid* — different
seeds, intensities, scenarios of the same length — re-uses one XLA
program, just like a knob grid).  With every link up and every GMN
alive the fault-aware code paths are exact no-ops, so a run under the
empty ``FaultSpec.none()`` schedule reproduces the frozen no-fault
goldens bitwise (tests/test_faults.py).

Deviations from the paper (documented in DESIGN.md §8): helper tasks occupy
the management plane (GMN time) rather than PEs.  Per-receiver beacon skew
(former deviation §8.2) is now modeled by the non-ideal topologies; the
default ``ideal`` fabric retains the atomic-update behavior for bitwise
continuity with the published golden results.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import eventq as EQ
from repro.core import faults as FLT
from repro.core import policies as P
from repro.core import trace as TR
from repro.core import transport as T
from repro.core.eventq import QUEUE_IMPLS  # noqa: F401 (re-export)
# the single INF sentinel both queue impls compare against — the
# linear/tree bitwise contract hinges on it being one shared value
from repro.core.eventq import INF
from repro.core.policies import DEFAULT_POLICY, SimPolicy  # noqa: F401 (re-export)
from repro.core.trace import TraceSpec  # noqa: F401 (re-export)
from repro.core.transport import DEFAULT_TOPOLOGY, Topology  # noqa: F401 (re-export)

EV_ARRIVE = 0
EV_LOCAL_SPAWN = 1
EV_JOIN_EXIT = 2
EV_BEACON_RX = 3
# fault events (compiled in only when a FaultSchedule is passed);
# EV == EV_LINK_DOWN + faults.F_* kind
EV_LINK_DOWN = 4
EV_LINK_UP = 5
EV_GMN_FAIL = 6
EV_GMN_HEAL = 7
# timer-driven beacon wake-up (compiled in only under the "heartbeat"
# beacon policy, core/policies.py ALL_BEACON_POLICIES): each GMN
# re-schedules its own EV_HEARTBEAT every T_b ticks, so status
# broadcasts keep flowing while a manager is idle (DESIGN.md §15)
EV_HEARTBEAT = 8

# stage-1 view tombstone for dead clusters: large enough that every
# mapping policy's min-search avoids them, small enough that i32
# arithmetic on the masked view cannot overflow
_DEAD_VIEW = jnp.int32(1 << 30)


@dataclass(frozen=True)
class SimShape:
    """Shape-determining simulator parameters.  Hashable and static: one
    XLA compilation per distinct value."""
    m: int = 256                 # processing elements
    k: int = 16                  # global management nodes (clusters)
    n_childs: int = 100          # child tasks per application
    queue_cap: int = 2048
    max_apps: int = 512
    record_s1: bool = False      # record per-decision stage-1 traces
                                 # (view/age/choice) for serving.replay
    queue_impl: str = "linear"   # event-queue structure (core/eventq.py):
                                 # "linear" = O(Q) argmin scan (golden
                                 # anchor), "tree" = flat leaf array
                                 # with a dense root, "calendar" =
                                 # bucketed calendar prototype — all
                                 # bitwise-identical
    batch_pop: int = 1           # max same-timestamp BEACON_RX events
                                 # popped per body iteration (DESIGN.md
                                 # §11); 1 = singleton (golden anchor
                                 # behavior, bitwise at any value)

    def __post_init__(self):
        if self.queue_impl not in QUEUE_IMPLS:
            raise ValueError(f"unknown queue_impl {self.queue_impl!r}; "
                             f"choose from {QUEUE_IMPLS}")
        if not 1 <= self.batch_pop <= self.queue_cap:
            raise ValueError(f"batch_pop {self.batch_pop} must be in "
                             f"[1, queue_cap={self.queue_cap}]")

    @property
    def mpk(self) -> int:
        return self.m // self.k

    @property
    def ns(self) -> int:
        """Static stage-1 fan-out: cluster targets per application."""
        return stage1_targets(self)


def stage1_targets(shape) -> int:
    """Static number of LOCAL_SPAWN targets per ARRIVE (Sec 4.1)."""
    return int(min(shape.k, max(1, -(-shape.n_childs // shape.mpk))))


class SimKnobs(NamedTuple):
    """Traced numeric knobs — a JAX pytree.  Stack leaves along a leading
    axis to form a batch of configs for ``repro.core.sweep``."""
    c_b: jnp.ndarray             # f32, message delay (4 tx + 4 rx)
    c_s: jnp.ndarray             # f32, selection delay coefficient
    c_join: jnp.ndarray          # f32, GMN barrier-decrement processing
    dn_th: jnp.ndarray           # i32, beacon drift threshold
    T_b: jnp.ndarray             # f32, beacon period/deadline (periodic,
                                 #      hybrid, staleness_weighted)
    c_hop: jnp.ndarray           # f32, per-hop latency (mesh2d, torus2d)
    susp_mult: jnp.ndarray       # f32, failure-detector multiplier
                                 #      (DESIGN.md §15): suspect a peer
                                 #      whose beacon is older than
                                 #      susp_mult * T_b
    retry_after: jnp.ndarray     # f32, best-effort re-beacon delay for a
                                 #      lost delivery (0 = retries off)

    @classmethod
    def make(cls, c_b=8.0, c_s=8.0, c_join=8.0, dn_th=4,
             T_b=1000.0, c_hop=2.0, susp_mult=3.0,
             retry_after=0.0) -> "SimKnobs":
        return cls(jnp.asarray(c_b, jnp.float32),
                   jnp.asarray(c_s, jnp.float32),
                   jnp.asarray(c_join, jnp.float32),
                   jnp.asarray(dn_th, jnp.int32),
                   jnp.asarray(T_b, jnp.float32),
                   jnp.asarray(c_hop, jnp.float32),
                   jnp.asarray(susp_mult, jnp.float32),
                   jnp.asarray(retry_after, jnp.float32))


@dataclass(frozen=True)
class SimParams:
    m: int = 256                 # processing elements
    k: int = 16                  # global management nodes (clusters)
    c_b: float = 8.0             # message delay (4 tx + 4 rx), bus-serialized
    c_s: float = 8.0             # selection delay coefficient
    c_join: float = 8.0          # GMN barrier-decrement processing
    dn_th: int = 4               # beacon drift threshold
    n_childs: int = 100          # child tasks per application
    queue_cap: int = 2048
    max_apps: int = 512
    T_b: float = 1000.0          # beacon period/deadline (traced knob)
    c_hop: float = 2.0           # per-hop mesh latency (traced knob)
    susp_mult: float = 3.0       # failure-detector multiplier (traced
                                 # knob, DESIGN.md §15)
    retry_after: float = 0.0     # best-effort re-beacon delay (traced
                                 # knob; 0 disables retries)
    mapping: str = "min_search"  # stage-1 policy (static, core/policies.py)
    beacon: str = "threshold"    # beacon policy (static, core/policies.py)
    topology: str = "ideal"      # fabric model (static, core/transport.py)
    record_s1: bool = False      # record stage-1 decision traces (replay)
    queue_impl: str = "linear"   # event-queue structure (core/eventq.py)
    batch_pop: int = 1           # same-timestamp BEACON_RX batch window
                                 # (static, DESIGN.md §11)

    def __post_init__(self):
        if self.queue_impl not in QUEUE_IMPLS:
            raise ValueError(f"unknown queue_impl {self.queue_impl!r}; "
                             f"choose from {QUEUE_IMPLS}")
        if not 1 <= self.batch_pop <= self.queue_cap:
            raise ValueError(f"batch_pop {self.batch_pop} must be in "
                             f"[1, queue_cap={self.queue_cap}]")

    @property
    def mpk(self) -> int:
        return self.m // self.k

    @property
    def shape(self) -> SimShape:
        return SimShape(m=self.m, k=self.k, n_childs=self.n_childs,
                        queue_cap=self.queue_cap, max_apps=self.max_apps,
                        record_s1=self.record_s1,
                        queue_impl=self.queue_impl,
                        batch_pop=self.batch_pop)

    @property
    def knobs(self) -> SimKnobs:
        return SimKnobs.make(c_b=self.c_b, c_s=self.c_s, c_join=self.c_join,
                             dn_th=self.dn_th, T_b=self.T_b, c_hop=self.c_hop,
                             susp_mult=self.susp_mult,
                             retry_after=self.retry_after)

    @property
    def policy(self) -> SimPolicy:
        return SimPolicy(mapping=self.mapping, beacon=self.beacon)

    @property
    def topo(self) -> Topology:
        return Topology(kind=self.topology)

    @property
    def sel_global(self) -> float:
        """Stage-1 decision cost c_s * log2(k) (same formula the traced
        _Ctx uses)."""
        return self.c_s * _log2_levels(self.k)

    @property
    def sel_local(self) -> float:
        """Stage-2 decision cost c_s * log2(m/k) (same formula the traced
        _Ctx uses)."""
        return self.c_s * _log2_levels(self.mpk)


def _log2_levels(v: int) -> float:
    """Static decision-tree depth factor: log2(v) for v > 1, else 0."""
    return float(np.log2(v)) if v > 1 else 0.0


class _Ctx:
    """Per-trace context: static shape ints + policy + topology + traced
    knob scalars, presented through the attribute names the event handlers
    historically used."""
    __slots__ = ("m", "k", "mpk", "n_childs", "queue_cap", "max_apps",
                 "c_b", "c_s", "c_join", "dn_th", "T_b", "c_hop",
                 "susp_mult", "retry_after", "policy",
                 "topology", "hops", "ns", "record_s1", "queue",
                 "sel_global", "sel_local", "faults_on",
                 "batch_pop", "stage_fan", "stage_h", "trace")

    def __init__(self, shape: SimShape, knobs: SimKnobs,
                 policy: SimPolicy = DEFAULT_POLICY,
                 topology: Topology = DEFAULT_TOPOLOGY,
                 faults_on: bool = False,
                 trace: TraceSpec | None = None):
        self.m = shape.m
        self.k = shape.k
        self.mpk = shape.mpk
        self.n_childs = shape.n_childs
        self.queue_cap = shape.queue_cap
        self.max_apps = shape.max_apps
        self.c_b = knobs.c_b
        self.c_s = knobs.c_s
        self.c_join = knobs.c_join
        self.dn_th = knobs.dn_th
        self.T_b = knobs.T_b
        self.c_hop = knobs.c_hop
        self.susp_mult = knobs.susp_mult
        self.retry_after = knobs.retry_after
        self.policy = policy
        self.topology = topology
        # the fabric's static hop table (an XLA constant; only the grid
        # fabrics, transport.MESH_KINDS, read it)
        self.hops = jnp.asarray(T.hop_table(topology.kind, shape.k))
        self.ns = shape.ns
        self.record_s1 = shape.record_s1
        # the event queue's format, behind its one seam (core/eventq.py).
        # Event times cluster at multiples of the coarser of c_b (buses)
        # and c_s (decisions), which sets the calendar's bucket width
        # (traced: only the work depends on it, never a result).
        self.queue = EQ.resolve(shape.queue_impl, shape.queue_cap,
                                jnp.maximum(jnp.maximum(knobs.c_b, knobs.c_s),
                                            jnp.float32(1.0)))
        self.sel_global = knobs.c_s * _log2_levels(shape.k)
        self.sel_local = knobs.c_s * _log2_levels(shape.mpk)
        # static: whether the fault machinery (mask state, fault event
        # branches, mask-routed message paths) is compiled in
        self.faults_on = faults_on
        # static: the instrumentation layer (core/trace.py, DESIGN.md
        # §14) — None compiles the uninstrumented program
        self.trace = trace
        # fused-commit staging layout (DESIGN.md §11): every handler
        # returns one fixed-shape batch of push requests — a k-wide
        # beacon fan-out segment (non-ideal fabrics only; the ideal
        # fabric delivers atomically and pushes no BEACON_RX) followed
        # by a handler segment sized for the widest handler push
        # (arrive's ns LOCAL_SPAWNs / spawn's n_max JOIN_EXITs)
        self.batch_pop = shape.batch_pop
        # the fault-aware program appends a k-wide *retry* segment to the
        # fan-out (DESIGN.md §15): one bounded re-beacon per lost
        # delivery, pushed as a BEACON_RX with src encoded as g + k.
        # With retries off (retry_after == 0) every retry row is masked
        # and slot assignment is bitwise the no-retry program's.
        self.stage_fan = (shape.k if topology.kind != "ideal" else 0) \
            + (shape.k if faults_on else 0)
        self.stage_h = max(shape.ns, _spawn_group_bound(shape))


def make_state(p):
    k, mpk, A = p.k, p.mpk, p.max_apps
    return p.queue.state() | {
        # infra
        "pe_free": jnp.zeros((k, mpk), jnp.float32),
        "gmn_free": jnp.zeros((k,), jnp.float32),
        "gbus_free": jnp.zeros((), jnp.float32),
        "lbus_free": jnp.zeros((k,), jnp.float32),
        # load bookkeeping
        "loads": jnp.zeros((k, mpk), jnp.int32),   # mapped tasks per PE
        "view": jnp.zeros((k, k), jnp.int32),      # GMN g's view of cluster c
        "view_t": jnp.zeros((k, k), jnp.float32),  # tick view[g, c] was recvd
        "last_bcast": jnp.zeros((k,), jnp.int32),
        "last_bcast_t": jnp.zeros((k,), jnp.float32),
        "rr_ptr": jnp.zeros((k,), jnp.int32),      # per-GMN decision counter
        "beacons_tx": jnp.zeros((), jnp.int32),
        # transport: in-flight beacon matrix [src, rcv] tracking the
        # LATEST pending arrival per pair (non-ideal topologies; stays
        # INF under "ideal") + the delivery counter — conservation is
        # exact: beacons_rx == (k-1) * beacons_tx at the end of a run
        "bcn_t": jnp.full((k, k), INF),            # arrival time (INF = none)
        "beacons_rx": jnp.zeros((), jnp.int32),    # per-receiver deliveries
        # per-receiver delivery skew of each fired beacon (max - min
        # arrival): the heterogeneity the ideal fabric hides
        "bcn_skew_sum": jnp.zeros((), jnp.float32),
        "bcn_skew_max": jnp.zeros((), jnp.float32),
        # management accounting (benchmarks/topology_frontier.py):
        # mgmt_latency sums (delivery - ready) over transported messages —
        # the pure communication overhead, broken out per fabric;
        # mgmt_proc sums manager-side queueing + service (fork expansion,
        # stage-2 decision batches, barrier decrements) — the computation
        # overhead that saturates a centralized manager
        "mgmt_msgs": jnp.zeros((), jnp.int32),
        "mgmt_latency": jnp.zeros((), jnp.float32),
        "mgmt_proc": jnp.zeros((), jnp.float32),
        # applications
        "app_remaining": jnp.zeros((A,), jnp.int32),
        "app_arrive": jnp.full((A,), INF),
        "app_done": jnp.full((A,), INF),
        "events_processed": jnp.zeros((), jnp.int32),
        # loop trips: one pop, or one same-time BEACON_RX cohort of up
        # to batch_pop events.  Under vmap a grid's trip count is the
        # maximum over its lanes; a device trace divided by it gives the
        # cost of one iteration.  Pure telemetry, like evq_peak.
        "iterations": jnp.zeros((), jnp.int32),
        # the trips among them whose popped root is a BEACON_RX: with
        # beacons_rx it gives the deliveries a cohort trip retires.
        # Telemetry too: it counts trips, not simulated behaviour.
        "rx_trips": jnp.zeros((), jnp.int32),
        "dropped": jnp.zeros((), jnp.int32),
        # always-on queue-occupancy telemetry (DESIGN.md §14): the live
        # entry count and its high-water mark.  The peak candidate is
        # taken at the START of each body iteration (before the pop), a
        # point every batch_pop grouping and queue impl visits with the
        # same value — which keeps the counter bitwise-invariant across
        # the (queue_impl x batch_pop) head-to-head gates.  Near-
        # overflow is therefore measurable: dropped > 0 implies
        # evq_peak == queue_cap.
        "evq_len": jnp.zeros((), jnp.int32),
        "evq_peak": jnp.zeros((), jnp.int32),
    } | ({
        # fault fabric state (repro.core.faults, DESIGN.md §13): the
        # traced link mask + GMN liveness the message paths route
        # through, outage-start bookkeeping, and the availability
        # counters of the overhead decomposition.  Only present when a
        # FaultSchedule is passed (the fault-aware program).
        "link_up": jnp.ones((k, k), jnp.float32),     # directed, 1 = up
        "gmn_alive": jnp.ones((k,), jnp.float32),     # 1 = alive
        "link_down_t": jnp.zeros((k, k), jnp.float32),
        "gmn_down_t": jnp.zeros((k,), jnp.float32),
        "msgs_lost": jnp.zeros((), jnp.int32),    # dropped beacon deliveries
        "reroutes": jnp.zeros((), jnp.int32),     # detours + re-homed work
        "downtime": jnp.zeros((), jnp.float32),   # completed outage ticks
        # failure detector (DESIGN.md §15): the traced (k, k) suspicion
        # matrix — suspect[g, c] == 1 when GMN g's view of peer c is
        # older than susp_mult * T_b — with per-pair onset/clear
        # counters and false-positive accounting against the ground-
        # truth gmn_alive/link_up masks.  Refreshed straight-line at
        # every event pop; feeds nothing unless a SUSPECT_POLICY reads
        # the same predicate, so every pre-detector golden is untouched.
        "suspect": jnp.zeros((k, k), jnp.float32),
        "susp_onsets": jnp.zeros((k, k), jnp.int32),
        "susp_clears": jnp.zeros((k, k), jnp.int32),
        "susp_false_pos": jnp.zeros((), jnp.int32),
        # per-suspector detector epoch: a manager rejoining after an
        # outage restarts its failure-detector timers at the heal tick
        # (det_floor[g] <- t) instead of trusting pre-crash receipt
        # times.  Mapping-policy staleness ages are NOT floored — the
        # healed manager's load views really are stale and the
        # staleness-aware policies keep pricing them.  All-zero (the
        # no-fault case) the floor is an exact no-op: view_t >= 0.
        "det_floor": jnp.zeros((k,), jnp.float32),
        # bounded re-beacon accounting: conservation generalizes to
        # beacons_rx + msgs_lost == (k-1) * beacons_tx + retries_tx
        "retries_tx": jnp.zeros((), jnp.int32),
    } if getattr(p, "faults_on", False) else {}) | ({
        # stage-1 decision trace (serving/replay.py cross-validation)
        "dec_view": jnp.zeros((A, p.ns, k), jnp.int32),
        "dec_age": jnp.zeros((A, k), jnp.float32),
        "dec_choice": jnp.zeros((A, p.ns), jnp.int32),
        "dec_rr0": jnp.zeros((A,), jnp.int32),
        "dec_t": jnp.full((A,), INF),
    } if p.record_s1 else {}) | ({
        # under faults the deciding GMN can differ from the stimulus GMN
        # (min_search takeover); replay needs the effective decider
        "dec_gmn": jnp.zeros((A,), jnp.int32),
    } if p.record_s1 and getattr(p, "faults_on", False) else {}) \
        | (TR.trace_state(p.trace, k)
           if getattr(p, "trace", None) is not None else {})


# Dynamic-index updates are written as one-hot selects rather than
# ``.at[i].set``: under vmap a per-lane index can't lower to a
# dynamic-update-slice, and XLA:CPU's general scatter is a serial loop that
# dominates batched-sweep runtime.  The selects compute identical values
# (no arithmetic on unselected elements), which keeps sweep results bitwise
# equal to per-config runs (tests/test_sweep.py).

# the scatter-free row-set primitive lives once, in transport.py
_set1 = T._set1


def _add1(arr, i, delta):
    """arr.at[i].add(delta) as a one-hot select."""
    return jnp.where(jnp.arange(arr.shape[0]) == i, arr + delta, arr)


def _add2(arr, i, j, delta):
    """arr.at[i, j].add(delta) as a one-hot select."""
    hot = (jnp.arange(arr.shape[0])[:, None] == i) \
        & (jnp.arange(arr.shape[1])[None, :] == j)
    return jnp.where(hot, arr + delta, arr)


def _hist_add(st, p, key, vals, mask, weight=None):
    """Masked scatter-add into one of the (hist_bins,) trace histograms
    (core/trace.py; exact no-op when tracing is off).  Masked lanes
    route to the out-of-range bin and drop.  These tiny in-branch
    scatters are fine — the §11 copy-insertion hazard is proportional
    to buffer size, and hist_bins is ~100 floats."""
    if p.trace is None:
        return st
    b = TR.hist_bin(vals, p.trace)
    idx = jnp.where(mask, b, p.trace.hist_bins)
    w = jnp.float32(1.0) if weight is None else weight
    st = dict(st)
    st[key] = st[key].at[idx].add(w, mode="drop")
    return st


def _bulk_push(st, p, mask, times, typ, a0, a1, a2):
    """Insert the masked entries of an event batch, exactly equivalent to
    pushing them one by one in order: the j-th masked entry takes the
    j-th lowest free queue slot, and masked entries past the free count
    drop (core/eventq.py, the same rule under every queue format)."""
    return p.queue.bulk_push(st, mask, times, typ, a0, a1, a2)


# --------------------------------------------------------------------------
# Fused-commit staging (DESIGN.md §11).  Handlers no longer push events
# or write the big (k, k) matrices mid-handler: they return a
# fixed-shape *staged record* next to the updated state, and the body
# applies everything AFTER lax.switch — the queue pushes fused with the
# pop into one end-of-body EQ.commit, the matrix rows/cells as masked
# mode="drop" scatters.  A scatter into a big buffer inside a switch/
# cond branch region forces XLA:CPU to copy the whole buffer in the
# executed branch every iteration (the measured ~850 us/event of the
# pre-fused paper point); branches that return small records instead
# keep every big-buffer update straight-line and in place.
#
# Staged push layout (rows of the (stage_fan + stage_h,) batch):
#   [0, stage_fan)             beacon fan-out BEACON_RX entries (non-ideal
#                              fabrics; 0-width under "ideal")
#   [stage_fan, +stage_h)      the handler's own pushes (arrive's ns
#                              LOCAL_SPAWNs / spawn's n_max JOIN_EXITs),
#                              padded with masked-off rows
# The fan-out segment precedes the handler segment because that is the
# historical push order (_maybe_beacon fired before the JOIN_EXIT push
# in _handle_local_spawn) — slot assignment, and hence every frozen
# golden, is order-sensitive.
# --------------------------------------------------------------------------

def _fan_zero(p):
    """Zero fan-out staging: no BEACON_RX pushes, no deferred bcn_t/view
    writes.  The identity branch of the fanout cond returns this."""
    pf = p.stage_fan
    return {
        "mask": jnp.zeros((pf,), bool),
        "t": jnp.zeros((pf,), jnp.float32),
        "a0": jnp.zeros((pf,), jnp.float32),
        "a1": jnp.zeros((pf,), jnp.float32),
        "a2": jnp.zeros((pf,), jnp.float32),
        "on": jnp.zeros((), bool),           # a beacon fired this event
        "g": jnp.zeros((), jnp.int32),       # sender GMN
        "brow": jnp.zeros((p.k,), jnp.float32),   # new bcn_t row g
        "load": jnp.zeros((), jnp.int32),    # view[g, g] <- load_g
        "t_tx": jnp.zeros((), jnp.float32),  # view_t[g, g] <- t_tx
    }


def _staged(p, fan, h_mask, h_t, h_typ, h_a0, h_a1, h_a2,
            vrow_on=None, vrow_i=None, vrow=None):
    """Assemble one handler's staged record: fan-out segment (``fan`` —
    None means no beacon path ran this event) + handler-push segment
    (padded to stage_h), plus the deferred view-row write of
    _handle_arrive.  Every branch of the body's lax.switch returns a
    record of this exact pytree structure."""
    pf, ph = p.stage_fan, p.stage_h
    n = h_mask.shape[0]
    pad = ph - n

    def ext(x):
        x = jnp.broadcast_to(jnp.asarray(x, jnp.float32), (n,))
        return jnp.concatenate([x, jnp.zeros((pad,), jnp.float32)]) \
            if pad else x

    h_mask = jnp.concatenate([jnp.asarray(h_mask, bool),
                              jnp.zeros((pad,), bool)]) if pad \
        else jnp.asarray(h_mask, bool)
    if fan is None:
        fan = _fan_zero(p)
    out = {
        "push_mask": jnp.concatenate([fan["mask"], h_mask]),
        "push_t": jnp.concatenate([fan["t"], ext(h_t)]),
        "push_typ": jnp.concatenate([
            jnp.full((pf,), EV_BEACON_RX, jnp.float32),
            jnp.full((ph,), h_typ, jnp.float32)]),
        "push_a0": jnp.concatenate([fan["a0"], ext(h_a0)]),
        "push_a1": jnp.concatenate([fan["a1"], ext(h_a1)]),
        "push_a2": jnp.concatenate([fan["a2"], ext(h_a2)]),
        # deferred _handle_arrive view-row write
        "vrow_on": jnp.zeros((), bool) if vrow_on is None else vrow_on,
        "vrow_i": jnp.zeros((), jnp.int32) if vrow_i is None else vrow_i,
        "vrow": jnp.zeros((p.k,), jnp.int32) if vrow is None else vrow,
    }
    if pf:
        # deferred fan-out matrix writes (bcn_t row, sender's own
        # view/view_t cell)
        out |= {k2: fan[k2] for k2 in ("on", "g", "brow", "load", "t_tx")}
    return out


def _stage_none(p):
    """The no-push staged record (BEACON_RX placeholder branch, fault
    handlers, _handle_join_exit's non-beacon part)."""
    z = jnp.zeros((0,), jnp.float32)
    return _staged(p, None, jnp.zeros((0,), bool), z, 0.0, z, z, z)


def _apply_staged(st, p, stg):
    """Apply a staged record's deferred big-matrix writes, straight-line
    after the switch (masked lanes route to an out-of-range row and
    drop).  At most one family is active per event — arrive stages the
    view row, spawn/join stage the fan-out — so application order is
    irrelevant."""
    k = p.k
    st = dict(st)
    vi = jnp.where(stg["vrow_on"], stg["vrow_i"], k)
    st["view"] = st["view"].at[vi].set(stg["vrow"], mode="drop")
    if p.stage_fan:
        fg = jnp.where(stg["on"], stg["g"], k)
        st["bcn_t"] = st["bcn_t"].at[fg].set(stg["brow"], mode="drop")
        st["view"] = st["view"].at[fg, stg["g"]].set(stg["load"],
                                                     mode="drop")
        st["view_t"] = st["view_t"].at[fg, stg["g"]].set(stg["t_tx"],
                                                         mode="drop")
    return st


def _maybe_beacon(st, p, g, t):
    """Status broadcast check (Sec 4.2, generalized).  The trigger is the
    statically selected BeaconPolicy (core/policies.py); ``threshold`` is
    the paper's drift rule, and the `k > 1` gate is topology, not policy.

    Delivery is the statically selected Topology (core/transport.py):
    ``ideal`` updates every receiver's view atomically at the global-bus
    grant (the historical behavior, kept operation-for-operation for the
    bitwise golden tests); the non-ideal fabrics enqueue k-1 in-flight
    entries with per-receiver arrival times and deliver via BEACON_RX.

    Returns ``(st, fan)``: ``fan`` is the staged fan-out record for the
    end-of-body commit (None under ``ideal``, whose atomic delivery
    pushes no events; its view-column writes stay in-branch — they are
    the golden ideal program and not the perf target)."""
    load_g = st["loads"][g].sum()
    delta = jnp.abs(load_g - st["last_bcast"][g])
    due = P.beacon_policy(p.policy.beacon)(
        delta, t, st["last_bcast_t"][g], dn_th=p.dn_th, T_b=p.T_b)
    fire = jnp.logical_and(due, p.k > 1)
    return _fire_beacon(st, p, g, t, fire, load_g)


def _fire_beacon(st, p, g, t, fire, load_g):
    """Transmit a status beacon from ``g`` when ``fire`` holds — the
    delivery/accounting half of :func:`_maybe_beacon`, shared with the
    GMN_HEAL rejoin announcement (DESIGN.md §15), which fires it
    unconditionally so peers clear their suspicion of a recovered
    manager without waiting for its next workload-driven broadcast."""
    if p.faults_on:
        # a dead GMN transmits nothing (alive everywhere: exact no-op)
        fire = jnp.logical_and(fire, st["gmn_alive"][g] > 0)
    st = dict(st)
    if p.topology.kind == "ideal":
        # bus grant: serialize on the global bus; atomic view update.
        # Column .at[] updates, not (k, k) one-hot selects: at the paper
        # point k=256 the one-hot form pays a full 65k-element pass per
        # event; the stored values are identical (element [i, g] becomes
        # fire ? x : old either way), so the frozen goldens still pass
        t_tx = jnp.maximum(t, st["gbus_free"]) + p.c_b
        st["gbus_free"] = jnp.where(fire, t_tx, st["gbus_free"])
        rcv = jnp.arange(p.k) != g
        if p.faults_on:
            # route the atomic update through the mask: receivers behind
            # a down (g, i) link or dead stay stale; the sender's own
            # entry is local bookkeeping and always lands.  With the
            # mask all-up `ok` equals the broadcast `fire`, so the
            # stored values match the no-fault program bitwise.
            dlv = jnp.logical_and(st["link_up"][g] > 0,
                                  st["gmn_alive"] > 0)
            dlv = jnp.logical_or(dlv, jnp.logical_not(rcv))
            ok = jnp.logical_and(fire, dlv)
            lost = jnp.logical_and(fire, jnp.logical_and(
                rcv, jnp.logical_not(dlv)))
            st["msgs_lost"] = st["msgs_lost"] \
                + jnp.sum(lost).astype(jnp.int32)
            ndlv = jnp.sum(jnp.logical_and(rcv, dlv)).astype(jnp.int32)
        else:
            ok = fire
            ndlv = jnp.int32(p.k - 1)
        st["view"] = st["view"].at[:, g].set(
            jnp.where(ok, load_g, st["view"][:, g]))
        st["view_t"] = st["view_t"].at[:, g].set(
            jnp.where(ok, t_tx, st["view_t"][:, g]))
        st["last_bcast"] = jnp.where(fire, _set1(st["last_bcast"], g, load_g),
                                     st["last_bcast"])
        st["last_bcast_t"] = jnp.where(fire,
                                       _set1(st["last_bcast_t"], g, t_tx),
                                       st["last_bcast_t"])
        st["beacons_tx"] = st["beacons_tx"] + jnp.where(fire, 1, 0)
        nrcv = jnp.int32(p.k - 1)
        st["mgmt_msgs"] = st["mgmt_msgs"] + jnp.where(fire, nrcv, 0)
        st["mgmt_latency"] = st["mgmt_latency"] \
            + jnp.where(fire, ndlv.astype(jnp.float32) * (t_tx - t), 0.0)
        # every delivery shares the t_tx - t bus latency: one weighted
        # histogram entry keeps mass == deliveries exactly
        st = _hist_add(st, p, "th_mgmt", t_tx - t, fire,
                       weight=ndlv.astype(jnp.float32))
        if p.faults_on:
            # bounded re-beacon for the best-effort class (DESIGN.md
            # §15): each lost delivery schedules ONE retry, a BEACON_RX
            # at t_tx + retry_after with src encoded as g + k — the RX
            # handler re-checks the masks at delivery time.  With
            # retry_after == 0 (or no losses) every row is masked and
            # the program is bitwise the no-retry one.
            rtr = jnp.logical_and(lost, p.retry_after > 0)
            st["retries_tx"] = st["retries_tx"] \
                + jnp.sum(rtr).astype(jnp.int32)
            fan = _fan_zero(p) | {
                "mask": rtr,
                "t": jnp.full((p.k,), t_tx + p.retry_after, jnp.float32),
                "a0": jnp.full((p.k,), g + p.k, jnp.float32),
                "a1": jnp.arange(p.k, dtype=jnp.float32),
                "a2": jnp.full((p.k,), load_g, jnp.float32),
            }
            return st, fan
        return st, None

    # transport path: per-receiver delivery through the fabric.  The
    # whole fan-out (fabric grants, small bookkeeping) is gated behind
    # lax.cond: with `fire` false every masked update below is an exact
    # no-op, so skipping the branch is bitwise invisible — but on CPU
    # (seq mode) the common no-fire event then pays nothing.  Under
    # vmap the cond lowers to a select that executes both branches,
    # which is exactly the pre-gate behavior.  The k-entry queue push
    # and the bcn_t/view matrix writes are NOT performed here: they
    # come back in the staged fan record (a branch-region scatter into
    # a big buffer would copy the buffer — module comment above) and
    # the body applies them after the switch.
    with jax.named_scope("sim.fanout"):
        return jax.lax.cond(
            fire, lambda s: _beacon_fanout(s, p, g, t, fire, load_g),
            lambda s: (s, _fan_zero(p)), st)


def _beacon_fanout(st, p, g, t, fire, load_g):
    """The non-ideal beacon delivery path (only traced when `fire` can be
    true; all updates stay masked by the traced `fire` so the cond's
    both-branch vmap lowering reproduces the masked semantics
    bitwise).  Returns ``(st, fan)`` — the k BEACON_RX pushes and the
    bcn_t/view[g, g] writes ride the staged record."""
    st = dict(st)
    t_tx, t_arr, gbus, lbus = T.beacon_tx(
        p.topology, g, t, fire, gbus=st["gbus_free"], lbus=st["lbus_free"],
        c_b=p.c_b, c_hop=p.c_hop, hops=p.hops, k=p.k)
    st["gbus_free"], st["lbus_free"] = gbus, lbus
    rcv = jnp.arange(p.k) != g                     # receiver mask
    if p.faults_on:
        # best-effort beacons: a delivery whose (g, i) link is down or
        # whose receiver is dead is dropped at injection time and
        # counted in msgs_lost — conservation generalizes to
        # beacons_rx + msgs_lost == (k-1) * beacons_tx.  All-up mask:
        # dlv == rcv, every value below matches the no-fault program.
        dlv = jnp.logical_and(rcv, jnp.logical_and(
            st["link_up"][g] > 0, st["gmn_alive"] > 0))
        lost = jnp.logical_and(fire,
                               jnp.logical_and(rcv, jnp.logical_not(dlv)))
        st["msgs_lost"] = st["msgs_lost"] + jnp.sum(lost).astype(jnp.int32)
    else:
        dlv = rcv
    push = jnp.logical_and(fire, dlv)
    # track the latest pending arrival per (src, rcv); arrivals from one
    # source to one receiver are strictly increasing in send order
    # (c_b > 0 serializes the source), so earlier beacons still in the
    # event queue deliver first and the matrix drains on the last one.
    # The new bcn_t row g and the sender's own view/view_t cell are
    # STAGED, not written here (branch-region big-buffer scatters force
    # a full copy); the staged values are exactly what the in-branch
    # writes stored, so all bitwise contracts hold.
    fan = {
        "mask": push,
        "t": t_arr,
        "a0": jnp.full((p.k,), g, jnp.float32),
        "a1": jnp.arange(p.k, dtype=jnp.float32),
        "a2": jnp.full((p.k,), load_g, jnp.float32),
        "on": fire,
        "g": jnp.asarray(g, jnp.int32),
        "brow": jnp.where(push, t_arr, st["bcn_t"][g]),
        "load": jnp.asarray(load_g, jnp.int32),
        "t_tx": jnp.asarray(t_tx, jnp.float32),
    }
    if p.faults_on:
        # bounded re-beacon segment (DESIGN.md §15): one retry per lost
        # delivery, arriving retry_after ticks after the would-have
        # arrival, src encoded as g + k so the RX handler re-checks the
        # masks at delivery time.  Retries do not ride the bcn_t
        # in-flight matrix (they are best-effort re-sends, not tracked
        # beacons).  retry_after == 0 masks every row: bitwise no-op.
        rtr = jnp.logical_and(lost, p.retry_after > 0)
        st["retries_tx"] = st["retries_tx"] + jnp.sum(rtr).astype(jnp.int32)
        fan["mask"] = jnp.concatenate([push, rtr])
        fan["t"] = jnp.concatenate([t_arr, t_arr + p.retry_after])
        fan["a0"] = jnp.concatenate([fan["a0"],
                                     jnp.full((p.k,), g + p.k, jnp.float32)])
        fan["a1"] = jnp.concatenate([fan["a1"],
                                     jnp.arange(p.k, dtype=jnp.float32)])
        fan["a2"] = jnp.concatenate([fan["a2"],
                                     jnp.full((p.k,), load_g, jnp.float32)])
    st["last_bcast"] = jnp.where(fire, _set1(st["last_bcast"], g, load_g),
                                 st["last_bcast"])
    st["last_bcast_t"] = jnp.where(fire, _set1(st["last_bcast_t"], g, t_tx),
                                   st["last_bcast_t"])
    st["beacons_tx"] = st["beacons_tx"] + jnp.where(fire, 1, 0)
    # mgmt_msgs counts messages injected into the fabric (lost ones
    # included); latency and skew only accrue over actual deliveries.
    # No faults: push == fire & rcv == injected, the historical values.
    st["mgmt_msgs"] = st["mgmt_msgs"] \
        + jnp.sum(jnp.logical_and(fire, rcv)).astype(jnp.int32)
    st["mgmt_latency"] = st["mgmt_latency"] \
        + jnp.sum(jnp.where(push, t_arr - t, 0.0))
    st = _hist_add(st, p, "th_mgmt", t_arr - t, push)
    spread = jnp.maximum(jnp.max(jnp.where(dlv, t_arr, -INF))
                         - jnp.min(jnp.where(dlv, t_arr, INF)), 0.0)
    st["bcn_skew_sum"] = st["bcn_skew_sum"] + jnp.where(fire, spread, 0.0)
    st["bcn_skew_max"] = jnp.maximum(st["bcn_skew_max"],
                                     jnp.where(fire, spread, 0.0))
    return st, fan


def _handle_beacon_rx_batch(st, p, t, ok, lane_typ, la0, la1, la2):
    """Vectorized BEACON_RX processing, straight-line in the body (NOT a
    switch branch): beacons from GMN src reach receiver rcv (non-ideal
    topologies), up to batch_pop of them per iteration — all at the same
    timestamp t and with distinct (src, rcv) pairs (per-pair arrivals
    are strictly increasing in send order, c_b > 0 serializes the
    source), so the per-lane scalar writes commute and the batch is
    bitwise the sequential singleton order (core/eventq.py batch_take).
    Non-RX and masked lanes route their writes to out-of-range indices
    and drop; on a non-RX root event the whole block is an exact no-op.

    Every delivery applies — FIFO-correct even when a newer beacon from
    src is already in flight behind it.  The in-flight matrix clears
    only when the LAST tracked arrival lands (``bcn_t == t``), which is
    what lets tests assert it drains to empty."""
    k = p.k
    rx = jnp.logical_and(ok, lane_typ == EV_BEACON_RX)       # (B,)
    raw = jnp.where(rx, la0, 0)
    rcv = jnp.where(rx, la1, 0)
    if p.faults_on:
        # retry re-beacons carry src + k in a0 (DESIGN.md §15): decode,
        # then re-check the CURRENT masks at delivery time — a retry
        # whose (src, rcv) link is still down or whose receiver is
        # still dead is a final loss (one bounded retry, no cascade).
        # First-attempt deliveries are unconditional here: their loss
        # was decided at injection.  With no retries in flight dlv == rx
        # and every value below matches the no-retry program bitwise.
        retry = raw >= k
        src = jnp.where(retry, raw - k, raw)
        still = jnp.logical_and(st["link_up"][src, rcv] > 0,
                                st["gmn_alive"][rcv] > 0)
        dlv = jnp.logical_and(rx, jnp.logical_or(
            jnp.logical_not(retry), still))
        st = dict(st)
        st["msgs_lost"] = st["msgs_lost"] + jnp.sum(jnp.logical_and(
            rx, jnp.logical_and(retry, jnp.logical_not(still)))) \
            .astype(jnp.int32)
        # retries never ride the in-flight bcn_t matrix: route their
        # clear to the drop row
        si = jnp.where(jnp.logical_and(rx, jnp.logical_not(retry)), src, k)
    else:
        src = raw
        dlv = rx
        si = jnp.where(rx, src, k)                           # masked -> drop
        st = dict(st)
    last = st["bcn_t"][src, rcv] == t                        # (B,) gather
    ri = jnp.where(dlv, rcv, k)
    st["bcn_t"] = st["bcn_t"].at[si, rcv].set(
        jnp.where(last, INF, st["bcn_t"][src, rcv]), mode="drop")
    st["view"] = st["view"].at[ri, src].set(la2, mode="drop")
    st["view_t"] = st["view_t"].at[ri, src].set(
        jnp.full(rx.shape, t, jnp.float32), mode="drop")
    st["beacons_rx"] = st["beacons_rx"] + jnp.sum(dlv).astype(jnp.int32)
    return st


def _handle_arrive(st, p, t, app, g, _unused, lengths):
    """Stage 1: expand the fork tree at GMN g, fan out LOCAL_SPAWN msgs."""
    k, n = p.k, p.n_childs
    ns = p.ns                                     # cluster targets (static)
    depth = int(np.ceil(np.log2(ns))) if ns > 1 else 0
    share = n // ns
    rem = n - share * ns

    st = dict(st)
    t_eff = t
    if p.faults_on:
        # hot-spare migration: a stimulus addressed to a dead GMN
        # re-homes to the min_search takeover manager through one
        # redirect hop.  Alive everywhere: g unchanged, zero-cost.
        g0 = g
        g = _takeover(st, p, g)
        rehomed = g != g0
        t_eff, gbus_r, lbus_r, lat_r = T.unicast(
            p.topology, g0, g, t, rehomed, gbus=st["gbus_free"],
            lbus=st["lbus_free"], c_b=p.c_b, c_hop=p.c_hop, hops=p.hops)
        st["gbus_free"], st["lbus_free"] = gbus_r, lbus_r
        st["reroutes"] = st["reroutes"] + jnp.where(rehomed, 1, 0)
        st["mgmt_msgs"] = st["mgmt_msgs"] + jnp.where(rehomed, 1, 0)
        st["mgmt_latency"] = st["mgmt_latency"] + lat_r
        st = _hist_add(st, p, "th_mgmt", lat_r, rehomed)

    # GMN compute: the critical path of the binary fork tree does
    # 2 stage-1 decisions per level (paper Eqn 3: log(n) * Omega_s(k)).
    t_cpu = jnp.maximum(t_eff, st["gmn_free"][g])
    t_tree = t_cpu + 2.0 * depth * p.sel_global
    st["gmn_free"] = _set1(st["gmn_free"], g, t_tree)

    # own cluster count is exact (local data structure); remote via beacons
    own_view = _set1(st["view"][g], g, st["loads"][g].sum())
    # beacon ages feed the staleness-aware policies; own entry always fresh
    age = _set1(jnp.maximum(t_eff - st["view_t"][g], 0.0), g, 0.0)
    # stage-1 cluster choice is the statically selected MappingPolicy
    # (core/policies.py); min_search reproduces the historical inline rule
    # bitwise (min over the view, ties from the GMN's own index)
    pick_cluster = P.mapping_policy(p.policy.mapping)
    rr0 = st["rr_ptr"][g]
    if p.faults_on:
        up_row = st["link_up"][g]

    def pick(carry, i):
        view, st_gbus, st_lbus, rr = carry
        # deciders act on DISTRIBUTED knowledge only — the (possibly
        # stale) beacon views and the local failure detector — never a
        # ground-truth liveness oracle (DESIGN.md §15).  A spawn sent to
        # a dead cluster re-homes at delivery via the min_search
        # takeover; the detector-aware policies exist to avoid paying
        # that detour in the first place.
        view_pick = view
        c = pick_cluster(view_pick, age, g, rr, app, i, k=p.k, T_b=p.T_b,
                         susp_mult=p.susp_mult)
        cnt = share + jnp.where(i < rem, 1, 0)
        new_view = _add1(view, c, cnt)             # optimistic local bookkeeping
        # task-start message through the fabric (core/transport.py); a
        # self-targeted spawn is a local operation and skips it entirely
        is_remote = c != g
        t_arr, st_gbus, st_lbus, lat = T.unicast(
            p.topology, g, c, t_tree, is_remote, gbus=st_gbus, lbus=st_lbus,
            c_b=p.c_b, c_hop=p.c_hop, hops=p.hops)
        outs = (c, cnt, t_arr, lat, is_remote, view_pick)
        if p.faults_on:
            # reliable task-start: a down (g, c) link detours (never
            # drops); all-up the penalty is exactly 0.0
            pen = T.link_penalty(p.topology, up_row[c], is_remote,
                                 c_b=p.c_b, c_hop=p.c_hop)
            outs = (c, cnt, t_arr + pen, lat + pen, is_remote, view_pick,
                    jnp.logical_and(is_remote, up_row[c] == 0))
        return (new_view, st_gbus, st_lbus, rr + 1), outs

    (new_view, gbus, lbus, rr_out), ys \
        = jax.lax.scan(pick, (own_view, st["gbus_free"], st["lbus_free"],
                              rr0), jnp.arange(ns))
    if p.faults_on:
        cs, cnts, t_arrs, lats, remotes, views, detours = ys
        st["reroutes"] = st["reroutes"] + jnp.sum(detours).astype(jnp.int32)
    else:
        cs, cnts, t_arrs, lats, remotes, views = ys
    # st["view"] row g <- new_view is deferred to the staged record (a
    # branch-region write into the (k, k) matrix copies it wholesale)
    st["rr_ptr"] = _set1(st["rr_ptr"], g, rr_out)
    st["gbus_free"] = gbus
    st["lbus_free"] = lbus
    st["mgmt_msgs"] = st["mgmt_msgs"] + jnp.sum(remotes).astype(jnp.int32)
    st["mgmt_latency"] = st["mgmt_latency"] + jnp.sum(lats)
    st = _hist_add(st, p, "th_mgmt", lats, remotes)
    st["mgmt_proc"] = st["mgmt_proc"] + (t_tree - t_eff)
    st["app_remaining"] = _set1(st["app_remaining"], app, n)
    st["app_arrive"] = _set1(st["app_arrive"], app, t)
    if p.record_s1:
        # per-decision inputs/outputs for serving/replay.py: the (possibly
        # stale) view each decision saw, the shared age vector, the chosen
        # cluster, and the round-robin pointer before the fork
        st["dec_view"] = _set1(st["dec_view"], app, views)
        st["dec_age"] = _set1(st["dec_age"], app, age)
        st["dec_choice"] = _set1(st["dec_choice"], app, cs)
        st["dec_rr0"] = _set1(st["dec_rr0"], app, rr0)
        st["dec_t"] = _set1(st["dec_t"], app, t)
        if p.faults_on:
            # the effective decider (post-takeover) for replay
            st["dec_gmn"] = _set1(st["dec_gmn"], app, g)

    return st, _staged(p, None, jnp.ones((ns,), bool), t_arrs,
                       EV_LOCAL_SPAWN, jnp.full((ns,), app), cs, cnts,
                       vrow_on=jnp.ones((), bool),
                       vrow_i=jnp.asarray(g, jnp.int32), vrow=new_view)


def _spawn_group_bound(p) -> int:
    """Static upper bound on childs per LOCAL_SPAWN group: _handle_arrive
    hands each of its ns targets share or share+1 childs."""
    n, ns = p.n_childs, p.ns
    share = n // ns
    return min(n, share + (1 if n - share * ns > 0 else 0))


def _handle_local_spawn(st, p, t, app, g, cnt, lengths):
    """Stage 2: GMN g maps cnt childs onto its PEs (exact local view).
    Intra-cluster task-starts ride the cluster's local bus — except under
    the ``shared_bus`` topology, where every management message contends
    on the single flat bus."""
    mpk = p.mpk
    n_max = _spawn_group_bound(p)   # static; cnt <= n_max always
    shared = p.topology.kind == "shared_bus"
    st = dict(st)
    t_eff = t
    if p.faults_on:
        # hot-spare migration: a spawn group delivered to a dead GMN
        # re-homes (tasks AND management) to the min_search takeover
        # cluster through one redirect hop
        g0 = g
        g = _takeover(st, p, g)
        rehomed = g != g0
        t_eff, gbus_r, lbus_r, lat_r = T.unicast(
            p.topology, g0, g, t, rehomed, gbus=st["gbus_free"],
            lbus=st["lbus_free"], c_b=p.c_b, c_hop=p.c_hop, hops=p.hops)
        st["gbus_free"], st["lbus_free"] = gbus_r, lbus_r
        st["reroutes"] = st["reroutes"] + jnp.where(rehomed, 1, 0)
        st["mgmt_msgs"] = st["mgmt_msgs"] + jnp.where(rehomed, 1, 0)
        st["mgmt_latency"] = st["mgmt_latency"] + lat_r
        st = _hist_add(st, p, "th_mgmt", lat_r, rehomed)

    def spawn(carry, i):
        t_cpu, bus, pe_free, loads = carry
        active = i < cnt
        t_cpu = t_cpu + jnp.where(active, p.sel_local, 0.0)
        pe = jnp.argmin(loads)                     # stage-2 min-search
        # task-start over the (local or shared) bus
        t_msg = jnp.maximum(t_cpu, bus) + p.c_b
        bus = jnp.where(active, t_msg, bus)
        start = jnp.maximum(t_msg, pe_free[pe])
        ln = lengths[app, i]
        finish = start + ln
        pe_free = jnp.where(active, _set1(pe_free, pe, finish), pe_free)
        loads = jnp.where(active, _add1(loads, pe, 1), loads)
        return (t_cpu, bus, pe_free, loads), \
            (pe, finish, active, jnp.where(active, t_msg - t_cpu, 0.0))

    t0 = jnp.maximum(t_eff, st["gmn_free"][g])
    bus0 = st["gbus_free"] if shared else st["lbus_free"][g]
    (t_cpu, bus, pe_free, loads), (pes, finishes, actives, lats) = \
        jax.lax.scan(spawn, (t0, bus0, st["pe_free"][g], st["loads"][g]),
                     jnp.arange(n_max))
    st["gmn_free"] = _set1(st["gmn_free"], g, t_cpu)
    if shared:
        st["gbus_free"] = bus
    else:
        st["lbus_free"] = _set1(st["lbus_free"], g, bus)
    st["pe_free"] = _set1(st["pe_free"], g, pe_free)
    st["loads"] = _set1(st["loads"], g, loads)
    st["mgmt_msgs"] = st["mgmt_msgs"] + jnp.sum(actives).astype(jnp.int32)
    st["mgmt_latency"] = st["mgmt_latency"] + jnp.sum(lats)
    st = _hist_add(st, p, "th_mgmt", lats, actives)
    st["mgmt_proc"] = st["mgmt_proc"] + (t_cpu - t_eff)

    st, fan = _maybe_beacon(st, p, g, t_cpu)

    return st, _staged(p, fan, actives, finishes, EV_JOIN_EXIT,
                       jnp.full((n_max,), app), jnp.full((n_max,), g), pes)


def _handle_join_exit(st, p, t, app, g, pe, lengths, parent_gmns):
    st = dict(st)
    shared = p.topology.kind == "shared_bus"
    # join-exit message over the bus of the child's cluster (the single
    # shared bus under shared_bus)
    if shared:
        t_msg = jnp.maximum(t, st["gbus_free"]) + p.c_b
        st["gbus_free"] = t_msg
    else:
        t_msg = jnp.maximum(t, st["lbus_free"][g]) + p.c_b
        st["lbus_free"] = _set1(st["lbus_free"], g, t_msg)
    st["loads"] = _add2(st["loads"], g, pe, -1)
    st["mgmt_msgs"] = st["mgmt_msgs"] + 1
    st["mgmt_latency"] = st["mgmt_latency"] + (t_msg - t)
    st = _hist_add(st, p, "th_mgmt", t_msg - t, jnp.ones((), bool))
    st, fan = _maybe_beacon(st, p, g, t_msg)
    # the join barrier lives at the application's arrival GMN: remote
    # join-exits forward through the fabric (Tab 2 / Sec 4)
    pg = parent_gmns[app]
    if p.faults_on:
        # the barrier re-homes with its manager (min_search takeover)
        pg0 = pg
        pg = _takeover(st, p, pg)
        st["reroutes"] = st["reroutes"] + jnp.where(pg != pg0, 1, 0)
    remote = pg != g
    t_fwd, gbus, lbus, lat = T.forward(
        p.topology, g, pg, t_msg, remote, gbus=st["gbus_free"],
        lbus=st["lbus_free"], c_b=p.c_b, c_hop=p.c_hop, hops=p.hops)
    if p.faults_on:
        # reliable join-exit forward: a down (g, pg) link detours
        pen = T.link_penalty(p.topology, st["link_up"][g, pg], remote,
                             c_b=p.c_b, c_hop=p.c_hop)
        t_fwd = t_fwd + pen
        lat = lat + pen
        st["reroutes"] = st["reroutes"] + jnp.where(
            jnp.logical_and(remote, st["link_up"][g, pg] == 0), 1, 0)
    st["gbus_free"], st["lbus_free"] = gbus, lbus
    st["mgmt_msgs"] = st["mgmt_msgs"] + jnp.where(remote, 1, 0)
    st["mgmt_latency"] = st["mgmt_latency"] + lat
    st = _hist_add(st, p, "th_mgmt", lat, remote)
    t_bar = jnp.maximum(t_fwd, st["gmn_free"][pg]) + p.c_join
    st["mgmt_proc"] = st["mgmt_proc"] + (t_bar - t_fwd)
    st["gmn_free"] = _set1(st["gmn_free"], pg, t_bar)
    rem = st["app_remaining"][app] - 1
    st["app_remaining"] = _set1(st["app_remaining"], app, rem)
    st["app_done"] = jnp.where(
        rem == 0, _set1(st["app_done"], app, t_bar), st["app_done"])
    # per-app response sample at the completing barrier decrement:
    # mass(th_resp) == completed-apps count, exactly
    st = _hist_add(st, p, "th_resp", t_bar - st["app_arrive"][app],
                   rem == 0)
    z = jnp.zeros((0,), jnp.float32)
    return st, _staged(p, fan, jnp.zeros((0,), bool), z, EV_JOIN_EXIT,
                       z, z, z)


def _takeover(st, p, g):
    """Hot-spare manager migration (Bosch-style takeover): management
    work addressed to a dead GMN re-homes to its ring successor — the
    first *live* GMN among g+1, g+2, ... (mod k).  Alive GMNs keep
    their own work.

    Deliberately NOT a least-loaded search: the supervisor performing
    the redirect knows liveness, not global load (a ground-truth
    least-loaded takeover would be a placement oracle that makes
    misdirecting work at dead managers nearly free — exactly the
    knowledge the stage-1 policies are being measured on, DESIGN.md
    §15).  Ring failover piles a dead manager's redirected work onto
    one successor, so policies that keep addressing the dead pay for
    it.  (If every GMN were dead the search degenerately returns g;
    the FaultSpec generators never kill GMN 0, see core/faults.py.)"""
    ring = (g + jnp.arange(p.k, dtype=jnp.int32)) % p.k
    alive = st["gmn_alive"][ring] > 0
    return ring[jnp.argmax(alive).astype(jnp.int32)]


def _handle_link_down(st, p, t, i, j):
    """LINK_DOWN(i, j): the directed (i, j) fabric link drops.
    Idempotent — a DOWN on an already-down link keeps the original
    outage start (overlapping failures merge, core/faults.py)."""
    st = dict(st)
    was_up = st["link_up"][i, j] > 0
    st["link_down_t"] = st["link_down_t"].at[i, j].set(
        jnp.where(was_up, t, st["link_down_t"][i, j]))
    st["link_up"] = st["link_up"].at[i, j].set(0.0)
    return st


def _handle_link_up(st, p, t, i, j):
    """LINK_UP(i, j): the link heals; the completed outage duration
    lands in the ``downtime`` counter."""
    st = dict(st)
    was_down = st["link_up"][i, j] == 0
    st["downtime"] = st["downtime"] + jnp.where(
        was_down, t - st["link_down_t"][i, j], 0.0)
    st["link_up"] = st["link_up"].at[i, j].set(1.0)
    return st


def _handle_gmn_fail(st, p, t, g):
    """GMN_FAIL(g): manager g dies.  Pending work re-homes lazily — each
    queued event addressed to g runs ``_takeover`` when it pops, so no
    queue surgery is needed and the re-home pays its redirect cost at
    the time the work actually moves."""
    st = dict(st)
    was_alive = st["gmn_alive"][g] > 0
    st["gmn_down_t"] = st["gmn_down_t"].at[g].set(
        jnp.where(was_alive, t, st["gmn_down_t"][g]))
    st["gmn_alive"] = st["gmn_alive"].at[g].set(0.0)
    return st


def _handle_gmn_heal(st, p, t, g):
    """GMN_HEAL(g): manager g recovers and announces its rejoin with an
    unconditional beacon (DESIGN.md §15) — peers' views/suspicion of g
    refresh at delivery rather than waiting for g's next workload-driven
    broadcast (a recovered idle manager would otherwise stay suspected
    indefinitely).  The announcement rides the normal beacon accounting,
    so the conservation identity and the frozen goldens (no heal events
    under FaultSpec.none()) are untouched."""
    st = dict(st)
    was_dead = st["gmn_alive"][g] == 0
    st["downtime"] = st["downtime"] + jnp.where(
        was_dead, t - st["gmn_down_t"][g], 0.0)
    st["gmn_alive"] = st["gmn_alive"].at[g].set(1.0)
    # detector epoch reset: the rejoining manager restarts its failure-
    # detector timers rather than trusting pre-crash receipt times (its
    # mapping-policy staleness ages stay honest — see make_state)
    st["det_floor"] = jnp.where(
        was_dead, _set1(st["det_floor"], g, t), st["det_floor"])
    announce = jnp.logical_and(was_dead, p.k > 1)
    st, fan = _fire_beacon(st, p, g, t, announce, st["loads"][g].sum())
    z = jnp.zeros((0,), jnp.float32)
    return st, _staged(p, fan, jnp.zeros((0,), bool), z, 0.0, z, z, z)


def _handle_heartbeat(st, p, t, g, sim_len):
    """EV_HEARTBEAT(g): timer-driven status broadcast (beacon
    "heartbeat", DESIGN.md §15).  Fires the shared beacon path under the
    periodic due-rule — a broadcast the activity plane already made
    within the last T_b suppresses this one — then re-schedules itself
    at t + T_b while that stays inside the horizon, so the chain (and
    the simulation) terminates."""
    load_g = st["loads"][g].sum()
    due = P.beacon_policy("heartbeat")(
        jnp.abs(load_g - st["last_bcast"][g]), t, st["last_bcast_t"][g],
        dn_th=p.dn_th, T_b=p.T_b)
    st, fan = _fire_beacon(st, p, g, t, jnp.logical_and(due, p.k > 1),
                           load_g)
    nxt = t + p.T_b
    return st, _staged(p, fan, jnp.asarray(nxt < sim_len)[None],
                       jnp.asarray(nxt)[None], EV_HEARTBEAT,
                       jnp.asarray(g, jnp.float32)[None],
                       jnp.zeros((1,), jnp.float32),
                       jnp.zeros((1,), jnp.float32))


def _push_faults(st, p, f, sim_len):
    """Seed the event queue with the fault schedule, grouped by kind in
    LINK_DOWN, LINK_UP, GMN_FAIL, GMN_HEAL order (after the arrivals) —
    a deterministic slot assignment, so same-tick ties between fault
    and work events break identically on every run and queue impl."""
    if f.times.shape[0] == 0:
        return st
    live = f.times < sim_len
    zeros = jnp.zeros_like(f.a0)
    for kind in range(4):
        st = _bulk_push(st, p, jnp.logical_and(live, f.kinds == kind),
                        f.times, EV_LINK_DOWN + kind, f.a0, f.a1, zeros)
    return st


def simulate(shape: SimShape, knobs: SimKnobs, arrivals, arrival_gmns,
             lengths, sim_len, policy: SimPolicy = DEFAULT_POLICY,
             topology: Topology = DEFAULT_TOPOLOGY,
             faults: FLT.FaultSchedule | None = None,
             trace: TR.TraceSpec | None = None):
    """Traceable core: static ``shape``, ``policy``, ``topology`` and
    ``trace``, traced everything else.  This is what
    ``repro.core.sweep`` vmaps over knob/workload batches (one XLA
    program per (shape, policy, topology, trace) combination;
    ``trace=None`` compiles the uninstrumented program)."""
    p = _Ctx(shape, knobs, policy, topology, faults_on=faults is not None,
             trace=trace)
    with jax.named_scope("sim.setup"):
        st = make_state(p)

        n_apps = arrivals.shape[0]
        st = _bulk_push(st, p, arrivals < sim_len, arrivals, EV_ARRIVE,
                        jnp.arange(n_apps), arrival_gmns,
                        jnp.zeros((n_apps,), jnp.int32))
        if faults is not None:
            st = _push_faults(st, p, faults, sim_len)
        hb_on = policy.beacon == "heartbeat"
        if hb_on and p.k > 1:
            # timer-driven beacon plane: one self-rescheduling
            # EV_HEARTBEAT chain per GMN, first tick at T_b (a static
            # policy value, so the frozen activity-driven programs never
            # compile this)
            hb_t = jnp.broadcast_to(jnp.asarray(p.T_b, jnp.float32),
                                    (p.k,))
            st = _bulk_push(st, p, hb_t < sim_len, hb_t, EV_HEARTBEAT,
                            jnp.arange(p.k), jnp.zeros((p.k,), jnp.int32),
                            jnp.zeros((p.k,), jnp.int32))
        # live-entry counter seed: everything accepted so far (the
        # counter is pure telemetry — no simulation value reads it)
        seeded = jnp.sum(arrivals < sim_len).astype(jnp.int32)
        if faults is not None and faults.times.shape[0]:
            seeded = seeded + jnp.sum(faults.times < sim_len) \
                .astype(jnp.int32)
        if hb_on and p.k > 1:
            seeded = seeded + jnp.sum(hb_t < sim_len).astype(jnp.int32)
        st["evq_len"] = seeded - st["dropped"]
        st["evq_peak"] = st["evq_len"]

    cond = p.queue.live

    # Every branch returns (state, staged record) — the queue pushes and
    # big-matrix writes happen AFTER the switch (see the fused-commit
    # staging block above): a scatter into a big buffer inside a branch
    # region forces XLA:CPU to copy the whole buffer every iteration.
    # (scope name, handler) per event type; the body runs each handler
    # under the named scope "sim.handlers.<name>", and the structural
    # placeholders (name None) under none.
    none = (None, lambda s, t, a: (s, _stage_none(p)))
    branches = [
        ("arrive", lambda s, t, a: _handle_arrive(s, p, t, a[0], a[1], a[2],
                                                  lengths)),
        ("local_spawn", lambda s, t, a: _handle_local_spawn(
            s, p, t, a[0], a[1], a[2], lengths)),
        ("join_exit", lambda s, t, a: _handle_join_exit(
            s, p, t, a[0], a[1], a[2], lengths, arrival_gmns)),
    ]
    rx_on = topology.kind != "ideal"
    if rx_on or p.faults_on or hb_on:
        # BEACON_RX exists only on the non-ideal fabrics, and is handled
        # vectorized OUTSIDE the switch (_handle_beacon_rx_batch) so same-
        # timestamp deliveries batch; slot 3 stays a structural placeholder
        # keeping the fault event types fixed at 4..7 even under ideal.
        branches.append(none)
    if p.faults_on:
        branches += [
            ("link_down", lambda s, t, a: (
                _handle_link_down(s, p, t, a[0], a[1]), _stage_none(p))),
            ("link_up", lambda s, t, a: (
                _handle_link_up(s, p, t, a[0], a[1]), _stage_none(p))),
            ("gmn_fail", lambda s, t, a: (
                _handle_gmn_fail(s, p, t, a[0]), _stage_none(p))),
            ("gmn_heal", lambda s, t, a: _handle_gmn_heal(s, p, t, a[0])),
        ]
    elif hb_on:
        # fault slots 4..7 as structural placeholders so EV_HEARTBEAT
        # keeps its fixed index 8 on fault-free heartbeat programs
        branches += [none] * 4
    if hb_on:
        branches.append(("heartbeat", lambda s, t, a: _handle_heartbeat(
            s, p, t, a[0], sim_len)))

    bp = p.batch_pop if rx_on else 1    # only BEACON_RX events batch

    def body(st):
        with jax.named_scope("sim.pop"):
            t, slot, typ, a = p.queue.root(st)

            # -- same-timestamp BEACON_RX batch selection ---------------------
            if bp > 1:
                # The O(Q) cohort scan AND the lane payload gathers live
                # inside the cond: non-RX iterations skip them, and the
                # conditional boundary materializes the results so no
                # consumer re-reads the queue buffer later in the body
                # (reads in a cond branch are safe — only scatters force
                # branch-region buffer copies).
                def _rx_cohort():
                    lt, lpay = p.queue.leaves(st)
                    sel, ok = EQ.batch_take(lt, lpay[:, 0], t, slot,
                                            EV_BEACON_RX, bp)
                    return sel, ok, lpay[sel].astype(jnp.int32)

                def _singleton():
                    lanes = jnp.zeros((bp, 4), jnp.int32) \
                        .at[0].set(jnp.concatenate([typ[None], a]))
                    return (jnp.zeros((bp,), jnp.int32).at[0].set(slot),
                            jnp.arange(bp) < 1, lanes)

                slots, okl, lanes = jax.lax.cond(
                    typ == EV_BEACON_RX, _rx_cohort, _singleton)
                lane_typ, la0, la1, la2 = (lanes[:, 0], lanes[:, 1],
                                           lanes[:, 2], lanes[:, 3])
            else:
                slots = slot[None]
                okl = jnp.ones((1,), bool)
                lane_typ, la0, la1, la2 = (typ[None], a[0][None], a[1][None],
                                           a[2][None])

            st = dict(st)
            # queue high-water mark, sampled BEFORE the pop: every batch_pop
            # grouping and queue impl visits this point with the same value
            # (intermediate singleton iterations inside a same-timestamp
            # cohort only ever see smaller queues), so the counter stays
            # bitwise-invariant across the head-to-head gates
            st["evq_peak"] = jnp.maximum(st["evq_peak"], st["evq_len"])
            ml0 = st["mgmt_latency"]        # per-iteration delta -> ring lat
            st["events_processed"] = st["events_processed"] + \
                jnp.sum(okl).astype(jnp.int32)
            st["iterations"] = st["iterations"] + 1
            st["rx_trips"] = st["rx_trips"] + \
                (typ == EV_BEACON_RX).astype(jnp.int32)

        # -- handlers -----------------------------------------------------
        if rx_on or p.faults_on:
            # straight-line vectorized RX; exact no-op on non-RX roots.
            # The fault program compiles it in even under "ideal": retry
            # re-beacons (DESIGN.md §15) are BEACON_RX events on every
            # fabric (none exist when retries are off — bitwise no-op).
            with jax.named_scope("sim.rx"):
                st = _handle_beacon_rx_batch(st, p, t, okl, lane_typ,
                                             la0, la1, la2)
        if p.faults_on:
            # failure detector (DESIGN.md §15): refresh the traced
            # (k, k) suspicion matrix at every event pop, after beacon
            # delivery — GMN g suspects peer c once c's summary is
            # older than susp_mult * T_b (the own entry never).  Whole-
            # matrix straight-line recompute, never a branch-region
            # scatter; per-pair onset/clear counters and false-positive
            # accounting run against the ground-truth masks.  Compiled
            # only into the fault-aware program and written only to its
            # own leaves, so every pre-detector golden stays bitwise.
            with jax.named_scope("sim.detector"):
                peers = jnp.logical_not(jnp.eye(p.k, dtype=bool))
                # receipt clocks floored at the suspector's detector epoch
                # (rejoin reset — see det_floor in make_state); a dead
                # manager runs no detector, so its whole row is masked.
                # Both gates are exact no-ops on an all-up fabric.
                seen = jnp.maximum(st["view_t"], st["det_floor"][:, None])
                sus = jnp.logical_and((t - seen) > p.susp_mult * p.T_b, peers)
                sus = jnp.logical_and(sus, st["gmn_alive"][:, None] > 0)
                # the detector is defined only on the observation window
                # [0, sim_len): both beacon planes stop generating traffic
                # at sim_len while the event queue drains its workload tail
                # (possibly far) past it, and staleness measured against a
                # deliberately silenced fabric would manufacture false
                # positives.  Freeze the matrix at its end-of-window value.
                sus = jnp.where(t < sim_len, sus, st["suspect"] > 0)
                prev = st["suspect"] > 0
                onset = jnp.logical_and(sus, jnp.logical_not(prev))
                clear = jnp.logical_and(prev, jnp.logical_not(sus))
                # ground truth: peer c is *actually fine* from g's
                # standpoint when c is alive and the (c -> g) beacon
                # direction is up — a suspicion onset against a fine peer
                # is a false positive
                truth_ok = jnp.logical_and(st["gmn_alive"][None, :] > 0,
                                           jnp.transpose(st["link_up"]) > 0)
                st["suspect"] = sus.astype(jnp.float32)
                st["susp_onsets"] = st["susp_onsets"] + onset.astype(jnp.int32)
                st["susp_clears"] = st["susp_clears"] + clear.astype(jnp.int32)
                st["susp_false_pos"] = st["susp_false_pos"] + jnp.sum(
                    jnp.logical_and(onset, truth_ok)).astype(jnp.int32)
        # Hold the big read-only buffers OUT of the switch outputs: a
        # buffer that is both operand and output of a conditional pays a
        # full copy in the executed branch even when no branch writes it.
        # Branches still receive them (reads are free); the originals are
        # reattached after.  Queue arrays are never touched in-branch;
        # the (k, k) matrices only under "ideal" (whose atomic beacon
        # writes columns in-branch — the golden program, not the perf
        # target).  Fault-state arrays stay in the outputs: the fault
        # handlers write them in-branch (rare event types).
        held_keys = list(p.queue.held)
        if rx_on:
            held_keys += ["view", "view_t", "bcn_t"]
        if p.faults_on:
            # detector leaves are written straight-line only (above) —
            # no branch touches them, so they ride outside the switch.
            # retries_tx is NOT held: the beacon paths add to it
            # in-branch.
            held_keys += ["suspect", "susp_onsets", "susp_clears",
                          "susp_false_pos"]
        if p.trace is not None:
            # ring/timeline buffers are written straight-line at the end
            # of the body only — never in a branch (the histograms ARE
            # branch-written, and small; they stay in the outputs)
            held_keys += ["tr_ring", "tl_t", "tl_busy", "tl_stale",
                          "tl_load", "tl_qdepth"]
        held = {kk: st[kk] for kk in held_keys if kk in st}

        def wrap(name, b):
            def f(s):
                with (jax.named_scope(f"sim.handlers.{name}") if name
                      else contextlib.nullcontext()):
                    s2, stg2 = b(s, t, a)
                return {k2: v for k2, v in s2.items()
                        if k2 not in held}, stg2
            return f

        with jax.named_scope("sim.handlers"):
            out, stg = jax.lax.switch(typ, [wrap(*b) for b in branches], st)
            st = {**out, **held}
            st = _apply_staged(st, p, stg)

        # -- fused end-of-body commit: pop(s) + all pushes ----------------
        with jax.named_scope("sim.commit"):
            pm, pt = stg["push_mask"], stg["push_t"]
            pty = stg["push_typ"]
            pa0, pa1, pa2 = stg["push_a0"], stg["push_a1"], stg["push_a2"]
            d0 = st["dropped"]
            st = p.queue.commit(st, slots, okl, pm, pt, pty, pa0, pa1, pa2)
            # live-entry accounting: accepted pushes minus retired pops
            accepted = jnp.sum(pm).astype(jnp.int32) - (st["dropped"] - d0)
            st["evq_len"] = st["evq_len"] + accepted \
                - jnp.sum(okl).astype(jnp.int32)
        if p.trace is not None:
            # straight-line end-of-body instrumentation (DESIGN.md §14):
            # ring append next to the fused commit, then at most one
            # timeline row on the events_processed stride
            with jax.named_scope("sim.trace_ring"):
                st = TR.ring_commit(st, p.trace, t, okl, slots, lane_typ,
                                    la0, la1, st["mgmt_latency"] - ml0)
                st = TR.timeline_sample(st, p.trace, t)
        return st

    return jax.lax.while_loop(cond, body, st)


_run = jax.jit(simulate, static_argnums=(0, 6, 7, 9))


def run(p: SimParams, arrivals, arrival_gmns, lengths, sim_len: float = 1e7,
        faults=None, trace: TraceSpec | None = None):
    """arrivals (A,) f32 times (INF = unused); arrival_gmns (A,) i32;
    lengths (A, n_childs) f32 child task lengths.

    Returns final state dict (response times = app_done - app_arrive).
    Compiles once per ``(p.shape, p.policy, p.topo)``; the numeric knobs
    (c_b, c_s, c_join, dn_th, T_b, c_hop, susp_mult, retry_after) and
    sim_len are traced, so threshold/cost/period/detector sweeps re-use
    the compiled program.

    ``faults`` is an optional ``FaultSpec`` or prebuilt ``FaultSchedule``
    (repro.core.faults).  The schedule is a *traced* pytree: swapping
    schedules of the same length (a fault seed/intensity grid) re-uses
    the compiled fault-aware program; only passing None vs a schedule —
    or changing the schedule length — compiles a new one.

    ``trace`` is an optional ``TraceSpec`` (repro.core.trace, DESIGN.md
    §14).  It is a *static* argument like shape and policy: None (the
    default) compiles the uninstrumented program bitwise-identical to
    the pre-trace goldens; a fixed spec across a knob/seed grid re-uses
    one instrumented program.
    """
    return _run(p.shape, p.knobs,
                jnp.asarray(arrivals, jnp.float32),
                jnp.asarray(arrival_gmns, jnp.int32),
                jnp.asarray(lengths, jnp.float32),
                jnp.float32(sim_len), p.policy, p.topo,
                FLT.as_schedule(faults, p.k, sim_len), trace)


def compile_cache_size() -> int:
    """Number of XLA programs compiled for ``run`` (one per
    (SimShape, SimPolicy, Topology) triple), read from jit's private
    cache introspection (the installed JAX has it; the no-recompile
    tests fail loudly if a later one drops it)."""
    return _run._cache_size()


# --------------------------------------------------------------------------
# Metrics — single implementation in repro.core.metrics, re-exported here
# (and from repro.core.sweep); shape-polymorphic over any leading batch
# axes.  speedup(state, lengths) returns the masked mean per point; the
# completion count is `response_times(state)[1].sum()`.
# --------------------------------------------------------------------------

from repro.core.metrics import (beacons, beacons_rx,  # noqa: E402,F401
                                evq_peak, mean_response, mgmt_latency,
                                mgmt_msgs, mgmt_proc, response_times,
                                speedup, trace_dropped)
