"""Plain reference of the clustered task manager on a grid fabric.

The semantics of ``bench/reference.py`` (arXiv:1502.02852, Sec 4-5, with
the repository's documented deviations: k global management nodes over m
processing elements, two-stage task mapping, threshold status beacons,
join barriers), with the hierarchical bus replaced by one of the two
grid fabrics:

  ``mesh2d``   GMNs row-major on a ``side``-wide grid, side the smallest
               whole number whose square holds k; hops are the Manhattan
               distance.
  ``torus2d``  the same placement with each dimension wrapped at its own
               length (rows = ceil(k / side), columns = side), so a hop
               count is ``min(|dx|, rows - |dx|) + min(|dy|, side - |dy|)``
               (Kalray MPPA-256's 2D-torus network-on-chip).

On both, a message between GMNs serializes on the source's port (its
local bus, ``lbus[src]`` plus ``c_b``) and then arrives ``hops x c_hop``
later, touching no other bus.  A beacon is one such injection at
``lbus[g]``, then one arrival per receiver at ``t_inj + hops[g] x c_hop``.

It imports nothing of the program.  One event is handled at a time from a
binary heap ordered by (time, slot); slots are allocated lowest-free-first,
the event queue's documented tie-breaking contract.  Times are float32
scalars, computed in the same order of operations as the model defines
them, so every time and counter is exact; only ``mgmt_latency``, a sum of
vectors whose reduction order is the compiler's, may differ in its last
bits.

Supported: the ``mesh2d`` and ``torus2d`` fabrics, ``min_search``
mapping, ``threshold`` beacons, no fault schedule.  Anything else raises
``NotImplementedError``.
"""
from __future__ import annotations

import heapq
import math

import numpy as np

F32 = np.float32
INF = F32(1e18)
EV_ARRIVE, EV_SPAWN, EV_JOIN, EV_RX = 0, 1, 2, 3
FABRICS = ("mesh2d", "torus2d")

# leaves whose value depends on the order in which a vector is summed
ORDER_DEPENDENT = ("mgmt_latency",)


def _check_supported(cfg: dict, faults) -> None:
    if cfg["topology"] not in FABRICS:
        raise NotImplementedError(f"reference models topology in {FABRICS}, "
                                  f"not {cfg['topology']!r}")
    want = {"mapping": "min_search", "beacon": "threshold"}
    for key, val in want.items():
        if cfg[key] != val:
            raise NotImplementedError(f"reference models {key}={val!r}, "
                                      f"not {cfg[key]!r}")
    if faults is not None:
        raise NotImplementedError("reference models no fault schedule")


def hops(fabric: str, k: int) -> np.ndarray:
    """(k, k) hop counts between GMNs of a grid fabric."""
    side = math.isqrt(k - 1) + 1 if k > 1 else 1
    rows = -(-k // side)
    pos = np.arange(k)
    dx = np.abs(pos[:, None] // side - pos[None, :] // side)
    dy = np.abs(pos[:, None] % side - pos[None, :] % side)
    if fabric == "torus2d":
        dx, dy = np.minimum(dx, rows - dx), np.minimum(dy, side - dy)
    return (dx + dy).astype(np.int32)


def _log2_levels(v: int) -> float:
    return float(np.log2(v)) if v > 1 else 0.0


class _Queue:
    """Bounded event queue: pop the (time, slot)-least entry, push into
    the lowest free slot, count what does not fit as dropped."""

    def __init__(self, cap: int):
        self.heap = []
        self.free = list(range(cap))          # a heap of free slot indices
        self.dropped = 0

    def push(self, t, typ, a0, a1, a2) -> bool:
        if not self.free:
            self.dropped += 1
            return False
        slot = heapq.heappop(self.free)
        heapq.heappush(self.heap, (float(t), slot, typ, a0, a1, a2))
        return True

    def pop(self):
        ev = heapq.heappop(self.heap)
        heapq.heappush(self.free, ev[1])
        return ev

    def peek(self):
        return self.heap[0] if self.heap else None


def simulate(cfg: dict, arrivals, gmns, lengths, sim_len, faults=None,
             queue_cap: int | None = None) -> dict:
    """Run one lane to the end and return its final state.

    ``cfg`` is the deployment and the lane's knobs (m, k, n_childs,
    max_apps, queue_cap, batch_pop, topology, policies, c_b, c_s, c_join,
    c_hop, dn_th); ``arrivals`` (A,) f32, ``gmns`` (A,) int, ``lengths``
    (A, n_childs) f32; ``faults`` must be None.  ``queue_cap`` overrides
    the configured capacity."""
    return simulate_counted(cfg, arrivals, gmns, lengths, sim_len, faults,
                            queue_cap)[0]


def simulate_counted(cfg: dict, arrivals, gmns, lengths, sim_len,
                     faults=None, queue_cap: int | None = None
                     ) -> tuple[dict, int]:
    """:func:`simulate`, and the count of loop trips whose popped event is
    a beacon delivery, which the final state does not hold."""
    _check_supported(cfg, faults)
    m, k = int(cfg["m"]), int(cfg["k"])
    mpk, n = m // k, int(cfg["n_childs"])
    A = int(cfg["max_apps"])
    Q = int(queue_cap or cfg["queue_cap"])
    bp = int(cfg["batch_pop"])
    dn_th = int(cfg["dn_th"])
    c_b, c_s, c_join = F32(cfg["c_b"]), F32(cfg["c_s"]), F32(cfg["c_join"])
    c_hop = F32(cfg["c_hop"])
    sim_len = F32(sim_len)
    hop_f = hops(cfg["topology"], k).astype(F32)
    ns = int(min(k, max(1, -(-n // mpk))))
    depth = int(math.ceil(math.log2(ns))) if ns > 1 else 0
    share, rem = n // ns, n - (n // ns) * ns
    n_max = min(n, share + (1 if rem > 0 else 0))
    sel_global = c_s * F32(_log2_levels(k))
    sel_local = c_s * F32(_log2_levels(mpk))
    tree_cost = F32(2.0 * depth) * sel_global
    arrivals = np.asarray(arrivals, F32)
    gmns = np.asarray(gmns, np.int64)
    lengths = np.asarray(lengths, F32)
    idx = np.arange(k)

    s = {
        "pe_free": np.zeros((k, mpk), F32), "gmn_free": np.zeros(k, F32),
        "gbus_free": F32(0), "lbus_free": np.zeros(k, F32),
        "loads": np.zeros((k, mpk), np.int32),
        "view": np.zeros((k, k), np.int32), "view_t": np.zeros((k, k), F32),
        "last_bcast": np.zeros(k, np.int32),
        "last_bcast_t": np.zeros(k, F32), "rr_ptr": np.zeros(k, np.int32),
        "beacons_tx": 0, "bcn_t": np.full((k, k), INF, F32),
        "beacons_rx": 0, "bcn_skew_sum": F32(0), "bcn_skew_max": F32(0),
        "mgmt_msgs": 0, "mgmt_latency": F32(0), "mgmt_proc": F32(0),
        "app_remaining": np.zeros(A, np.int32),
        "app_arrive": np.full(A, INF, F32), "app_done": np.full(A, INF, F32),
        "events_processed": 0, "evq_peak": 0, "iterations": 0,
    }
    rx_trips = 0

    q = _Queue(Q)
    for a in range(A):
        if arrivals[a] < sim_len:
            q.push(arrivals[a], EV_ARRIVE, a, int(gmns[a]), 0)
    evq_len = int(np.sum(arrivals < sim_len)) - q.dropped
    s["evq_peak"] = evq_len

    def add_latency(x):
        s["mgmt_latency"] = F32(s["mgmt_latency"] + F32(x))

    def inject(src, t_ready):
        """Serialize on the source's port; returns the injection time."""
        t_inj = F32(max(t_ready, s["lbus_free"][src]) + c_b)
        s["lbus_free"][src] = t_inj
        return t_inj

    def hop(src, dst, t_ready):
        """Injection at the source's port, then hops x c_hop."""
        return F32(inject(src, t_ready) + hop_f[src, dst] * c_hop)

    def fire_beacon(g, t, load_g, pushes):
        """Broadcast GMN g's load summary over the grid at t."""
        t_inj = inject(g, t)
        t_arr = (t_inj + hop_f[g] * c_hop).astype(F32)
        dlv = idx != g
        for i in np.flatnonzero(dlv):
            pushes.append((t_arr[i], EV_RX, g, int(i), int(load_g)))
        s["bcn_t"][g] = np.where(dlv, t_arr, s["bcn_t"][g])
        s["view"][g, g] = load_g
        s["view_t"][g, g] = t_inj
        s["last_bcast"][g] = load_g
        s["last_bcast_t"][g] = t_inj
        s["beacons_tx"] += 1
        s["mgmt_msgs"] += k - 1
        lat = F32(0)
        for i in np.flatnonzero(dlv):
            lat = F32(lat + F32(t_arr[i] - t))
        add_latency(lat)
        spread = F32(max(F32(t_arr[dlv].max() - t_arr[dlv].min()), F32(0)))
        s["bcn_skew_sum"] = F32(s["bcn_skew_sum"] + spread)
        s["bcn_skew_max"] = F32(max(s["bcn_skew_max"], spread))

    def maybe_beacon(g, t, pushes):
        load_g = int(s["loads"][g].sum())
        if abs(load_g - int(s["last_bcast"][g])) < dn_th or k <= 1:
            return
        fire_beacon(g, t, load_g, pushes)

    def arrive(t, app, g, pushes):
        t_tree = F32(max(t, s["gmn_free"][g]) + tree_cost)
        s["gmn_free"][g] = t_tree
        view = s["view"][g].copy()
        view[g] = s["loads"][g].sum()
        perm = (idx + g) % k
        lat = F32(0)
        for i in range(ns):
            c = int(perm[np.argmin(view[perm])])
            cnt = share + (1 if i < rem else 0)
            view[c] += cnt
            t_arr = t_tree
            if c != g:
                t_arr = hop(g, c, t_tree)
                s["mgmt_msgs"] += 1
                lat = F32(lat + F32(t_arr - t_tree))
            pushes.append((t_arr, EV_SPAWN, app, c, cnt))
        s["rr_ptr"][g] += ns
        add_latency(lat)
        s["mgmt_proc"] = F32(s["mgmt_proc"] + F32(t_tree - t))
        s["app_remaining"][app] = n
        s["app_arrive"][app] = t
        s["view"][g] = view

    def spawn(t, app, g, cnt, pushes):
        t_cpu = F32(max(t, s["gmn_free"][g]))
        bus = s["lbus_free"][g]
        pe_free, loads = s["pe_free"][g], s["loads"][g]
        lat = F32(0)
        started = []
        for i in range(min(cnt, n_max)):
            t_cpu = F32(t_cpu + sel_local)
            pe = int(np.argmin(loads))
            t_msg = F32(max(t_cpu, bus) + c_b)
            bus = t_msg
            finish = F32(max(t_msg, pe_free[pe]) + lengths[app, i])
            pe_free[pe] = finish
            loads[pe] += 1
            lat = F32(lat + F32(t_msg - t_cpu))
            started.append((finish, EV_JOIN, app, g, pe))
        s["gmn_free"][g] = t_cpu
        s["lbus_free"][g] = bus
        s["mgmt_msgs"] += len(started)
        add_latency(lat)
        s["mgmt_proc"] = F32(s["mgmt_proc"] + F32(t_cpu - t))
        maybe_beacon(g, t_cpu, pushes)
        pushes.extend(started)

    def join_exit(t, app, g, pe, pushes):
        t_msg = F32(max(t, s["lbus_free"][g]) + c_b)
        s["lbus_free"][g] = t_msg
        s["loads"][g, pe] -= 1
        s["mgmt_msgs"] += 1
        add_latency(t_msg - t)
        maybe_beacon(g, t_msg, pushes)
        pg = int(gmns[app])
        t_fwd = t_msg
        if pg != g:
            t_fwd = hop(g, pg, t_msg)
            s["mgmt_msgs"] += 1
            add_latency(t_fwd - t_msg)
        t_bar = F32(max(t_fwd, s["gmn_free"][pg]) + c_join)
        s["mgmt_proc"] = F32(s["mgmt_proc"] + F32(t_bar - t_fwd))
        s["gmn_free"][pg] = t_bar
        s["app_remaining"][app] -= 1
        if s["app_remaining"][app] == 0:
            s["app_done"][app] = t_bar

    def beacon_rx(t, src, rcv, load):
        if s["bcn_t"][src, rcv] == t:
            s["bcn_t"][src, rcv] = INF
        s["view"][rcv, src] = load
        s["view_t"][rcv, src] = t
        s["beacons_rx"] += 1

    while q.heap:
        s["evq_peak"] = max(s["evq_peak"], evq_len)
        t_f, _, typ, a0, a1, a2 = q.pop()
        t = F32(t_f)
        popped = 1
        if typ == EV_RX:
            rx_trips += 1
            beacon_rx(t, a0, a1, a2)
            # a same-time run of deliveries, up to batch_pop, is one step
            while popped < bp:
                nxt = q.peek()
                if nxt is None or nxt[0] != t_f or nxt[2] != EV_RX:
                    break
                _, _, _, b0, b1, b2 = q.pop()
                beacon_rx(t, b0, b1, b2)
                popped += 1
        s["events_processed"] += popped
        s["iterations"] += 1
        pushes = []
        if typ == EV_ARRIVE:
            arrive(t, a0, a1, pushes)
        elif typ == EV_SPAWN:
            spawn(t, a0, a1, a2, pushes)
        elif typ == EV_JOIN:
            join_exit(t, a0, a1, a2, pushes)
        d0 = q.dropped
        for ev in pushes:
            q.push(*ev)
        evq_len += len(pushes) - (q.dropped - d0) - popped
    s["dropped"] = q.dropped
    s["evq_len"] = evq_len
    # the simulator's dtypes: f32 ticks (DESIGN.md 8.6), int32 counts
    return ({k: np.int32(v) if isinstance(v, int) else v
             for k, v in s.items()}, rx_trips)
