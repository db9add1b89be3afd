"""Plain reference of the clustered task manager's transaction-level model.

A straightforward discrete-event simulation in Python and NumPy of the
semantics the simulator under test implements (arXiv:1502.02852, Sec 4-5,
with the repository's documented deviations): k global management nodes
(GMNs) over m processing elements, two-stage task mapping, threshold
status beacons over the hierarchical bus fabric, join barriers, and the
GMN fail/heal scenario with hot-spare takeover and the failure detector.

It imports nothing of the program.  One event is handled at a time from a
binary heap ordered by (time, slot); slots are allocated lowest-free-first,
which is the event queue's documented tie-breaking contract.  Times are
float32 scalars, computed in the same order of operations as the model
defines them, so every time and counter is exact; only ``mgmt_latency``,
a sum of vectors whose reduction order is the compiler's, may differ in
its last bits.

Supported: the ``hier_tree`` fabric, ``min_search`` mapping, ``threshold``
beacons, and fault schedules of link and GMN events with retries off.  A
configuration outside that raises ``NotImplementedError``.
"""
from __future__ import annotations

import heapq
import math

import numpy as np

F32 = np.float32
INF = F32(1e18)
EV_ARRIVE, EV_SPAWN, EV_JOIN, EV_RX = 0, 1, 2, 3
EV_LINK_DOWN, EV_LINK_UP, EV_GMN_FAIL, EV_GMN_HEAL = 4, 5, 6, 7

# leaves whose value depends on the order in which a vector is summed
ORDER_DEPENDENT = ("mgmt_latency",)


def _check_supported(cfg: dict) -> None:
    want = {"topology": "hier_tree", "mapping": "min_search",
            "beacon": "threshold"}
    for key, val in want.items():
        if cfg[key] != val:
            raise NotImplementedError(f"reference models {key}={val!r}, "
                                      f"not {cfg[key]!r}")
    if float(cfg.get("retry_after", 0.0)) != 0.0:
        raise NotImplementedError("reference models retry_after=0 only")


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
    max_apps, queue_cap, batch_pop, policies, c_b, c_s, c_join, dn_th,
    T_b, susp_mult, retry_after); ``arrivals`` (A,) f32, ``gmns`` (A,) int,
    ``lengths`` (A, n_childs) f32; ``faults`` is None or a list of
    ``(t, kind, a0, a1)`` in schedule order (kind 0..3 = link down, link
    up, GMN fail, GMN heal).  ``queue_cap`` overrides the configured
    capacity."""
    _check_supported(cfg)
    m, k = int(cfg["m"]), int(cfg["k"])
    mpk, n = m // k, int(cfg["n_childs"])
    A = int(cfg["max_apps"])
    Q = int(queue_cap or cfg["queue_cap"])
    bp = int(cfg["batch_pop"])
    dn_th = int(cfg["dn_th"])
    c_b, c_s, c_join = F32(cfg["c_b"]), F32(cfg["c_s"]), F32(cfg["c_join"])
    T_b, susp_mult = F32(cfg["T_b"]), F32(cfg["susp_mult"])
    sim_len = F32(sim_len)
    faults_on = faults is not None
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
    if faults_on:
        s |= {"link_up": np.ones((k, k), F32), "gmn_alive": np.ones(k, F32),
              "link_down_t": np.zeros((k, k), F32),
              "gmn_down_t": np.zeros(k, F32), "msgs_lost": 0, "reroutes": 0,
              "downtime": F32(0), "suspect": np.zeros((k, k), F32),
              "susp_onsets": np.zeros((k, k), np.int32),
              "susp_clears": np.zeros((k, k), np.int32),
              "susp_false_pos": 0, "det_floor": np.zeros(k, F32),
              "retries_tx": 0}

    q = _Queue(Q)
    for a in range(A):
        if arrivals[a] < sim_len:
            q.push(arrivals[a], EV_ARRIVE, a, int(gmns[a]), 0)
    seeded = int(np.sum(arrivals < sim_len))
    if faults_on:
        for kind in range(4):
            for t, kd, a0, a1 in faults:
                if kd == kind and F32(t) < sim_len:
                    q.push(F32(t), EV_LINK_DOWN + kind, int(a0), int(a1), 0)
        seeded += sum(1 for f in faults if F32(f[0]) < sim_len)
    evq_len = seeded - q.dropped
    s["evq_peak"] = evq_len

    def add_latency(x):
        s["mgmt_latency"] = F32(s["mgmt_latency"] + F32(x))

    def takeover(g):
        for off in range(k):
            c = (g + off) % k
            if s["gmn_alive"][c] > 0:
                return c
        return g

    def hop(dst, t_ready):
        """Global-bus grant then the destination's local-bus grant."""
        t_g = F32(max(t_ready, s["gbus_free"]) + c_b)
        s["gbus_free"] = t_g
        t_in = F32(max(t_g, s["lbus_free"][dst]) + c_b)
        s["lbus_free"][dst] = t_in
        return t_in

    def rehome(g, t):
        """Management work for GMN g at t re-homes to the takeover GMN."""
        g2 = takeover(g)
        if g2 == g:
            return g, t
        t_eff = hop(g2, t)
        s["reroutes"] += 1
        s["mgmt_msgs"] += 1
        add_latency(t_eff - t)
        return g2, t_eff

    def fire_beacon(g, t, load_g, pushes):
        """Broadcast GMN g's load summary over the fabric at t."""
        t_g = F32(max(t, s["gbus_free"]) + c_b)
        s["gbus_free"] = t_g
        t_arr = (np.maximum(t_g, s["lbus_free"]) + c_b).astype(F32)
        rcv = idx != g
        s["lbus_free"] = np.where(rcv, t_arr, s["lbus_free"]).astype(F32)
        if faults_on:
            dlv = rcv & (s["link_up"][g] > 0) & (s["gmn_alive"] > 0)
            s["msgs_lost"] += int(np.sum(rcv & ~dlv))
        else:
            dlv = rcv
        for i in np.flatnonzero(dlv):
            pushes.append((t_arr[i], EV_RX, g, int(i), int(load_g)))
        s["bcn_t"][g] = np.where(dlv, t_arr, s["bcn_t"][g])
        s["view"][g, g] = load_g
        s["view_t"][g, g] = t_g
        s["last_bcast"][g] = load_g
        s["last_bcast_t"][g] = t_g
        s["beacons_tx"] += 1
        s["mgmt_msgs"] += k - 1
        lat = F32(0)
        for i in np.flatnonzero(dlv):
            lat = F32(lat + F32(t_arr[i] - t))
        add_latency(lat)
        if dlv.any():
            spread = F32(max(F32(t_arr[dlv].max() - t_arr[dlv].min()),
                             F32(0)))
        else:
            spread = F32(0)
        s["bcn_skew_sum"] = F32(s["bcn_skew_sum"] + spread)
        s["bcn_skew_max"] = F32(max(s["bcn_skew_max"], spread))

    def maybe_beacon(g, t, pushes):
        load_g = int(s["loads"][g].sum())
        if abs(load_g - int(s["last_bcast"][g])) < dn_th or k <= 1:
            return
        if faults_on and not s["gmn_alive"][g] > 0:
            return
        fire_beacon(g, t, load_g, pushes)

    def arrive(t, app, g, pushes):
        t_eff = t
        if faults_on:
            g, t_eff = rehome(g, t)
        t_tree = F32(max(t_eff, s["gmn_free"][g]) + tree_cost)
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
                t_arr = hop(c, t_tree)
                s["mgmt_msgs"] += 1
                if faults_on and s["link_up"][g, c] == 0:
                    t_arr = F32(t_arr + F32(2.0) * c_b)
                    s["reroutes"] += 1
                lat = F32(lat + F32(t_arr - t_tree))
            pushes.append((t_arr, EV_SPAWN, app, c, cnt))
        s["rr_ptr"][g] += ns
        add_latency(lat)
        s["mgmt_proc"] = F32(s["mgmt_proc"] + F32(t_tree - t_eff))
        s["app_remaining"][app] = n
        s["app_arrive"][app] = t
        s["view"][g] = view

    def spawn(t, app, g, cnt, pushes):
        t_eff = t
        if faults_on:
            g, t_eff = rehome(g, t)
        t_cpu = F32(max(t_eff, s["gmn_free"][g]))
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
        s["mgmt_proc"] = F32(s["mgmt_proc"] + F32(t_cpu - t_eff))
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
        if faults_on:
            pg2 = takeover(pg)
            s["reroutes"] += int(pg2 != pg)
            pg = pg2
        t_fwd = t_msg
        if pg != g:
            t_fwd = hop(pg, t_msg)
            if faults_on and s["link_up"][g, pg] == 0:
                t_fwd = F32(t_fwd + F32(2.0) * c_b)
                s["reroutes"] += 1
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

    def fault(t, typ, a0, a1, pushes):
        if typ == EV_LINK_DOWN:
            if s["link_up"][a0, a1] > 0:
                s["link_down_t"][a0, a1] = t
            s["link_up"][a0, a1] = 0
        elif typ == EV_LINK_UP:
            if s["link_up"][a0, a1] == 0:
                s["downtime"] = F32(s["downtime"]
                                    + F32(t - s["link_down_t"][a0, a1]))
            s["link_up"][a0, a1] = 1
        elif typ == EV_GMN_FAIL:
            if s["gmn_alive"][a0] > 0:
                s["gmn_down_t"][a0] = t
            s["gmn_alive"][a0] = 0
        else:                                         # GMN heal
            was_dead = s["gmn_alive"][a0] == 0
            if was_dead:
                s["downtime"] = F32(s["downtime"]
                                    + F32(t - s["gmn_down_t"][a0]))
            s["gmn_alive"][a0] = 1
            if was_dead:
                s["det_floor"][a0] = t
                if k > 1:
                    fire_beacon(a0, t, int(s["loads"][a0].sum()), pushes)

    peers = ~np.eye(k, dtype=bool)
    sus_age = susp_mult * T_b

    def detector(t):
        seen = np.maximum(s["view_t"], s["det_floor"][:, None])
        sus = ((t - seen) > sus_age) & peers & (s["gmn_alive"][:, None] > 0)
        prev = s["suspect"] > 0
        if not t < sim_len:
            sus = prev
        onset, clear = sus & ~prev, prev & ~sus
        truth_ok = (s["gmn_alive"][None, :] > 0) & (s["link_up"].T > 0)
        s["suspect"] = sus.astype(F32)
        s["susp_onsets"] += onset
        s["susp_clears"] += clear
        s["susp_false_pos"] += int(np.sum(onset & truth_ok))

    while q.heap:
        s["evq_peak"] = max(s["evq_peak"], evq_len)
        t_f, _, typ, a0, a1, a2 = q.pop()
        t = F32(t_f)
        popped = 1
        if typ == EV_RX:
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
        if faults_on:
            detector(t)
        pushes = []
        if typ == EV_ARRIVE:
            arrive(t, a0, a1, pushes)
        elif typ == EV_SPAWN:
            spawn(t, a0, a1, a2, pushes)
        elif typ == EV_JOIN:
            join_exit(t, a0, a1, a2, pushes)
        elif typ >= EV_LINK_DOWN:
            fault(t, typ, a0, a1, pushes)
        d0 = q.dropped
        for ev in pushes:
            q.push(*ev)
        evq_len += len(pushes) - (q.dropped - d0) - popped
    s["dropped"] = q.dropped
    s["evq_len"] = evq_len
    # the simulator's dtypes: f32 ticks (DESIGN.md 8.6), int32 counts
    return {k: np.int32(v) if isinstance(v, int) else v for k, v in s.items()}
