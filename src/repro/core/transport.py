"""Interconnect transport model for the management plane (DESIGN.md §10).

The paper's claim is architectural: a *clustered* manager infrastructure
reduces the communication overhead of run-time management versus
centralized and fully-distributed configurations (Sec 5.4).  Until this
module existed the simulator delivered every beacon atomically at its
single bus-grant time (deviation §8.2), so the message-passing protocol
the paper analyzes was effectively free and skew-less.  ``Topology``
makes the fabric an explicit, static design-space axis:

  ``ideal``       the historical behavior, kept bitwise: one global bus
                  for inter-cluster messages, k local buses for
                  intra-cluster ones, beacons update every view
                  atomically at the global-bus grant.
  ``shared_bus``  a single serialized bus carries *all* management
                  messages (intra-cluster ones included), and a beacon
                  broadcast degenerates to k-1 back-to-back unicasts —
                  the contention-heavy flat-bus baseline.
  ``hier_tree``   the paper's physical fabric: global bus + k local
                  buses, each hop paying a serialized grant (``c_b``).
                  An inter-cluster message crosses the global bus and
                  then the *destination* cluster's local bus, so beacon
                  deliveries contend with local traffic per receiver.
  ``mesh2d``      GMNs on a ⌈√k⌉ x ⌈√k⌉ grid (a GMN mesh network):
                  injection serializes on the source's local port, then
                  delivery costs Manhattan-hops x ``c_hop`` — latency
                  scales with physical distance, no shared medium.
  ``torus2d``     the same grid with wraparound links in both dimensions
                  (Kalray MPPA-256's 2D-torus NoC, de Dinechin et al.,
                  HPEC 2013): the ``mesh2d`` cost model over the shorter
                  way round each dimension (:func:`hop_table`).

``MESH_KINDS`` names the two grid fabrics; they share every cost rule
and differ only in their hop table.

Like ``SimPolicy``, a ``Topology`` is hashable and static: each kind
compiles its own XLA program, and the untaken fabric models cost
nothing.  The numeric transport parameters — the bus service time
``c_b`` and the per-hop mesh latency ``c_hop`` — stay traced
``SimKnobs`` leaves, so knob/seed grids under any topology remain one
compilation per (shape, policy, topology).

Under the non-ideal kinds, a fired beacon becomes k-1 in-flight entries
in a (k, k) delivery matrix (``bcn_t``, rows = source, columns =
receiver, tracking the latest pending arrival per pair) and one
``BEACON_RX`` event per receiver; views then update at per-receiver
arrival times, so ``view_t``/``age`` in ``core/policies.py`` genuinely
differ across receivers.  Arrivals from one source to one receiver are
strictly increasing in send order (``c_b > 0`` serializes the source),
so deliveries apply FIFO per pair and conservation is exact:

    beacons_rx == (k - 1) * beacons_tx

with the matrix draining to empty by the end of every run
(tests/test_transport.py).  The wall-clock analog for the serving
engine (``serving/engine.FleetSim``) uses :func:`host_beacon_delays`,
stateless per-receiver delays in the same shapes.

Under fault injection (repro.core.faults, DESIGN.md §13) every message
class routes through the traced (k, k) ``link_up`` mask: beacons are
best-effort (a down link or dead receiver drops the delivery into
``msgs_lost``, generalizing conservation to ``beacons_rx + msgs_lost
== (k-1) * beacons_tx``), while task-start groups and join-exit
forwards are reliable and pay :func:`link_penalty` — a detour
(``2 * c_hop`` on the grid fabrics) or retransmit grant pair (``2 * c_b``
elsewhere) counted in ``reroutes``.  On ``torus2d`` the detour cost is
an assumption carried over from the mesh: a torus has a second way
round, which may be shorter or longer than the mesh's two-hop detour,
and no published figure fixes it.  On an all-up mask every penalty is
exactly 0.0, so the fault-aware programs reproduce the frozen goldens
bitwise.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

TOPOLOGIES = ("ideal", "shared_bus", "hier_tree", "mesh2d", "torus2d")
# the grid fabrics: source-port injection, then hops x c_hop
MESH_KINDS = ("mesh2d", "torus2d")


@dataclass(frozen=True)
class Topology:
    """Static fabric selection: hashable, one XLA program per kind."""
    kind: str = "ideal"

    def __post_init__(self):
        if self.kind not in TOPOLOGIES:
            raise ValueError(f"unknown topology {self.kind!r}; "
                             f"choose from {TOPOLOGIES}")


DEFAULT_TOPOLOGY = Topology()


def topology_grid(kinds=TOPOLOGIES):
    """All topology kinds as Topology values (the static sweep axis)."""
    return [Topology(kind) for kind in kinds]


def grid_side(k: int) -> int:
    """Side of the smallest square GMN grid holding k nodes."""
    return max(1, math.isqrt(k - 1) + 1) if k > 1 else 1


def mesh_hops(k: int) -> np.ndarray:
    """(k, k) Manhattan hop counts between GMNs placed row-major on a
    ``grid_side(k)``-wide 2D grid.  Symmetric, zero diagonal; static —
    it becomes an XLA constant inside the compiled program."""
    s = grid_side(k)
    pos = np.arange(k)
    x, y = pos // s, pos % s
    return (np.abs(x[:, None] - x[None, :])
            + np.abs(y[:, None] - y[None, :])).astype(np.int32)


def hop_table(kind: str, k: int) -> np.ndarray:
    """(k, k) hop counts of a fabric's GMN placement.  ``torus2d`` places
    GMNs as :func:`mesh_hops` does and wraps each dimension at its own
    length (rows = ceil(k / side), columns = side), so a hop count is
    ``min(|dx|, rows - |dx|) + min(|dy|, side - |dy|)``; every other kind
    takes :func:`mesh_hops` (only the grid fabrics read it)."""
    if kind != "torus2d":
        return mesh_hops(k)
    s = grid_side(k)
    rows = -(-k // s)
    pos = np.arange(k)
    dx = np.abs(pos[:, None] // s - pos[None, :] // s)
    dy = np.abs(pos[:, None] % s - pos[None, :] % s)
    return (np.minimum(dx, rows - dx)
            + np.minimum(dy, s - dy)).astype(np.int32)


# ==========================================================================
# Traced fabric primitives (used by repro.core.sim's event handlers).
#
# All of them branch on ``topo.kind`` at trace time (the topology is
# static), take the bus-occupancy state explicitly and return it updated;
# every returned latency is (delivery - ready), the per-message
# communication overhead accumulated into ``mgmt_latency``.  The ideal
# branches reproduce the historical inline bus code operation-for-
# operation — that is the bitwise-golden contract of
# tests/test_sweep.py.
# ==========================================================================

def unicast(topo: Topology, src, dst, t_ready, is_remote, *, gbus, lbus,
            c_b, c_hop, hops):
    """One inter-GMN management message (stage-1 task-start group).

    Returns ``(t_arr, gbus, lbus, latency)``.  A self-targeted message
    (``is_remote`` false) is a local data-structure operation in every
    topology: it arrives at ``t_ready`` and touches no fabric.
    """
    if topo.kind in ("ideal", "shared_bus"):
        # one serialized grant on the single global/shared bus
        t_bus = jnp.maximum(t_ready, gbus) + c_b
        gbus = jnp.where(is_remote, t_bus, gbus)
        t_arr = jnp.where(is_remote, t_bus, t_ready)
    elif topo.kind == "hier_tree":
        # global-bus hop, then the destination cluster's local-bus hop
        t_g = jnp.maximum(t_ready, gbus) + c_b
        gbus = jnp.where(is_remote, t_g, gbus)
        t_in = jnp.maximum(t_g, lbus[dst]) + c_b
        lbus = jnp.where(is_remote, _set1(lbus, dst, t_in), lbus)
        t_arr = jnp.where(is_remote, t_in, t_ready)
    elif topo.kind in MESH_KINDS:
        # serialized injection at the source port, then hop latency
        t_inj = jnp.maximum(t_ready, lbus[src]) + c_b
        lbus = jnp.where(is_remote, _set1(lbus, src, t_inj), lbus)
        t_arr = jnp.where(is_remote,
                          t_inj + hops[src, dst].astype(jnp.float32) * c_hop,
                          t_ready)
    return t_arr, gbus, lbus, jnp.where(is_remote, t_arr - t_ready, 0.0)


def forward(topo: Topology, src, dst, t_ready, is_remote, *, gbus, lbus,
            c_b, c_hop, hops):
    """A remote join-exit forward from GMN ``src`` to the barrier GMN
    ``dst`` — same fabric path as :func:`unicast`, separate entry point
    so the accounting and DESIGN.md can name the message class."""
    return unicast(topo, src, dst, t_ready, is_remote, gbus=gbus, lbus=lbus,
                   c_b=c_b, c_hop=c_hop, hops=hops)


def link_penalty(topo: Topology, up, is_remote, *, c_b, c_hop):
    """Extra delivery latency a *reliable* management message (task-start
    group, join-exit forward) pays when its (src, dst) link is down
    (DESIGN.md §13).  Reliable messages are never lost — the fabric
    detours them:

      mesh2d,    the XY route is blocked; the dimension-ordered detour
      torus2d    around the failed link costs two extra hops
                 (``2 * c_hop``; on the torus an assumption, see the
                 module docstring).
      otherwise  the bus-based fabrics retransmit through the
                 supervisor path: one extra grant pair (``2 * c_b``).

    Returns the traced penalty (0.0 when the link is up, the message is
    local, or faults are disabled) — adding it to an arrival time is an
    exact no-op on an all-up mask, which is the bitwise no-fault
    contract the frozen goldens ride on.  ``up`` is the (src, dst) entry
    of the traced ``link_up`` mask."""
    base = 2.0 * (c_hop if topo.kind in MESH_KINDS else c_b)
    hit = jnp.logical_and(is_remote, up == 0)
    return jnp.where(hit, base, 0.0)


def beacon_tx(topo: Topology, g, t, fire, *, gbus, lbus, c_b, c_hop, hops,
              k: int):
    """Transmit a status beacon from GMN ``g`` at tick ``t`` (masked by
    the traced ``fire``; bus state only advances where it fires).

    Returns ``(t_tx, t_arr, gbus, lbus)``: ``t_tx`` the transmission
    grant (feeds ``last_bcast_t``), ``t_arr`` (k,) per-receiver arrival
    times (entry ``g`` is meaningless — the caller masks it out).
    Only defined for the non-ideal kinds; ``ideal`` keeps the historical
    atomic-update path inside ``sim._maybe_beacon``.
    """
    if topo.kind == "shared_bus":
        # no hardware broadcast on the flat bus: k-1 back-to-back
        # unicasts in own-first order, one serialized grant (c_b) each
        t0 = jnp.maximum(t, gbus) + c_b
        j = jnp.mod(jnp.arange(k) - g, k)            # own-first rank, own = 0
        t_arr = t0 + (j - 1).astype(jnp.float32) * c_b
        t_last = t0 + jnp.float32(max(k - 2, 0)) * c_b
        gbus = jnp.where(fire, t_last, gbus)
        return t0, t_arr, gbus, lbus
    if topo.kind == "hier_tree":
        # one global-bus grant, then each receiver's local-bus grant
        t_g = jnp.maximum(t, gbus) + c_b
        gbus = jnp.where(fire, t_g, gbus)
        t_arr = jnp.maximum(t_g, lbus) + c_b
        rcv = jnp.arange(k) != g
        lbus = jnp.where(jnp.logical_and(fire, rcv), t_arr, lbus)
        return t_g, t_arr, gbus, lbus
    if topo.kind in MESH_KINDS:
        # one serialized injection, then per-receiver hop latency
        t_inj = jnp.maximum(t, lbus[g]) + c_b
        lbus = jnp.where(fire, _set1(lbus, g, t_inj), lbus)
        t_arr = t_inj + hops[g].astype(jnp.float32) * c_hop
        return t_inj, t_arr, gbus, lbus
    raise ValueError(f"beacon_tx is undefined for topology {topo.kind!r}")


def _set1(arr, i, val):
    """arr.at[i].set(val) as a one-hot select (row update for ndim > 1).
    Vmap-safe and scatter-free; the single shared copy — repro.core.sim
    aliases it (see the rationale comment there)."""
    hot = jnp.arange(arr.shape[0]) == i
    return jnp.where(hot.reshape((-1,) + (1,) * (arr.ndim - 1)), val, arr)


# ==========================================================================
# Wall-clock host analog (serving.engine.FleetSim).
#
# The serving engine has no tick-granular bus occupancy; the analog is a
# stateless per-receiver delay vector with the same *shape* as the
# tick-domain fabric: shared_bus serializes receivers, hier_tree pays a
# fixed two-hop crossing, the grid fabrics pay hop-count latency.  ``ideal``
# returns all-zero delays, which FleetSim treats as instant delivery —
# exactly the pre-transport `_broadcast` fan-out.
# ==========================================================================

def max_delivery_delay(kind: str, k: int, *, c_b: float = 1.0,
                       c_hop: float = 0.5) -> float:
    """Worst-case zero-contention beacon delivery latency (tx -> last
    receiver) on a k-GMN fabric — the static bound the failure-detector
    chaos properties ride on (tests/test_failure_detector.py): a peer
    that stops beaconing at ``t_fail`` can have refreshed a live view no
    later than ``t_fail + max_delivery_delay(...)``, so every live GMN
    must suspect it within ``susp_mult * T_b + max_delivery_delay``
    ticks of detector evaluation after that.

    Contention (bus backlog) can stretch an individual delivery beyond
    this; callers needing a hard bound under load multiply by a slack
    factor sized to their beacon rate.  ``ideal`` is 0: views update
    atomically at the transmission grant."""
    if kind not in TOPOLOGIES:
        raise ValueError(f"unknown topology {kind!r}; "
                         f"choose from {TOPOLOGIES}")
    if kind == "ideal" or k <= 1:
        return 0.0
    if kind == "shared_bus":
        return float(max(k - 1, 1)) * c_b            # k-1 serialized unicasts
    if kind == "hier_tree":
        return 2.0 * c_b                             # global + local grant
    return c_b + float(hop_table(kind, k).max()) * c_hop   # MESH_KINDS


def host_beacon_delays(kind: str, k: int, src: int, *, c_b: float = 1.0,
                       c_hop: float = 0.5) -> np.ndarray:
    """(k,) wall-clock beacon delivery delays from ``src`` per receiver
    (entry ``src`` is 0 and unused)."""
    if kind not in TOPOLOGIES:
        raise ValueError(f"unknown topology {kind!r}; "
                         f"choose from {TOPOLOGIES}")
    d = np.zeros(k, np.float64)
    if kind == "ideal" or k <= 1:
        return d
    if kind == "shared_bus":
        rank = (np.arange(k) - src) % k              # own-first order
        d = rank * c_b
    elif kind == "hier_tree":
        d = np.full(k, 2.0 * c_b)                    # global + local hop
    elif kind in MESH_KINDS:
        d = c_b + hop_table(kind, k)[src] * c_hop
    d[src] = 0.0
    return d
