"""Event-queue structures for the TLM simulator (DESIGN.md §11).

The simulator's hot loop pops the earliest pending event once per
iteration (or one same-timestamp BEACON_RX cohort, see ``batch_take``)
and commits that iteration's pushes.  This module owns the queue's
format: ``sim.py`` calls ``resolve`` once and then only the functions
of the ``Queue`` it returns, without knowing which format is
underneath.  Three formats sit behind it (one XLA program each):

``"tree"`` — the name is kept for the configurations and goldens that
name it, though no tree is left.  The whole queue is ONE ``(Q, 6)`` f32
array ``evq_tree`` (Q = queue_cap) of per-slot leaf rows [time, slot,
ev_type, a0, a1, a2], slot j at row j; INF time marks a free slot.
Slot indices and payloads are small exact integers in f32 (queue_cap is
capped at 2**24, event arguments are app/cluster/PE indices and counts
far below it).  The next event is the ``(6,)`` root mirror
``evq_root``: every commit ends with ONE ``jnp.argmin`` over the leaf
times and copies that leaf's row, so the loop condition and the pop
read the mirror and never the big buffer.

``"calendar"`` — the same leaf rows inside ONE ``(1 + Q + NB, 6)``
array ``evq_cal``: row 0 the root row, rows 1..1+Q the leaves, then NB
bucket-summary rows.  Bucket b = floor(t/W) mod NB holds the
lexmin-(time, slot) row among its live events (W = the traced
``time_scale``, NB = CAL_BUCKETS).  A summary is the min over ALL years
mapped to its bucket, so the root (min over summaries) is exact
whatever W is — W only shapes the work.  A commit rebuilds the popped
bucket (all pops of a trip share one time, hence one bucket) with one
masked O(Q) pass, merges the pushed rows into their buckets with a
scatter-min, and sets the root from the NB summaries.

``"linear"`` — the historical ``ev_time``/``ev_type``/``ev_a`` arrays,
kept operation for operation as the test reference and the golden
anchor every frozen sha in tests/test_sweep.py gates: an O(Q)
``jnp.argmin`` pop, a queue-wide ``min`` loop condition and a
queue-wide stable ``argsort`` push.

One array per queue is the point, not a convenience: a chain of
gathers-then-scatters on a single buffer updates in place, but a
second scatter whose indices derive from a read of another array
forces a full copy of the big buffer per event.  A commit applies a
whole body iteration's pops AND pushes as one scatter chain (clear the
popped leaves -> allocate -> write the push leaves), then sets the
root.  Handlers stage their push requests instead of pushing
mid-handler, so the body makes exactly one commit and no queue
scatter sits inside a ``lax.switch`` branch.

Slot rule, shared by every format: the j-th masked entry of a push
batch takes the j-th lowest free slot, and masked entries past the
free count drop (counted in ``dropped``).  ``_alloc`` finds the slots
from a dense ``(S, ALLOC_SEG)`` table of free flags: a cumsum of its
row sums and ``searchsorted`` give each entry's segment, and the
entry's row of the table gives the slot within it.

Tie-breaking contract: ``jnp.argmin`` returns the LOWEST index among
equal minima, so every format pops the lexmin of (time, slot)
(tests/test_eventq.py::test_pop_slot_matches_argmin_under_ties; the
calendar takes the lexmin explicitly).  ``batch_take`` extends it to
same-timestamp batching: the selected window is the contiguous
slot-order prefix of the root-time cohort consisting of BEACON_RX
events only, stopping at the first tied non-RX slot — exactly the
events a singleton loop would pop consecutively (BEACON_RX handlers
push nothing, so the cohort cannot change mid-prefix).

Everything is fixed-shape with no data-dependent control flow: updates
are ``.at[].set`` writes with traced indices (out-of-range lanes
dropped via ``mode="drop"``), so masked entries write nothing, and the
queue stays vmap-able — ``sweep.py``'s "vmap" and "seq" modes are
bitwise identical under every format.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax.numpy as jnp

INF = jnp.float32(1e18)

QUEUE_IMPLS = ("linear", "tree", "calendar")

# Free-slot search granularity: the allocator's cumsum runs over
# Q / ALLOC_SEG segment counts (512 at the paper-scale Q=32768), and
# each entry reads its segment's 64 free flags as one row of a dense
# (S, 64) table built from the leaf times in one pass, not as n x 64
# gathered leaf times: a TPU gather costs about 10 ns per index, and
# each leaf time sits in its own (..., 6) row, padded to 128 lanes
# (DESIGN.md §11).
ALLOC_SEG = 64

# Calendar-queue bucket count (static; capped by queue_cap).
CAL_BUCKETS = 256

# Queue slots (and event payloads) are stored as exact small integers in
# the leaf rows' f32 columns.
MAX_QUEUE_CAP = 1 << 24

# Row layout: [time, slot, ev_type, a0, a1, a2].
ROW_W = 6


class Queue(NamedTuple):
    """The seam between the simulator and one queue format at one
    capacity (``resolve``).

    state()                 the empty queue's state-dict leaves
    live(st)                whether any event is pending (loop condition)
    root(st)                the next event: ``(t, slot, typ, a)``, with
                            ``slot``/``typ`` i32 and ``a`` (3,) i32
    leaves(st)              (Q,) per-slot times (INF = free) and (Q, 4)
                            f32 payloads [ev_type, a0, a1, a2], for the
                            same-timestamp cohort scan
    held                    the state keys the body holds out of its
                            ``lax.switch`` (written only by the commit)
    commit(st, pop_slots, pop_ok, mask, times, typ, a0, a1, a2)
                            one iteration's pops (all at the root's
                            time), then its pushes
    bulk_push(st, mask, times, typ, a0, a1, a2)
                            pushes alone (the set-up's seeding)

    ``typ`` may be a scalar or a per-entry array."""
    state: Callable
    live: Callable
    root: Callable
    leaves: Callable
    held: tuple
    commit: Callable
    bulk_push: Callable


# --------------------------------------------------------------------------
# Shared by the tree and the calendar: leaf rows, root mirror, allocator.
# --------------------------------------------------------------------------

def build_tree(times, typ=None, a=None):
    """(queue_cap,) event times (+ optional payloads: ``typ`` (Q,) and
    ``a`` (Q, 3)) -> the ``evq_tree`` leaf rows."""
    q = times.shape[0]
    if q > MAX_QUEUE_CAP:
        raise ValueError(f"queue_cap {q} exceeds the exact-f32 slot-index "
                         f"range ({MAX_QUEUE_CAP})")
    times = jnp.asarray(times, jnp.float32)
    typ = jnp.zeros((q,), jnp.float32) if typ is None \
        else jnp.asarray(typ, jnp.float32)
    a = jnp.zeros((q, 3), jnp.float32) if a is None \
        else jnp.asarray(a, jnp.float32)
    return jnp.concatenate([
        jnp.stack([times, jnp.arange(q, dtype=jnp.float32), typ], -1),
        a], axis=-1)


def _root_event(st):
    """The next event from the ``evq_root`` mirror."""
    root = st["evq_root"]
    return (root[0], root[1].astype(jnp.int32), root[2].astype(jnp.int32),
            root[3:].astype(jnp.int32))


def _live(st):
    return st["evq_root"][0] < INF


def _clear_rows(pop_slots):
    """The free leaf rows the popped slots are reset to."""
    return jnp.zeros((pop_slots.shape[0], ROW_W)) \
        .at[:, 0].set(INF) \
        .at[:, 1].set(pop_slots.astype(jnp.float32))


def _push_rows(times, slot, typ, a0, a1, a2):
    """The leaf rows a push batch writes at its allocated slots."""
    shape = times.shape
    return jnp.stack([
        times, slot.astype(jnp.float32),
        jnp.broadcast_to(jnp.asarray(typ, jnp.float32), shape),
        jnp.broadcast_to(jnp.asarray(a0, jnp.float32), shape),
        jnp.broadcast_to(jnp.asarray(a1, jnp.float32), shape),
        jnp.broadcast_to(jnp.asarray(a2, jnp.float32), shape)], -1)


def _alloc(leaf_t, mask):
    """Slot allocator: the j-th masked entry takes the j-th lowest free
    slot of the (Q,) leaf times (INF = free; bitwise the linear format's
    first-free-slot rule).  Returns ``(slot, ok, n_dropped)``.

    One dense pass marks the free leaves as an (S, 64) table, padded
    with taken slots past Q; the cumsum of its row sums and
    ``searchsorted`` find each entry's segment, and the entry's row of
    the table the slot within it (see ALLOC_SEG)."""
    mask = jnp.asarray(mask, bool)
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1    # rank among masked
    pad = jnp.zeros((-leaf_t.shape[0] % ALLOC_SEG,), bool)
    free = jnp.concatenate([leaf_t >= INF, pad]).reshape(-1, ALLOC_SEG)
    csum = jnp.cumsum(free.sum(axis=1, dtype=jnp.int32))
    total_free = csum[-1]
    # first segment whose cumulative free count reaches rank+1
    seg = jnp.searchsorted(csum, rank + 1, side="left").astype(jnp.int32)
    segc = jnp.minimum(seg, csum.shape[0] - 1)       # clamped (overflow)
    r = rank - jnp.where(segc > 0, csum[segc - 1], 0)  # rank in segment
    free_w = free[segc]
    hit = jnp.logical_and(free_w,
                          jnp.cumsum(free_w, axis=1) == r[:, None] + 1)
    slot = segc * ALLOC_SEG + jnp.argmax(hit, axis=1).astype(jnp.int32)
    ok = jnp.logical_and(mask, rank < total_free)
    return slot, ok, jnp.maximum(mask.sum() - total_free, 0)


# --------------------------------------------------------------------------
# "tree": the (Q, 6) leaf array with a dense root.
# --------------------------------------------------------------------------

def _root(tree):
    """The next event of an ``evq_tree`` array: the row of the leaf at
    the lowest slot among equal minimum times (``jnp.argmin``'s rule)."""
    return tree[jnp.argmin(tree[:, 0])]


def queue_state(queue_cap: int) -> dict:
    """The state-dict leaves of ``queue_impl="tree"`` (an empty queue:
    all times INF, all slots free).

    ``evq_root`` holds the root row (``_root``) in a separate (ROW_W,)
    buffer, maintained by ``commit``.  The simulator body and loop
    condition read the next event from it, NEVER from the big leaf
    buffer: any read of the buffer outside the commit chain gets
    rematerialized by XLA fusion at its consumers, and a read of the
    original value after the in-place chain has started makes
    copy-insertion clone the whole buffer every iteration (measured
    ~850 us/event at the k=256 paper point — the entire cost of the
    pre-fused simulator)."""
    tr = build_tree(jnp.full((queue_cap,), INF))
    return {"evq_tree": tr, "evq_root": _root(tr)}


def commit(st, pop_slots, pop_ok, mask, times, typ, a0, a1, a2,
           queue_cap: int):
    """Apply one body iteration's pops AND pushes as ONE scatter chain on
    ``evq_tree``: clear the popped leaves, allocate push slots (the
    allocator sees the just-freed slots, exactly the sequential
    pop-then-push semantics), write the push leaves, then set the root
    mirror from the final leaf times.  An entry timed at INF takes its
    slot in the assignment order (as in the linear format) but leaves
    it free.

    ``pop_slots``/``pop_ok`` are (B,) — B = 0 makes this a pure bulk
    push; masked lanes (``pop_ok`` False) are dropped from every write."""
    tree = st["evq_tree"]
    pop_slots = jnp.asarray(pop_slots, jnp.int32)
    pop_ok = jnp.asarray(pop_ok, bool)
    tree = tree.at[jnp.where(pop_ok, pop_slots, queue_cap)] \
        .set(_clear_rows(pop_slots), mode="drop")

    times = jnp.asarray(times, jnp.float32)
    slot, ok, n_drop = _alloc(tree[:, 0], mask)
    st = dict(st)
    st["dropped"] = st["dropped"] + n_drop
    tree = tree.at[jnp.where(ok, slot, queue_cap)] \
        .set(_push_rows(times, slot, typ, a0, a1, a2), mode="drop")
    st["evq_tree"] = tree
    # the dense root reads the buffer only after its last in-place write,
    # so copy-insertion clones nothing (queue_state)
    st["evq_root"] = _root(tree)
    return st


def bulk_push(st, mask, times, typ, a0, a1, a2, queue_cap: int):
    """Insert the masked entries of an event batch: a pure-push
    ``commit``."""
    return commit(st, jnp.zeros((0,), jnp.int32), jnp.zeros((0,), bool),
                  mask, times, typ, a0, a1, a2, queue_cap)


def empty(queue_cap: int) -> dict:
    """A minimal standalone tree queue state (no simulator around it) —
    the harness tests/test_eventq.py and the scatter check drive."""
    return {"dropped": jnp.zeros((), jnp.int32)} | queue_state(queue_cap)


# --------------------------------------------------------------------------
# "calendar": the leaf rows under a root row and NB bucket summaries.
# --------------------------------------------------------------------------

def cal_buckets(queue_cap: int) -> int:
    """Static calendar bucket count for a queue (never exceeds Q)."""
    return min(CAL_BUCKETS, max(1, queue_cap))


def _bucket(t, width, nb):
    """floor(t / W) mod NB — only ever evaluated on finite times."""
    return jnp.mod(jnp.floor(t / width), nb).astype(jnp.int32)


def cal_state(queue_cap: int) -> dict:
    """The state-dict leaves of ``queue_impl="calendar"`` (an empty
    queue: free leaves, empty summaries, the empty root row [INF, 0, 0,
    0, 0, 0] that an all-free bucket search returns).  ``evq_root``
    mirrors row 0 (see ``queue_state``)."""
    empty_row = jnp.zeros((1, ROW_W)).at[0, 0].set(INF)
    cal = jnp.concatenate([
        empty_row, build_tree(jnp.full((queue_cap,), INF)),
        jnp.tile(empty_row, (cal_buckets(queue_cap), 1))])
    return {"evq_cal": cal, "evq_root": cal[0]}


def cal_commit(st, pop_slots, pop_ok, root_t, mask, times, typ, a0, a1, a2,
               queue_cap: int, width):
    """Calendar twin of ``commit``: clear pops, allocate (shared
    ``_alloc`` — identical slot rule and drop accounting), write push
    leaves, then maintain the summaries: rebuild the single popped
    bucket from its leaves (all pops in a batch share the root time,
    hence one bucket), lexmin-merge the pushed rows into theirs, and
    rewrite the root row from the NB summaries.  ``root_t`` is the
    shared timestamp of the popped lanes (any value when B = 0)."""
    q = queue_cap
    nb = cal_buckets(q)
    sum_base = 1 + q
    cal = st["evq_cal"]
    oob = cal.shape[0]
    pop_slots = jnp.asarray(pop_slots, jnp.int32)
    pop_ok = jnp.asarray(pop_ok, bool)
    width = jnp.asarray(width, jnp.float32)
    cal = cal.at[jnp.where(pop_ok, 1 + pop_slots, oob)] \
        .set(_clear_rows(pop_slots), mode="drop")

    times = jnp.asarray(times, jnp.float32)
    slot, ok, n_drop = _alloc(cal[1:1 + q, 0], mask)
    st = dict(st)
    st["dropped"] = st["dropped"] + n_drop
    cal = cal.at[jnp.where(ok, 1 + slot, oob)] \
        .set(_push_rows(times, slot, typ, a0, a1, a2), mode="drop")

    # -- summary maintenance (value space) -------------------------------
    # The summary block and root row are COMPUTED from reads of the
    # buffer and written exactly once at the end.  Interleaving summary
    # writes with reads of older buffer values keeps the pre-write value
    # live across each in-place update, so XLA's copy-insertion
    # materializes the WHOLE array per loop iteration — the §11
    # pathology, caught by `repro.analysis` hlo_lint.
    leaf_t = cal[1:1 + q, 0]
    slots_f = jnp.arange(q, dtype=jnp.float32)
    alive = leaf_t < INF
    empty_row = jnp.zeros((ROW_W,)).at[0].set(INF)
    # (a) rebuild the popped bucket from its leaves (sees the new pushes;
    # exact because the pops share root_t)
    b_pop = _bucket(jnp.where(jnp.any(pop_ok), root_t, 0.0), width, nb)
    in_pop = jnp.logical_and(
        alive, _bucket(jnp.where(alive, leaf_t, 0.0), width, nb) == b_pop)
    t_in = jnp.where(in_pop, leaf_t, INF)
    tmin = jnp.min(t_in)
    smin = jnp.min(jnp.where(jnp.logical_and(in_pop, leaf_t == tmin),
                             slots_f, INF))
    found = smin < INF
    row = cal[1 + jnp.minimum(smin, q - 1).astype(jnp.int32)]
    new_sum = jnp.where(found, row, empty_row)
    hit = jnp.logical_and(jnp.any(pop_ok), jnp.arange(nb) == b_pop)
    summ = jnp.where(hit[:, None], new_sum[None, :],
                     cal[sum_base:sum_base + nb])
    # (b) lexmin-merge the pushed rows into their buckets
    plive = jnp.logical_and(ok, times < INF)
    pb = jnp.where(plive, _bucket(jnp.where(plive, times, 0.0), width, nb),
                   nb)
    pt = jnp.full((nb + 1,), INF).at[pb].min(
        jnp.where(plive, times, INF), mode="drop")
    ps = jnp.full((nb + 1,), INF).at[pb].min(
        jnp.where(jnp.logical_and(plive, times == pt[jnp.minimum(pb, nb)]),
                  slot.astype(jnp.float32), INF), mode="drop")
    cand_s = ps[:nb]
    cand_ok = cand_s < INF
    cand = cal[1 + jnp.minimum(cand_s, q - 1).astype(jnp.int32)]
    take_new = jnp.logical_and(cand_ok, jnp.logical_or(
        cand[:, 0] < summ[:, 0],
        jnp.logical_and(cand[:, 0] == summ[:, 0], cand[:, 1] < summ[:, 1])))
    summ = jnp.where(take_new[:, None], cand, summ)

    # -- root from the NB summaries, then the single block write ---------
    best_t = jnp.min(summ[:, 0])
    root_row = summ[jnp.argmin(jnp.where(summ[:, 0] == best_t, summ[:, 1],
                                         INF))]
    cal = cal.at[sum_base:sum_base + nb].set(summ)
    cal = cal.at[0].set(root_row)
    st["evq_cal"] = cal
    st["evq_root"] = root_row                        # mirror (queue_state)
    return st


def cal_bulk_push(st, mask, times, typ, a0, a1, a2, queue_cap: int, width):
    """Calendar twin of ``bulk_push``: a pure-push ``cal_commit``."""
    return cal_commit(st, jnp.zeros((0,), jnp.int32), jnp.zeros((0,), bool),
                      jnp.float32(0.0), mask, times, typ, a0, a1, a2,
                      queue_cap, width)


def cal_empty(queue_cap: int) -> dict:
    """Standalone calendar queue state for the test harness and the
    scatter check."""
    return {"dropped": jnp.zeros((), jnp.int32)} | cal_state(queue_cap)


# --------------------------------------------------------------------------
# "linear": the historical arrays, the test reference.
# --------------------------------------------------------------------------

def _linear_state(queue_cap: int) -> dict:
    return {
        "ev_time": jnp.full((queue_cap,), INF),
        "ev_type": jnp.zeros((queue_cap,), jnp.int32),
        "ev_a": jnp.zeros((queue_cap, 3), jnp.int32),  # (app, gmn/cluster, pe/cnt)
    }


def _linear_live(st):
    return st["ev_time"].min() < INF                 # O(Q) linear scan


def _linear_root(st):
    slot = jnp.argmin(st["ev_time"]).astype(jnp.int32)  # O(Q)
    return st["ev_time"][slot], slot, st["ev_type"][slot], st["ev_a"][slot]


def _linear_leaves(st):
    return st["ev_time"], jnp.concatenate(
        [st["ev_type"][:, None].astype(jnp.float32),
         st["ev_a"].astype(jnp.float32)], -1)


def _linear_bulk_push(st, mask, times, typ, a0, a1, a2):
    """One vectorized pass over the whole queue (cumsum of the free mask
    + a stable argsort), O(Q log Q) per batch."""
    n = times.shape[0]
    typb = jnp.broadcast_to(jnp.asarray(typ, jnp.int32), (n,))
    free = st["ev_time"] >= INF
    free_rank = jnp.cumsum(free) - 1                 # slot's rank among free
    cnt = mask.sum()
    order = jnp.argsort(jnp.logical_not(mask))       # stable: pushed first
    idx = jnp.minimum(free_rank, n - 1)
    ct = times[order][idx]
    ctyp = typb[order][idx]
    ca = jnp.stack([jnp.asarray(a0, jnp.int32)[order][idx],
                    jnp.asarray(a1, jnp.int32)[order][idx],
                    jnp.asarray(a2, jnp.int32)[order][idx]], -1)
    write = free & (free_rank < cnt)
    st = dict(st)
    st["ev_time"] = jnp.where(write, ct, st["ev_time"])
    st["ev_type"] = jnp.where(write, ctyp, st["ev_type"])
    st["ev_a"] = jnp.where(write[:, None], ca, st["ev_a"])
    st["dropped"] = st["dropped"] + jnp.maximum(cnt - free.sum(), 0)
    return st


def _linear_commit(st, pop_slots, pop_ok, mask, times, typ, a0, a1, a2,
                   queue_cap: int):
    st = dict(st)
    st["ev_time"] = st["ev_time"].at[
        jnp.where(pop_ok, pop_slots, queue_cap)].set(INF, mode="drop")
    return _linear_bulk_push(st, mask, times, typ, a0, a1, a2)


# --------------------------------------------------------------------------
# The seam.
# --------------------------------------------------------------------------

def resolve(queue_impl: str, queue_cap: int, time_scale=1.0) -> Queue:
    """The ``Queue`` of one format (``QUEUE_IMPLS``) at one capacity.
    ``time_scale`` is the spacing at which event times cluster (traced);
    it sets the calendar's bucket width and nothing else."""
    q = queue_cap
    if queue_impl == "tree":
        return Queue(
            state=lambda: queue_state(q), live=_live, root=_root_event,
            leaves=lambda st: (st["evq_tree"][:, 0], st["evq_tree"][:, 2:]),
            held=("evq_tree",),
            commit=functools.partial(commit, queue_cap=q),
            bulk_push=functools.partial(bulk_push, queue_cap=q))
    if queue_impl == "calendar":
        return Queue(
            state=lambda: cal_state(q), live=_live, root=_root_event,
            leaves=lambda st: (st["evq_cal"][1:1 + q, 0],
                               st["evq_cal"][1:1 + q, 2:]),
            held=("evq_cal",),
            # every pop of a trip is at the root's time, which the
            # mirror still holds until this commit sets the next root
            commit=lambda st, pop_slots, pop_ok, *push: cal_commit(
                st, pop_slots, pop_ok, st["evq_root"][0], *push, q,
                time_scale),
            bulk_push=lambda st, *push: cal_bulk_push(st, *push, q,
                                                      time_scale))
    if queue_impl == "linear":
        return Queue(
            state=lambda: _linear_state(q), live=_linear_live,
            root=_linear_root, leaves=_linear_leaves,
            held=("ev_time", "ev_type", "ev_a"),
            commit=functools.partial(_linear_commit, queue_cap=q),
            bulk_push=_linear_bulk_push)
    raise ValueError(f"unknown queue_impl {queue_impl!r}; choose from "
                     f"{QUEUE_IMPLS}")


def batch_take(leaf_t, leaf_typ, root_t, root_slot, rx_typ, batch_pop: int):
    """Select up to ``batch_pop`` same-timestamp BEACON_RX slots that a
    singleton loop would pop consecutively: the contiguous slot-order
    prefix of the root-time cohort consisting of RX events only,
    stopping at the first tied non-RX slot (that event must run through
    its own switch iteration, and may push new events).  RX handlers
    push nothing and only write view matrices, so the cohort is static
    across the prefix — popping the whole prefix in one body iteration
    is bitwise the sequential order.  Returns ``(slots, ok)``, both
    (batch_pop,); lane 0 is ALWAYS the root slot (the singleton
    fallback when the root is not an RX event)."""
    q = leaf_t.shape[0]
    sl = jnp.arange(q, dtype=jnp.int32)
    eq = leaf_t == root_t
    isrx = jnp.logical_and(eq, leaf_typ == jnp.float32(rx_typ))
    blocked = jnp.logical_and(eq, jnp.logical_not(isrx))
    first_block = jnp.min(jnp.where(blocked, sl, q))
    take = jnp.logical_and(isrx, sl < first_block)
    csum = jnp.cumsum(take.astype(jnp.int32))
    b = jnp.minimum(csum[-1], batch_pop)
    sel = jnp.searchsorted(csum, jnp.arange(batch_pop, dtype=jnp.int32) + 1,
                           side="left").astype(jnp.int32)
    slots = sel.at[0].set(root_slot)
    ok = jnp.arange(batch_pop) < jnp.maximum(b, 1)
    return slots, ok
