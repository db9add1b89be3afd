"""Event-queue structures for the TLM simulator (DESIGN.md §11).

The simulator's hot loop pops the earliest pending event once per
iteration.  The historical implementation (``queue_impl="linear"``) finds
it with ``jnp.argmin`` over the whole ``(queue_cap,)`` ``ev_time`` array,
checks termination with a queue-wide ``min``, and inserts batches with a
queue-wide stable ``argsort`` — O(Q)-to-O(Q log Q) work per event, which
ROADMAP.md names as the blocker for the paper's m=256/k=256 distributed
configuration on non-ideal fabrics (every beacon there fans out into k-1
BEACON_RX events, so Q must be large exactly where the per-event scan
hurts most).

This module provides two replacements, selected through the static
``queue_impl`` axis on ``SimShape`` (one XLA program per value):

``"tree"`` — the name is kept for the configurations and goldens that
name it; the structure is a **flat leaf array with a dense root**.  The
whole queue lives in ONE ``(Q + S + S2, 6)`` f32 array ``evq_tree``
(Q = queue_cap; S per-segment free counters; S2 super-segment counters):

  rows 0..Q       per-slot leaf rows [time, slot, ev_type, a0, a1, a2],
                  slot j at row j; INF time marks a free slot.  Slot
                  indices and payloads are small exact integers in f32
                  (queue_cap is capped at 2**24, event arguments are
                  app/cluster/PE indices and counts far below it).
  rows Q..Q+S     per-ALLOC_SEG-slot free counters (column 0).
  rows Q+S..+S2   per-SUPER_SEG-segment super counters (column 0) —
                  each the sum of its 64 segment counters, kept in sync
                  by every pop/push so large-Q allocation can search the
                  counter hierarchy instead of cumsum-ing all S segments
                  (see ``_alloc``).

  The next event is the ``(6,)`` root mirror ``evq_root``: every commit
  ends with ONE ``jnp.argmin`` over the Q leaf times and copies that
  leaf's row.  An earlier layout kept a tournament tree of winner rows
  above the leaves and repaired the touched root paths level by level.
  On a TPU v5e each level's gather and scatter ran one after another
  (13-15 levels of 81-262 us each at Q = 8192 / 32768), while one dense
  reduction over the leaf times reads a few MB; the internal rows also
  doubled every whole-buffer copy and select of the batched loop.

``"calendar"`` — a **bucketed calendar prototype** for the Q=32k+
regime: ONE ``(1 + Q + NB + S + S2, 6)`` f32 array ``evq_cal``:

  row 0           the root row — the global lexmin-(time, slot) event,
                  maintained by every commit so cond/peek/pop read it
                  O(1) exactly like the tree root.
  rows 1..1+Q     per-slot leaf rows, same [time, slot, type, a0..a2]
                  record as the tree leaves.
  rows 1+Q..+NB   bucket summary rows: bucket b = floor(t/W) mod NB
                  holds the lexmin-(time, slot) row among its live
                  events (W = ``cal_width``, tuned from the c_b/c_s tick
                  granularity; NB = CAL_BUCKETS).  Buckets partition by
                  time-of-year, but a summary is the min over ALL years
                  mapped to it, so the root (min over summaries) is
                  exact regardless of wrapping — W only shapes the
                  work distribution, never correctness.
  remaining rows  the same segment/super free counters as the tree.

  Push maintenance is incremental (scatter-min of the pushed rows into
  their buckets, O(batch + NB)); a pop rebuilds only the popped bucket
  (all same-timestamp pops share one bucket) with a masked O(Q)
  reduction — ~2 fused passes instead of linear's argmin-plus-argsort,
  amortized further by ``batch_pop``.  Classic calendar-queue O(1) does
  not materialize under fixed-shape XLA (no host-side resizing or
  data-dependent bucket walks), so this stays an honest O(Q/commit)
  prototype; see benchmarks/README.md for the measured linear/tree/
  calendar crossover.

One array per queue is the point, not a convenience: XLA:CPU updates a
chain of gathers-then-scatters on a single buffer in place, but a
second scatter whose indices derive from a read of another array forces
a full copy of the big buffer per event (measured ~60-100 us at
Q=32768 — more than the whole pop).  Fusing payloads, summaries and
counters into one buffer keeps every per-event write on one array:

  cond/peek  read the root mirror: O(1) instead of a queue-wide ``min``;
             pop needs no payload gathers at all.
  pop        the root IS (t, slot, type, args).
  commit     ``commit`` applies a whole body iteration's pops AND pushes
             as one scatter chain (clear pops -> return counters ->
             allocate -> write push leaves -> take counters), then sets
             the root from the final leaf times.  Handlers stage their
             push requests instead of pushing mid-handler, so the body
             issues exactly one commit — the fused-commit architecture
             that keeps every queue scatter out of ``lax.switch``
             branches (a scatter inside a branch region forces XLA:CPU
             to copy the whole buffer in the executed branch; measured
             ~1.4 ms/iter at 4 MB vs ~40 us staged).
  bulk push  allocate slots from the free counters: a cumsum +
             ``searchsorted`` over the segment counters finds each
             entry's segment, a gathered (n, 64) window of leaf times
             finds the exact slot — so the j-th masked entry takes the
             j-th lowest free slot, bitwise the linear impl's
             first-free-slot rule — then one scatter writes the leaves.

Everything is fixed-shape with no data-dependent control flow: updates
are ``.at[].set`` writes with traced indices (out-of-range lanes dropped
via ``mode="drop"``), so masked entries simply write nothing.  That
keeps the structure vmap-able and scan-friendly — ``sweep.py``'s "vmap"
and "seq" modes stay bitwise identical under ``queue_impl="tree"``
(tests/test_eventq.py), and the whole queue state is one ordinary
state-dict leaf.

Tie-breaking contract: ``jnp.argmin`` returns the LOWEST index among
equal minima, and same-timestamp events must pop in identical order
under every impl.  The tree's root is that argmin over the leaf times
itself, so it is the lexmin of (time, slot)
(tests/test_eventq.py::test_pop_slot_matches_argmin_under_ties); the
calendar keeps the same contract through its explicit (time, slot)
lexmin.  ``batch_take`` extends the contract to same-timestamp batching:
the selected window is the contiguous slot-order prefix of the
root-time cohort consisting of BEACON_RX events only, stopping at the
first tied non-RX slot — exactly the events a singleton loop would pop
consecutively (BEACON_RX handlers push nothing, so the cohort cannot
change mid-prefix).

``"linear"`` keeps the historical code operation-for-operation — the
golden anchor every frozen sha in tests/test_sweep.py gates — and the
other impls route pop/push through this module with bitwise-identical
results.
"""
from __future__ import annotations

import jax.numpy as jnp

INF = jnp.float32(1e18)

QUEUE_IMPLS = ("linear", "tree", "calendar")

# Free-slot accounting granularity: one counter per ALLOC_SEG queue
# slots.  64 keeps the per-push cumsum at Q/64 elements (512 at the
# paper-scale Q=32768).  The within-segment search reads each entry's
# 64 free flags as one row of a dense (S, 64) table built from the leaf
# times in one pass, not as n x 64 gathered leaf times: a TPU gather
# costs about 10 ns per index, and each leaf time sits in its own
# (..., 6) row, padded to 128 lanes, so a per-slot window cost more than
# a whole pass over the leaves (DESIGN.md §11).
ALLOC_SEG = 64

# Segments per super counter.  Kept in sync on every pop/push; the
# allocator only *searches* through the super level once the flat
# segment cumsum would be wider than HIER_MIN_SEGS (below that the flat
# cumsum is cheaper than the per-entry (n, 64) segment window the
# hierarchy needs — measured in DESIGN.md §11).
SUPER_SEG = 64
HIER_MIN_SEGS = 1024

# Calendar-queue bucket count (static; capped by queue_cap).
CAL_BUCKETS = 256

# Queue slots (and event payloads) are stored as exact small integers in
# the tree's f32 columns.
MAX_QUEUE_CAP = 1 << 24

# Row layout: [time, slot, ev_type, a0, a1, a2].
ROW_W = 6


def seg_count(queue_cap: int) -> int:
    """Number of ALLOC_SEG-slot segments covering the queue."""
    return -(-queue_cap // ALLOC_SEG)


def super_count(queue_cap: int) -> int:
    """Number of SUPER_SEG-segment super counters."""
    return -(-seg_count(queue_cap) // SUPER_SEG)


def cal_buckets(queue_cap: int) -> int:
    """Static calendar bucket count for a queue (never exceeds Q)."""
    return min(CAL_BUCKETS, max(1, queue_cap))


# --------------------------------------------------------------------------
# Full builds (vectorized, O(Q)): initial state + test fixtures.
# --------------------------------------------------------------------------

def _leaf_rows(times, typ, a):
    q = times.shape[0]
    times = jnp.asarray(times, jnp.float32)
    typ = jnp.zeros((q,), jnp.float32) if typ is None \
        else jnp.asarray(typ, jnp.float32)
    a = jnp.zeros((q, 3), jnp.float32) if a is None \
        else jnp.asarray(a, jnp.float32)
    return jnp.concatenate([
        jnp.stack([times, jnp.arange(q, dtype=jnp.float32), typ], -1),
        a], axis=-1)


def _counter_rows(times):
    """Segment + super free-counter rows from the leaf times."""
    free = jnp.asarray(times, jnp.float32) >= INF
    segc = build_freecnt(free).astype(jnp.float32)
    s = segc.shape[0]
    s2 = -(-s // SUPER_SEG)
    sup = jnp.concatenate([segc, jnp.zeros((s2 * SUPER_SEG - s,))]) \
        .reshape(s2, SUPER_SEG).sum(axis=1)
    rows = jnp.zeros((s + s2, ROW_W))
    return rows.at[:, 0].set(jnp.concatenate([segc, sup]))


def build_tree(times, typ=None, a=None):
    """(queue_cap,) event times (+ optional payloads: ``typ`` (Q,) and
    ``a`` (Q, 3)) -> the full ``evq_tree`` array: the leaf rows, then the
    free counters."""
    q = times.shape[0]
    if q > MAX_QUEUE_CAP:
        raise ValueError(f"queue_cap {q} exceeds the exact-f32 slot-index "
                         f"range ({MAX_QUEUE_CAP})")
    return jnp.concatenate([_leaf_rows(times, typ, a), _counter_rows(times)])


def _root(tree, queue_cap: int):
    """The next event of an ``evq_tree`` array: the row of the leaf at
    the lowest slot among equal minimum times (``jnp.argmin``'s rule)."""
    return tree[jnp.argmin(tree[:queue_cap, 0])]


def _seg_rows(free_mask):
    """(queue_cap,) bool free mask -> (S, ALLOC_SEG) bool, one row per
    segment; the slots past queue_cap in the last row read taken."""
    q = free_mask.shape[0]
    s = seg_count(q)
    pad = jnp.zeros((s * ALLOC_SEG - q,), bool)
    return jnp.concatenate([free_mask, pad]).reshape(s, ALLOC_SEG)


def build_freecnt(free_mask):
    """(queue_cap,) bool free mask -> (S,) i32 per-segment free-slot
    counts (the last segment may cover fewer than ALLOC_SEG slots)."""
    return _seg_rows(jnp.asarray(free_mask, bool)).sum(axis=1) \
        .astype(jnp.int32)


def build_cal(times, typ=None, a=None, width=None):
    """(queue_cap,) event times (+ optional payloads) -> the full
    ``evq_cal`` array: root row, leaf rows, bucket summaries, counters."""
    q = times.shape[0]
    if q > MAX_QUEUE_CAP:
        raise ValueError(f"queue_cap {q} exceeds the exact-f32 slot-index "
                         f"range ({MAX_QUEUE_CAP})")
    nb = cal_buckets(q)
    width = jnp.float32(1.0) if width is None \
        else jnp.asarray(width, jnp.float32)
    leaves = _leaf_rows(times, typ, a)
    cal = jnp.concatenate([
        jnp.zeros((1, ROW_W)).at[0, 0].set(INF),
        leaves,
        jnp.zeros((nb, ROW_W)).at[:, 0].set(INF),
        _counter_rows(times)])
    # exact summaries + root via the commit path's full-rebuild mode
    st = {"evq_cal": cal, "dropped": jnp.zeros((), jnp.int32)}
    st = cal_commit(st, jnp.zeros((0,), jnp.int32), jnp.zeros((0,), bool),
                    INF, leaves[:, 0] < INF, leaves[:, 0], leaves[:, 2],
                    leaves[:, 3], leaves[:, 4], leaves[:, 5], q, width,
                    _prebuilt_slots=True)
    return st["evq_cal"]


def queue_state(queue_cap: int) -> dict:
    """The state-dict leaf of ``queue_impl="tree"`` (an empty queue: all
    times INF, all slots free).  The linear impl's ``ev_time`` /
    ``ev_type`` / ``ev_a`` arrays do not exist in tree mode — times and
    payloads live in the tree rows (``leaf_times``/``leaf_payloads``).

    ``evq_root`` holds the root row (``_root``) in a separate
    (ROW_W,) buffer, maintained by ``pop``/``commit``.  The simulator
    body and loop condition read the next event from it, NEVER from the
    big tree buffer: any read of the tree outside the commit chain gets
    rematerialized by XLA fusion at its consumers, and a read of the
    original value after the in-place chain has started makes
    copy-insertion clone the whole buffer every iteration (measured
    ~850 us/event at the k=256 paper point — the entire cost of the
    pre-fused simulator)."""
    tr = build_tree(jnp.full((queue_cap,), INF))
    return {"evq_tree": tr, "evq_root": _root(tr, queue_cap)}


def cal_state(queue_cap: int) -> dict:
    """The state-dict leaf of ``queue_impl="calendar"`` (empty queue).
    ``evq_root`` mirrors row 0 (see ``queue_state``)."""
    c = build_cal(jnp.full((queue_cap,), INF))
    return {"evq_cal": c, "evq_root": c[0]}


# --------------------------------------------------------------------------
# Views (tests, debugging).
# --------------------------------------------------------------------------

def leaf_times(st, queue_cap: int):
    """(Q,) per-slot event times from the leaf rows — INF marks a free
    slot.  Authoritative in tree mode (there is no ``ev_time``)."""
    return st["evq_tree"][:queue_cap, 0]


def leaf_payloads(st, queue_cap: int):
    """(Q, 4) per-slot [ev_type, a0, a1, a2] from the leaf rows."""
    return st["evq_tree"][:queue_cap, 2:]


def freecnt(st, queue_cap: int):
    """(S,) i32 per-segment free counts from the counter rows."""
    s = seg_count(queue_cap)
    return st["evq_tree"][queue_cap:queue_cap + s, 0].astype(jnp.int32)


def supercnt(st, queue_cap: int):
    """(S2,) i32 super-segment free counts from the counter rows."""
    return st["evq_tree"][queue_cap + seg_count(queue_cap):, 0] \
        .astype(jnp.int32)


def cal_leaf_times(st, queue_cap: int):
    """(Q,) per-slot event times from the calendar leaf rows."""
    return st["evq_cal"][1:1 + queue_cap, 0]


def cal_leaf_payloads(st, queue_cap: int):
    """(Q, 4) per-slot [ev_type, a0, a1, a2] from the calendar leaves."""
    return st["evq_cal"][1:1 + queue_cap, 2:]


def cal_freecnt(st, queue_cap: int):
    """(S,) i32 per-segment free counts from the calendar counters."""
    base = 1 + queue_cap + cal_buckets(queue_cap)
    s = seg_count(queue_cap)
    return st["evq_cal"][base:base + s, 0].astype(jnp.int32)


# --------------------------------------------------------------------------
# Queue operations on the simulator state dict.
# --------------------------------------------------------------------------

def peek_time(st):
    """Earliest pending event time — the root mirror, O(1).  The
    tree-mode while-loop condition is ``peek_time(st) < INF``."""
    return st["evq_root"][0]


def cal_peek_time(st):
    """Calendar twin of ``peek_time``: the maintained root row."""
    return st["evq_cal"][0, 0]


def pop(st, queue_cap: int):
    """Pop the earliest event: the root mirror IS the event — no payload
    gathers.  Commits the pop alone: clear the leaf, return its
    counters, set the dense root.  Returns ``(st, t, slot, typ, a)``
    with ``typ`` i32 and ``a`` (3,) i32 — exactly the values linear mode
    reads from ``ev_type``/``ev_a``."""
    root = st["evq_root"]
    t = root[0]
    slot = root[1].astype(jnp.int32)
    typ = root[2].astype(jnp.int32)
    a = root[3:].astype(jnp.int32)
    z = jnp.zeros((0,), jnp.float32)
    st = commit(st, slot[None], jnp.ones((1,), bool), jnp.zeros((0,), bool),
                z, z, z, z, z, queue_cap)
    return st, t, slot, typ, a


def _alloc(arr, leaf_base, cnt_base, sup_base, s, queue_cap, mask, times):
    """Shared slot allocator: j-th masked entry -> j-th lowest free slot
    (bitwise the linear impl's first-free-slot rule), reading the free
    counters at ``cnt_base`` and the leaf times at ``leaf_base`` of one
    queue array.  Returns ``(slot, segc, ok, n_dropped)``.

    Two search strategies, chosen statically: below HIER_MIN_SEGS
    segments a flat cumsum over all S segment counters is cheapest; at
    large Q the allocator cumsums the S2 = S/64 super counters instead
    and only gathers the (n, 64) segment-counter window of each entry's
    super — O(S/64 + 64n) vs O(S), the touched-segments fix of the old
    unconditional O(Q/64) cumsum (DESIGN.md §11)."""
    q = queue_cap
    mask = jnp.asarray(mask, bool)
    times = jnp.asarray(times, jnp.float32)
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1    # rank among masked
    cnt = mask.sum()
    if s < HIER_MIN_SEGS:
        csum = jnp.cumsum(arr[cnt_base:cnt_base + s, 0].astype(jnp.int32))
        total_free = csum[-1]
        # first segment whose cumulative free count reaches rank+1
        seg = jnp.searchsorted(csum, rank + 1, side="left").astype(jnp.int32)
        segc = jnp.minimum(seg, s - 1)               # clamped (overflow)
        r = rank - jnp.where(segc > 0, csum[segc - 1], 0)  # rank in segment
    else:
        s2 = -(-s // SUPER_SEG)
        csup = jnp.cumsum(arr[sup_base:sup_base + s2, 0].astype(jnp.int32))
        total_free = csup[-1]
        sup = jnp.searchsorted(csup, rank + 1, side="left").astype(jnp.int32)
        supc = jnp.minimum(sup, s2 - 1)
        r_sup = rank - jnp.where(supc > 0, csup[supc - 1], 0)
        segw_cols = supc[:, None] * SUPER_SEG \
            + jnp.arange(SUPER_SEG)[None, :]         # (n, 64) segment ids
        segw = arr[cnt_base + jnp.minimum(segw_cols, s - 1), 0] \
            .astype(jnp.int32) * (segw_cols < s)
        cs = jnp.cumsum(segw, axis=1)
        seg_off = jnp.argmax(cs >= r_sup[:, None] + 1, axis=1) \
            .astype(jnp.int32)
        segc = jnp.minimum(supc * SUPER_SEG + seg_off, s - 1)
        below = jnp.take_along_axis(
            cs, jnp.maximum(seg_off - 1, 0)[:, None], axis=1)[:, 0]
        r = r_sup - jnp.where(seg_off > 0, below, 0)
    # the (r+1)-th free slot inside the segment: one dense pass marks
    # the free leaves (INF time) as an (S, 64) table, and each entry
    # takes its segment's row (see ALLOC_SEG)
    free_w = _seg_rows(arr[leaf_base:leaf_base + q, 0] >= INF)[segc]
    hit = jnp.logical_and(free_w,
                          jnp.cumsum(free_w, axis=1) == r[:, None] + 1)
    slot = segc * ALLOC_SEG + jnp.argmax(hit, axis=1).astype(jnp.int32)
    ok = jnp.logical_and(mask, rank < total_free)
    return slot, segc, ok, jnp.maximum(cnt - total_free, 0)


def commit(st, pop_slots, pop_ok, mask, times, typ, a0, a1, a2,
           queue_cap: int):
    """Apply one body iteration's pops AND pushes as ONE scatter chain on
    ``evq_tree``: clear the popped leaves, return their free counters,
    allocate push slots (the allocator sees the just-freed slots, exactly
    the sequential pop-then-push semantics), write the push leaves, take
    their counters, then set the root mirror from the final leaf times.
    ``typ`` may be a scalar or a per-entry array.

    ``pop_slots``/``pop_ok`` are (B,) — B = 0 makes this a pure bulk
    push; masked lanes (``pop_ok`` False) are dropped from every write."""
    q = queue_cap
    tree = st["evq_tree"]
    s = seg_count(q)
    cnt_base = q
    sup_base = q + s
    oob = tree.shape[0]
    pop_slots = jnp.asarray(pop_slots, jnp.int32)
    pop_ok = jnp.asarray(pop_ok, bool)
    nb = pop_slots.shape[0]

    # -- clear popped leaves + return their counters ---------------------
    clear_rows = jnp.zeros((nb, ROW_W)) \
        .at[:, 0].set(INF) \
        .at[:, 1].set(pop_slots.astype(jnp.float32))
    tree = tree.at[jnp.where(pop_ok, pop_slots, oob)] \
        .set(clear_rows, mode="drop")
    pseg = pop_slots // ALLOC_SEG
    inc_idx = jnp.concatenate([
        jnp.where(pop_ok, cnt_base + pseg, oob),
        jnp.where(pop_ok, sup_base + pseg // SUPER_SEG, oob)])
    tree = tree.at[inc_idx, 0].add(
        jnp.ones((2 * nb,), jnp.float32), mode="drop")

    # -- allocation (sees the freed slots) + push leaf writes ------------
    mask = jnp.asarray(mask, bool)
    times = jnp.asarray(times, jnp.float32)
    slot, segc, ok, n_drop = _alloc(tree, 0, cnt_base, sup_base, s, q,
                                    mask, times)
    st = dict(st)
    st["dropped"] = st["dropped"] + n_drop
    leaf_rows = jnp.stack([
        times, slot.astype(jnp.float32),
        jnp.broadcast_to(jnp.asarray(typ, jnp.float32), mask.shape),
        jnp.broadcast_to(jnp.asarray(a0, jnp.float32), mask.shape),
        jnp.broadcast_to(jnp.asarray(a1, jnp.float32), mask.shape),
        jnp.broadcast_to(jnp.asarray(a2, jnp.float32), mask.shape)], -1)
    tree = tree.at[jnp.where(ok, slot, oob)].set(leaf_rows, mode="drop")
    # an ok entry with time >= INF takes its slot in the assignment order
    # (as in linear mode) but leaves the leaf free, so it must not
    # decrement the segment counter — counters always equal the number
    # of INF leaves per segment (tests/test_eventq.py)
    live = jnp.logical_and(ok, times < INF)
    dec_idx = jnp.concatenate([
        jnp.where(live, cnt_base + segc, oob),
        jnp.where(live, sup_base + segc // SUPER_SEG, oob)])
    tree = tree.at[dec_idx, 0].add(
        jnp.full((2 * mask.shape[0],), -1.0), mode="drop")
    st["evq_tree"] = tree
    # the dense root reads the buffer only after its last in-place write,
    # so copy-insertion clones nothing (queue_state)
    st["evq_root"] = _root(tree, q)
    return st


def bulk_push(st, mask, times, typ, a0, a1, a2, queue_cap: int):
    """Tree-mode twin of ``sim._bulk_push``: insert the masked entries of
    an event batch with the identical slot-assignment rule (the j-th
    masked entry takes the j-th lowest free slot) and identical overflow
    accounting (excess masked entries drop).  A pure-push ``commit``."""
    return commit(st, jnp.zeros((0,), jnp.int32), jnp.zeros((0,), bool),
                  mask, times, typ, a0, a1, a2, queue_cap)


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


# --------------------------------------------------------------------------
# Calendar queue.
# --------------------------------------------------------------------------

def _cal_bases(queue_cap: int):
    nb = cal_buckets(queue_cap)
    sum_base = 1 + queue_cap
    cnt_base = sum_base + nb
    sup_base = cnt_base + seg_count(queue_cap)
    return nb, sum_base, cnt_base, sup_base


def _bucket(t, width, nb):
    """floor(t / W) mod NB — only ever evaluated on finite times."""
    return jnp.mod(jnp.floor(t / width), nb).astype(jnp.int32)


def cal_commit(st, pop_slots, pop_ok, root_t, mask, times, typ, a0, a1, a2,
               queue_cap: int, width, _prebuilt_slots: bool = False):
    """Calendar twin of ``commit``: clear pops, return counters, allocate
    (shared ``_alloc`` — identical slot rule and drop accounting), write
    push leaves, then maintain the summaries: rebuild the single popped
    bucket from its leaves (all pops in a batch share the root time,
    hence one bucket), lexmin-merge the pushed rows into theirs, and
    rewrite the root row from the NB summaries.  ``root_t`` is the
    shared timestamp of the popped lanes (any value when B = 0).

    ``_prebuilt_slots=True`` is the ``build_cal`` path: entry j IS slot
    j (the leaves are already written), so allocation is skipped and
    every bucket is rebuilt."""
    q = queue_cap
    nb, sum_base, cnt_base, sup_base = _cal_bases(q)
    s = seg_count(q)
    cal = st["evq_cal"]
    oob = cal.shape[0]
    pop_slots = jnp.asarray(pop_slots, jnp.int32)
    pop_ok = jnp.asarray(pop_ok, bool)
    npop = pop_slots.shape[0]
    width = jnp.asarray(width, jnp.float32)

    # -- clear popped leaves + return their counters ---------------------
    clear_rows = jnp.zeros((npop, ROW_W)) \
        .at[:, 0].set(INF) \
        .at[:, 1].set(pop_slots.astype(jnp.float32))
    cal = cal.at[jnp.where(pop_ok, 1 + pop_slots, oob)] \
        .set(clear_rows, mode="drop")
    pseg = pop_slots // ALLOC_SEG
    inc_idx = jnp.concatenate([
        jnp.where(pop_ok, cnt_base + pseg, oob),
        jnp.where(pop_ok, sup_base + pseg // SUPER_SEG, oob)])
    cal = cal.at[inc_idx, 0].add(
        jnp.ones((2 * npop,), jnp.float32), mode="drop")

    # -- allocation + push leaf writes -----------------------------------
    mask = jnp.asarray(mask, bool)
    times = jnp.asarray(times, jnp.float32)
    st = dict(st)
    if _prebuilt_slots:
        slot = jnp.arange(q, dtype=jnp.int32)
        ok = mask
    else:
        slot, segc, ok, n_drop = _alloc(cal, 1, cnt_base, sup_base, s, q,
                                        mask, times)
        st["dropped"] = st["dropped"] + n_drop
        leaf_rows = jnp.stack([
            times, slot.astype(jnp.float32),
            jnp.broadcast_to(jnp.asarray(typ, jnp.float32), mask.shape),
            jnp.broadcast_to(jnp.asarray(a0, jnp.float32), mask.shape),
            jnp.broadcast_to(jnp.asarray(a1, jnp.float32), mask.shape),
            jnp.broadcast_to(jnp.asarray(a2, jnp.float32), mask.shape)], -1)
        cal = cal.at[jnp.where(ok, 1 + slot, oob)] \
            .set(leaf_rows, mode="drop")
        live = jnp.logical_and(ok, times < INF)
        dec_idx = jnp.concatenate([
            jnp.where(live, cnt_base + segc, oob),
            jnp.where(live, sup_base + segc // SUPER_SEG, oob)])
        cal = cal.at[dec_idx, 0].add(
            jnp.full((2 * mask.shape[0],), -1.0), mode="drop")

    # -- summary maintenance (value space) -------------------------------
    # The summary block and root row are COMPUTED from reads of the
    # buffer and written exactly once at the end.  Interleaving summary
    # writes with reads of older buffer values (the previous layout:
    # write popped-bucket row, read leaves + summaries, write block,
    # read block, write root) keeps the pre-write value live across
    # each in-place update, so XLA's copy-insertion materializes the
    # WHOLE (1+Q+NB+S+S2, 6) array per loop iteration — the §11
    # pathology, caught by `repro.analysis` hlo_lint.  All values are
    # bit-identical: selects replace scatters, no arithmetic changes.
    leaf_t = cal[1:1 + q, 0]
    slots_f = jnp.arange(q, dtype=jnp.float32)
    alive = leaf_t < INF
    empty_row = jnp.zeros((ROW_W,)).at[0].set(INF)
    if _prebuilt_slots:
        # full rebuild: exact lexmin-(time, slot) winner per bucket
        tb = jnp.where(alive, _bucket(jnp.where(alive, leaf_t, 0.0),
                                      width, nb), nb)
        tmin = jnp.full((nb + 1,), INF).at[tb].min(
            jnp.where(alive, leaf_t, INF), mode="drop")
        smin = jnp.full((nb + 1,), INF).at[tb].min(
            jnp.where(jnp.logical_and(alive, leaf_t == tmin[
                jnp.minimum(tb, nb)]), slots_f, INF), mode="drop")
        win = smin[:nb]
        found = win < INF
        rows = cal[1 + jnp.minimum(win, q - 1).astype(jnp.int32)]
        summ = jnp.where(found[:, None], rows, empty_row[None, :])
    else:
        # rebuild only the popped bucket (exact: pops share root_t)
        b_pop = _bucket(jnp.where(jnp.any(pop_ok), root_t, 0.0), width, nb)
        in_pop = jnp.logical_and(
            alive, _bucket(jnp.where(alive, leaf_t, 0.0), width, nb) == b_pop)
        # (a) popped-bucket rebuild from the leaves (sees the new pushes)
        t_in = jnp.where(in_pop, leaf_t, INF)
        tmin = jnp.min(t_in)
        smin = jnp.min(jnp.where(jnp.logical_and(in_pop, leaf_t == tmin),
                                 slots_f, INF))
        found = smin < INF
        row = cal[1 + jnp.minimum(smin, q - 1).astype(jnp.int32)]
        new_sum = jnp.where(found, row, empty_row)
        hit = jnp.logical_and(jnp.any(pop_ok),
                              jnp.arange(nb) == b_pop)
        summ = jnp.where(hit[:, None], new_sum[None, :],
                         cal[sum_base:sum_base + nb])
        # (b) lexmin-merge the pushed rows into their buckets
        plive = jnp.logical_and(ok, times < INF)
        pb = jnp.where(plive, _bucket(jnp.where(plive, times, 0.0),
                                      width, nb), nb)
        pt = jnp.full((nb + 1,), INF).at[pb].min(
            jnp.where(plive, times, INF), mode="drop")
        ps = jnp.full((nb + 1,), INF).at[pb].min(
            jnp.where(jnp.logical_and(plive, times == pt[
                jnp.minimum(pb, nb)]), slot.astype(jnp.float32), INF),
            mode="drop")
        cand_s = ps[:nb]
        cand_ok = cand_s < INF
        cand = cal[1 + jnp.minimum(cand_s, q - 1).astype(jnp.int32)]
        take_new = jnp.logical_and(cand_ok, jnp.logical_or(
            cand[:, 0] < summ[:, 0],
            jnp.logical_and(cand[:, 0] == summ[:, 0],
                            cand[:, 1] < summ[:, 1])))
        summ = jnp.where(take_new[:, None], cand, summ)

    # -- root from the NB summaries, then the single block write ---------
    best_t = jnp.min(summ[:, 0])
    bsel = jnp.where(summ[:, 0] == best_t, summ[:, 1], INF)
    bi = jnp.argmin(bsel)
    root_row = summ[bi]
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


def cal_pop(st, queue_cap: int, width):
    """Singleton calendar pop (tests/harness): read the root row, commit
    the pop with no pushes.  Returns ``(st, t, slot, typ, a)`` exactly
    like ``pop``."""
    root = st["evq_cal"][0]
    t = root[0]
    slot = root[1].astype(jnp.int32)
    typ = root[2].astype(jnp.int32)
    a = root[3:].astype(jnp.int32)
    z = jnp.zeros((0,), jnp.float32)
    st = cal_commit(st, slot[None], jnp.ones((1,), bool), t,
                    jnp.zeros((0,), bool), z, z, z, z, z, queue_cap, width)
    return st, t, slot, typ, a


def empty(queue_cap: int) -> dict:
    """A minimal standalone queue state (no simulator around it) — the
    harness tests/test_eventq.py drives push/pop against directly."""
    return {"dropped": jnp.zeros((), jnp.int32)} | queue_state(queue_cap)


def cal_empty(queue_cap: int) -> dict:
    """Standalone calendar queue state for the test harness."""
    return {"dropped": jnp.zeros((), jnp.int32)} | cal_state(queue_cap)
