"""Tree event queue (core/eventq.py, DESIGN.md §11), a flat leaf array
with a dense root: pop order equals sorted order under ties, the root
mirror equals numpy's first-index argmin row after every commit, the
counters equal the free leaves, drop parity with the linear impl, a
commit whose structure does not grow with Q, and vmap == seq bitwise
under queue_impl="tree"."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import eventq as EQ
from repro.core import sweep as SW
from repro.core import workloads as W
from repro.core.sim import SimParams

INF = float(EQ.INF)

_jit_pop = jax.jit(EQ.pop, static_argnums=1)
_jit_push = jax.jit(EQ.bulk_push, static_argnums=(3, 7))


def _times(q, cap):
    """Per-slot event times from the leaf rows (INF = free)."""
    return np.asarray(EQ.leaf_times(q, cap))


def _from_times(cap, times):
    """Standalone queue state whose slots hold ``times`` (INF = free)."""
    q = dict(EQ.empty(cap))
    q["evq_tree"] = EQ.build_tree(jnp.asarray(times, jnp.float32))
    q["evq_root"] = q["evq_tree"][int(np.argmin(times))]
    return q


def _push(q, times, mask=None, typ=1, cap=None):
    n = len(times)
    times = jnp.asarray(times, jnp.float32)
    mask = jnp.ones((n,), bool) if mask is None else jnp.asarray(mask, bool)
    z = jnp.zeros((n,), jnp.int32)
    return _jit_push(q, mask, times, typ, z, z, z, cap)


def _drain(q, cap):
    """Pop until empty; returns [(t, slot), ...]."""
    out = []
    while float(EQ.peek_time(q)) < INF:
        q, t, slot, typ, a = _jit_pop(q, cap)
        out.append((float(t), int(slot)))
    return q, out


def _assert_root_and_counters(q, cap):
    """The oracle after every commit: ``evq_root`` is the leaf row at
    numpy's first-index argmin of the leaf times, and the segment and
    super counters equal the INF leaves they cover."""
    tree = np.asarray(q["evq_tree"])
    lt = tree[:cap, 0]
    assert np.array_equal(np.asarray(q["evq_root"]),
                          tree[int(np.argmin(lt))])
    free = lt >= INF
    segs = -(-cap // EQ.ALLOC_SEG)
    seg = np.zeros(segs * EQ.ALLOC_SEG, int)
    seg[:cap] = free
    seg = seg.reshape(segs, EQ.ALLOC_SEG).sum(1)
    sups = -(-segs // EQ.SUPER_SEG)
    sup = np.zeros(sups * EQ.SUPER_SEG, int)
    sup[:segs] = seg
    sup = sup.reshape(sups, EQ.SUPER_SEG).sum(1)
    assert np.array_equal(seg, np.asarray(EQ.freecnt(q, cap)))
    assert np.array_equal(sup, np.asarray(EQ.supercnt(q, cap)))


def test_pop_order_is_sorted_with_ties():
    """Pops come out sorted by (time, slot) — the argmin rule — including
    heavy timestamp ties."""
    rng = np.random.default_rng(0)
    cap = 128
    times = rng.integers(0, 8, size=100).astype(np.float32)  # many ties
    q = _push(EQ.empty(cap), times, cap=cap)
    q, popped = _drain(q, cap)
    assert len(popped) == 100
    # push order == slot order here (fresh queue), so expected pop order
    # sorts by (time, slot)
    expect = sorted((t, s) for s, t in enumerate(times.tolist()))
    assert popped == [(t, s) for t, s in expect]
    assert int(q["dropped"]) == 0
    # drained: every slot free again
    assert bool((_times(q, cap) >= INF).all())


def test_pop_returns_payload():
    """The popped root row carries the event payload exactly."""
    cap = 64
    q = _jit_push(EQ.empty(cap), jnp.ones((2,), bool),
                  jnp.asarray([9.0, 7.0], jnp.float32), 3,
                  jnp.asarray([5, 11], jnp.int32),
                  jnp.asarray([6, 22], jnp.int32),
                  jnp.asarray([8, 33], jnp.int32), cap)
    _, t, slot, typ, a = _jit_pop(q, cap)
    assert (float(t), int(slot), int(typ)) == (7.0, 1, 3)
    assert np.asarray(a).tolist() == [11, 22, 33]


@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([32, 100, 128]))
@settings(max_examples=10, deadline=None)
def test_interleaved_push_pop_matches_heap(seed, cap):
    """Random interleaving of batch pushes and pops behaves as a priority
    queue with (time, slot) ordering; after every commit the root mirror
    is the first-index argmin row and the counters match the leaves."""
    rng = np.random.default_rng(seed)
    q = EQ.empty(cap)
    live = {}                               # slot -> time (host reference)
    for _ in range(6):
        n = int(rng.integers(1, 12))
        times = rng.integers(0, 50, size=n).astype(np.float32)
        mask = rng.random(n) < 0.8
        before_free = sorted(s for s in range(cap) if s not in live)
        q = _push(q, times, mask=mask, cap=cap)
        _assert_root_and_counters(q, cap)
        for j, s in zip(np.flatnonzero(mask), before_free):
            live[int(s)] = float(times[j])
        for _ in range(int(rng.integers(0, 8))):
            if not live:
                break
            q, t, slot, _, _ = _jit_pop(q, cap)
            _assert_root_and_counters(q, cap)
            exp_t = min(live.values())
            exp_s = min(s for s, tv in live.items() if tv == exp_t)
            assert (float(t), int(slot)) == (exp_t, exp_s)
            del live[exp_s]
        assert sorted(np.flatnonzero(_times(q, cap) < INF).tolist()) \
            == sorted(live)


def test_bulk_push_path_repair_equals_full_rebuild():
    """After a large masked batch lands on scattered free slots, the
    incrementally written buffer is identical to a full build from its
    own leaf rows (payloads and counters included), and the root mirror
    is the first-index argmin row."""
    rng = np.random.default_rng(3)
    cap = 256
    q = _push(EQ.empty(cap), rng.uniform(1, 1e6, 200).astype(np.float32),
              typ=2, cap=cap)
    for _ in range(30):                     # free up scattered slots
        q, _, _, _, _ = _jit_pop(q, cap)
    times = rng.uniform(1, 1e6, 64).astype(np.float32)
    q = _push(q, times, mask=rng.random(64) < 0.5, typ=2, cap=cap)
    lt = jnp.asarray(_times(q, cap))
    pl = np.asarray(EQ.leaf_payloads(q, cap))
    rebuilt = EQ.build_tree(lt, typ=pl[:, 0], a=pl[:, 1:])
    assert np.array_equal(np.asarray(rebuilt), np.asarray(q["evq_tree"]))
    _assert_root_and_counters(q, cap)


def test_pop_slot_matches_argmin_under_ties():
    """The root a commit sets reproduces jnp.argmin's lowest-index-wins
    rule on adversarially tied inputs, all-free queues included."""
    rng = np.random.default_rng(7)
    cap = 64
    for i in range(50):
        times = rng.integers(0, 3, size=cap).astype(np.float32)
        if i % 10 == 0:
            times[:] = INF                  # empty: slot 0's row
        q = _push(EQ.empty(cap), times, cap=cap)
        assert int(q["evq_root"][1]) == int(np.argmin(times))
        assert float(EQ.peek_time(q)) == float(times.min())
        if times.min() < INF:
            _, t, slot, _, _ = _jit_pop(q, cap)
            assert int(slot) == int(np.argmin(times))
            assert float(t) == float(times.min())


def test_slot_assignment_matches_linear_rule():
    """The j-th masked entry takes the j-th lowest free slot — the linear
    impl's first-free-slot search — across segment boundaries."""
    cap = 256                               # spans 4 ALLOC_SEG=64 segments
    q = _push(EQ.empty(cap), np.full(cap, 5.0, np.float32), cap=cap)
    freed = [0, 1, 63, 64, 130, 200, 255]   # free a scattered set
    for _ in range(len(freed)):
        q, _, _, _, _ = _jit_pop(q, cap)    # pops are all t=5, slot order
    assert sorted(np.flatnonzero(_times(q, cap) >= INF).tolist()) \
        == list(range(7))
    # free specific scattered slots instead: rebuild that state directly
    ev = np.full(cap, 5.0, np.float32)
    ev[freed] = INF
    q = _from_times(cap, ev)
    mask = np.array([True, False, True, True, False, True, True])
    q = _push(q, np.arange(10.0, 17.0).astype(np.float32), mask=mask,
              cap=cap)
    got = {s: float(t) for s, t in enumerate(_times(q, cap))
           if t < INF and float(t) != 5.0}
    # masked entries (indices 0,2,3,5,6) land on freed slots in order
    assert got == {0: 10.0, 1: 12.0, 63: 13.0, 64: 15.0, 130: 16.0}


def test_inf_time_push_keeps_counters_in_sync():
    """A masked entry with time >= INF takes its slot in the assignment
    order (linear parity) but leaves the slot free — the segment
    counters must keep matching the INF-leaf count exactly."""
    cap = 128
    q = _push(EQ.empty(cap), [5.0, INF, 7.0], cap=cap)
    lt = _times(q, cap)
    # entry 1 consumed slot 1 in the assignment order but left it free
    assert (float(lt[0]), float(lt[2])) == (5.0, 7.0) and lt[1] >= INF
    assert np.array_equal(np.asarray(EQ.build_freecnt(lt >= INF)),
                          np.asarray(EQ.freecnt(q, cap)))
    # the freed-looking slot is allocatable again, counters still exact
    q = _push(q, [9.0], cap=cap)
    lt = _times(q, cap)
    assert float(lt[1]) == 9.0
    assert np.array_equal(np.asarray(EQ.build_freecnt(lt >= INF)),
                          np.asarray(EQ.freecnt(q, cap)))
    assert int(q["dropped"]) == 0


def test_overflow_drops_match_linear_accounting():
    """Excess masked entries drop exactly like the linear impl: the first
    total_free masked entries land, the tail is counted in dropped."""
    cap = 8
    q = _push(EQ.empty(cap), np.arange(1.0, 7.0).astype(np.float32),
              cap=cap)                                            # 6 in
    q = _push(q, np.arange(10.0, 15.0).astype(np.float32), cap=cap)  # 5 > 2
    assert int(q["dropped"]) == 3
    ev = _times(q, cap)
    assert float(ev[6]) == 10.0 and float(ev[7]) == 11.0
    # full queue: everything drops
    q = _push(q, np.array([99.0], np.float32), cap=cap)
    assert int(q["dropped"]) == 4


def _params(**kw):
    kw.setdefault("m", 16)
    kw.setdefault("k", 4)
    kw.setdefault("n_childs", 16)
    kw.setdefault("max_apps", 32)
    kw.setdefault("queue_cap", 512)
    return SimParams(**kw)


@pytest.mark.parametrize("topology", ["ideal", "mesh2d"])
def test_tree_vmap_equals_seq_bitwise(topology):
    """queue_impl="tree" keeps the sweep engine's bitwise vmap == seq
    contract on both the golden fabric and a non-ideal one."""
    p = _params(topology=topology, queue_impl="tree")
    wl = W.interference_batch(p, seeds=(0, 1), sim_len=2e5)
    kn = SW.knob_batch(dn_th=(2, 8))
    sv = SW.sweep(p.shape, kn, wl, 2e5, mode="vmap", topology=topology)
    ss = SW.sweep(p.shape, kn, wl, 2e5, mode="seq", topology=topology)
    for key in ("app_done", "app_arrive", "beacons_tx", "beacons_rx",
                "events_processed", "dropped"):
        assert np.array_equal(np.asarray(sv[key]), np.asarray(ss[key])), key


def test_tree_queue_state_shapes_and_cap_guard():
    qs = EQ.queue_state(512)
    s = 512 // EQ.ALLOC_SEG
    s2 = -(-s // EQ.SUPER_SEG)
    # leaf rows then counter rows: no internal nodes
    assert qs["evq_tree"].shape == (512 + s + s2, EQ.ROW_W)
    assert int(np.asarray(EQ.freecnt(qs, 512)).sum()) == 512
    assert int(np.asarray(EQ.supercnt(qs, 512)).sum()) == 512
    # an empty queue's root is slot 0's free row
    assert np.asarray(qs["evq_root"]).tolist() == [INF, 0, 0, 0, 0, 0]
    # non-power-of-two caps keep exactly queue_cap leaves (no padding)
    q100 = EQ.queue_state(100)
    assert q100["evq_tree"].shape == (100 + 2 + 1, EQ.ROW_W)
    assert _times(q100, 100).shape == (100,)
    with pytest.raises(ValueError):
        EQ.build_tree(jnp.zeros((EQ.MAX_QUEUE_CAP + 1,), jnp.float32))


def test_sim_rejects_unknown_queue_impl():
    with pytest.raises(ValueError):
        _params(queue_impl="radix")
    with pytest.raises(ValueError):
        SW.sweep(_params().shape, SW.knob_batch(dn_th=(1,)),
                 W.interference_batch(_params(), seeds=(0,), sim_len=1e5),
                 1e5, queue_impl="radix")
    # "calendar" is a real impl now (PR 7) — constructing params must work
    assert _params(queue_impl="calendar").queue_impl == "calendar"


def test_sim_rejects_bad_batch_pop():
    with pytest.raises(ValueError):
        _params(batch_pop=0)
    with pytest.raises(ValueError):
        _params(batch_pop=513)  # > queue_cap
    assert _params(batch_pop=512).batch_pop == 512


# --------------------------------------------------------------------------
# PR 7: fused end-of-body commit, batch_take, calendar queue.
# --------------------------------------------------------------------------

_jit_commit = jax.jit(EQ.commit, static_argnums=9)
_jit_cal_pop = jax.jit(EQ.cal_pop, static_argnums=1)


def _cal_push(q, times, mask=None, typ=1, cap=None, width=8.0):
    n = len(times)
    times = jnp.asarray(times, jnp.float32)
    mask = jnp.ones((n,), bool) if mask is None else jnp.asarray(mask, bool)
    z = jnp.zeros((n,), jnp.int32)
    return EQ.cal_bulk_push(q, mask, times, typ, z, z, z, cap,
                            jnp.float32(width))


def _cal_drain(q, cap, width=8.0):
    out = []
    while float(EQ.cal_peek_time(q)) < INF:
        q, t, slot, typ, a = _jit_cal_pop(q, cap, jnp.float32(width))
        out.append((float(t), int(slot)))
    return q, out


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=15, deadline=None)
def test_fused_commit_equals_sequential_pop_then_push(seed):
    """One ``commit`` call (pops + pushes fused into one scatter chain)
    is bitwise the sequential reference: pop each slot with ``pop``,
    then ``bulk_push`` the batch — tree, counters, root mirror and drop
    accounting all identical.  This is the end-of-body contract sim.py
    relies on (DESIGN.md §11)."""
    rng = np.random.default_rng(seed)
    cap = 128
    n0 = int(rng.integers(cap - 6, cap))    # near-full: exercises drops
    q0 = _push(EQ.empty(cap), rng.integers(0, 20, n0).astype(np.float32),
               cap=cap)
    nb = int(rng.integers(0, 6))
    qs = q0
    slots = []
    for _ in range(nb):                     # sequential reference
        qs, _, slot, _, _ = _jit_pop(qs, cap)
        slots.append(int(slot))
    push_t = rng.integers(0, 20, 8).astype(np.float32)
    mask = rng.random(8) < 0.7
    qs = _push(qs, push_t, mask=mask, cap=cap)
    z = jnp.zeros((8,), jnp.int32)
    qf = _jit_commit(q0, jnp.asarray(slots, jnp.int32),
                     jnp.ones((nb,), bool), jnp.asarray(mask),
                     jnp.asarray(push_t), 1, z, z, z, cap)
    assert np.array_equal(np.asarray(qs["evq_tree"]),
                          np.asarray(qf["evq_tree"]))
    assert int(qs["dropped"]) == int(qf["dropped"])
    assert np.array_equal(np.asarray(qs["evq_root"]),
                          np.asarray(qf["evq_root"]))
    _assert_root_and_counters(qf, cap)


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=25, deadline=None)
def test_batch_take_matches_sequential_singleton_pops(seed):
    """``batch_take`` returns exactly the slots a singleton loop would
    pop consecutively: the contiguous slot-order prefix of root-time RX
    events, stopping at the first tied non-RX slot; lane 0 is always the
    root slot (the singleton fallback)."""
    rng = np.random.default_rng(seed)
    q, bp = 64, int(rng.integers(1, 10))
    rx = 3
    leaf_t = rng.integers(0, 3, q).astype(np.float32)
    leaf_t[rng.random(q) < 0.3] = INF
    leaf_typ = rng.integers(0, 5, q).astype(np.float32)
    if not (leaf_t < INF).any():
        leaf_t[0] = 1.0
    root_t = float(leaf_t.min())
    root_slot = int(np.argmin(leaf_t))
    # host reference: walk slots in order through the root-time cohort
    ref = []
    for s in range(q):
        if leaf_t[s] != root_t:
            continue
        if leaf_typ[s] != rx:
            break                           # tied non-RX blocks the batch
        ref.append(s)
    ref = ref[:bp] or [root_slot]
    slots, ok = EQ.batch_take(jnp.asarray(leaf_t), jnp.asarray(leaf_typ),
                              jnp.float32(root_t), jnp.int32(root_slot),
                              rx, bp)
    slots, ok = np.asarray(slots), np.asarray(ok)
    assert ok.sum() == len(ref)
    assert slots[:len(ref)].tolist() == ref
    assert slots[0] == root_slot


def test_calendar_pop_order_is_sorted_with_ties():
    """Calendar pops come out sorted by (time, slot) — the same argmin
    contract as the tree — including heavy ties and bucket wraparound
    (times spanning many widths)."""
    rng = np.random.default_rng(0)
    cap = 128
    times = rng.integers(0, 97, size=100).astype(np.float32)  # > NB*W years
    q = _cal_push(EQ.cal_empty(cap), times, cap=cap, width=4.0)
    q, popped = _cal_drain(q, cap, width=4.0)
    expect = sorted((t, s) for s, t in enumerate(times.tolist()))
    assert popped == [(t, s) for t, s in expect]
    assert int(q["dropped"]) == 0
    assert bool((np.asarray(EQ.cal_leaf_times(q, cap)) >= INF).all())


def test_calendar_drop_parity_with_tree():
    """Calendar overflow accounting is bitwise the tree's (and hence the
    linear impl's): same landing slots, same dropped count."""
    cap = 8
    qc = _cal_push(EQ.cal_empty(cap),
                   np.arange(1.0, 7.0).astype(np.float32), cap=cap)
    qc = _cal_push(qc, np.arange(10.0, 15.0).astype(np.float32), cap=cap)
    assert int(qc["dropped"]) == 3
    ev = np.asarray(EQ.cal_leaf_times(qc, cap))
    assert float(ev[6]) == 10.0 and float(ev[7]) == 11.0
    qc = _cal_push(qc, np.array([99.0], np.float32), cap=cap)
    assert int(qc["dropped"]) == 4


@pytest.mark.slow
@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_calendar_interleaved_matches_tree(seed):
    """Random interleaving of pushes and pops produces the identical
    (time, slot) pop sequence and drop count on the calendar and the
    tree; both root mirrors stay consistent with their owner array."""
    rng = np.random.default_rng(seed)
    cap = 64
    qt, qc = EQ.empty(cap), EQ.cal_empty(cap)
    live = 0
    for _ in range(5):
        n = int(rng.integers(1, 20))
        times = rng.integers(0, 40, size=n).astype(np.float32)
        mask = rng.random(n) < 0.8
        qt = _push(qt, times, mask=mask, cap=cap)
        qc = _cal_push(qc, times, mask=mask, cap=cap, width=8.0)
        assert int(qt["dropped"]) == int(qc["dropped"])
        live = min(live + int(mask.sum()), cap)
        for _ in range(int(rng.integers(0, 6))):
            if not live:
                break
            qt, tt, ts, _, _ = _jit_pop(qt, cap)
            qc, ct, cs, _, _ = _jit_cal_pop(qc, cap, jnp.float32(8.0))
            assert (float(tt), int(ts)) == (float(ct), int(cs))
            live -= 1
        # the evq_root mirror is the contract sim.py's cond/body read —
        # it must equal the owner array's root row after every op
        _assert_root_and_counters(qt, cap)
        assert np.array_equal(np.asarray(qc["evq_root"]),
                              np.asarray(qc["evq_cal"])[0])


def test_hier_super_counter_alloc_matches_flat(monkeypatch):
    """Force the super-counter allocation path (normally only active at
    S >= HIER_MIN_SEGS segments) on a small queue and check it lands
    pushes on exactly the slots the flat-cumsum path picks."""
    cap = 1024                              # s=16 ALLOC_SEG=64 segments

    def push_raw(q, times, mask=None):
        # un-jitted: each call re-traces, reading the monkeypatched
        # HIER_MIN_SEGS (the jitted _push would serve a cached program)
        n = len(times)
        m = jnp.ones((n,), bool) if mask is None else jnp.asarray(mask, bool)
        z = jnp.zeros((n,), jnp.int32)
        return EQ.bulk_push(q, m, jnp.asarray(times, jnp.float32), 1,
                            z, z, z, cap)

    def build(q):
        rng = np.random.default_rng(5)
        q = push_raw(q, rng.uniform(1, 1e6, 900).astype(np.float32))
        for _ in range(200):                # scatter frees across segments
            q, _, _, _, _ = _jit_pop(q, cap)
        return push_raw(q, rng.uniform(1, 1e6, 300).astype(np.float32),
                        mask=rng.random(300) < 0.6)

    monkeypatch.setattr(EQ, "HIER_MIN_SEGS", 1)
    qh = build(EQ.empty(cap))
    monkeypatch.setattr(EQ, "HIER_MIN_SEGS", 10 ** 9)
    qf = build(EQ.empty(cap))               # flat-cumsum reference
    assert np.array_equal(np.asarray(qh["evq_tree"]),
                          np.asarray(qf["evq_tree"]))


@pytest.mark.parametrize("qi,bp", [("tree", 8), ("calendar", 1),
                                   ("calendar", 8)])
def test_queue_impl_and_batch_pop_match_linear_bitwise(qi, bp):
    """Full-sim gate: every queue_impl x batch_pop point is bitwise the
    linear singleton baseline on a non-ideal fabric (the tie-heavy
    BEACON_RX cohorts are exactly what batching must not reorder)."""
    kn = SW.knob_batch(dn_th=(2,))
    base_p = _params(topology="mesh2d")
    wl = W.interference_batch(base_p, seeds=(0,), sim_len=5e4)
    base = SW.sweep(base_p.shape, kn, wl, 5e4, mode="seq",
                    topology="mesh2d")
    p = _params(topology="mesh2d", queue_impl=qi, batch_pop=bp)
    got = SW.sweep(p.shape, kn, wl, 5e4, mode="seq", topology="mesh2d")
    for key in ("app_done", "app_arrive", "beacons_tx", "beacons_rx",
                "events_processed", "dropped"):
        assert np.array_equal(np.asarray(base[key]),
                              np.asarray(got[key])), key


def test_k256_shape_cohort_commits_match_host_model():
    """The k=256 benchmark's commit shape: Q=32768, 64-wide same-time
    BEACON_RX cohorts popped with ``batch_take`` and a 356-wide push
    batch (a 255-wide fan-out plus handler pushes) in each commit.  After
    every commit the leaf times equal a host model of the queue (pops
    free their slots, the j-th pushed entry takes the j-th lowest free
    slot), the root is the first-index argmin row, and the counters
    match the leaves."""
    rng = np.random.default_rng(11)
    cap, bp, n, rx = 32768, 64, 356, 3
    commit = jax.jit(EQ.commit, static_argnums=9)
    model = np.full(cap, INF, np.float32)
    typs = np.zeros(cap, np.float32)
    q = EQ.empty(cap)
    t_now = 0.0
    widths = []
    for _ in range(6):
        root = np.asarray(q["evq_root"])
        if root[0] < INF:
            slots, ok = EQ.batch_take(
                EQ.leaf_times(q, cap), EQ.leaf_payloads(q, cap)[:, 0],
                jnp.float32(root[0]), jnp.int32(root[1]), rx, bp)
            t_now = float(root[0])
        else:
            slots, ok = jnp.zeros((bp,), jnp.int32), jnp.zeros((bp,), bool)
        slots_np, ok_np = np.asarray(slots), np.asarray(ok)
        # the cohort: the root-time slots in slot order, all RX here
        cohort = np.flatnonzero(model == root[0])[:bp] \
            if root[0] < INF else np.zeros(0, int)
        assert slots_np[ok_np].tolist() == cohort.tolist()
        widths.append(len(cohort))
        # one fan-out of 255 RX events at one time, plus handler pushes
        # at half-integer times, which never tie with a cohort
        times = np.concatenate([
            np.full(255, t_now + 8.0, np.float32),
            (t_now + 100.5 + rng.integers(0, 40, n - 255))
            .astype(np.float32)])
        ptyp = np.concatenate([np.full(255, rx),
                               rng.integers(0, 3, n - 255)]) \
            .astype(np.float32)
        mask = rng.random(n) < 0.95
        z = jnp.zeros((n,), jnp.float32)
        q = commit(q, slots, ok, jnp.asarray(mask), jnp.asarray(times),
                   jnp.asarray(ptyp), z, z, z, cap)
        model[slots_np[ok_np]] = INF
        free = np.flatnonzero(model >= INF)
        land = free[:mask.sum()]
        model[land] = times[mask]
        typs[land] = ptyp[mask]
        assert np.array_equal(_times(q, cap), model)
        assert np.array_equal(np.asarray(EQ.leaf_payloads(q, cap))[:, 0]
                              [model < INF], typs[model < INF])
        _assert_root_and_counters(q, cap)
    assert widths.count(bp) >= 3, widths
    assert int(q["dropped"]) == 0


def _commit_scatter_count(cap, batched):
    nb, n = 8, 16
    st = EQ.empty(cap)
    args = (jnp.zeros((nb,), jnp.int32), jnp.ones((nb,), bool),
            jnp.ones((n,), bool), jnp.ones((n,), jnp.float32),
            jnp.zeros((n,)), jnp.zeros((n,)), jnp.zeros((n,)),
            jnp.zeros((n,)))

    def f(st, *a):
        return EQ.commit(st, *a, cap)

    if batched:
        f = jax.vmap(f)
        st, args = jax.tree.map(lambda x: jnp.stack([x, x]), (st, args))
    hlo = jax.jit(f).lower(st, *args).as_text(dialect="hlo")
    return len(re.findall(r"\bscatter\(", hlo))


def test_tree_commit_structure_does_not_grow_with_depth():
    """One tree commit holds the same number of scatter ops at Q=1024
    and Q=32768, batched or not: no per-level work is left that grows
    with log Q.  A structural guard on the CPU backend, not a speed."""
    counts = {(cap, b): _commit_scatter_count(cap, b)
              for cap in (1024, 32768) for b in (False, True)}
    assert len(set(counts.values())) == 1, counts
