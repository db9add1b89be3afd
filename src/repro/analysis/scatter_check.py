"""Scatter-aliasing race detector for the fused end-of-body commits.

The PR-7 commit chains (``eventq.commit``, ``eventq.cal_commit``,
``trace.ring_commit``) are long ``.at[]`` sequences whose correctness
rests on a per-scatter disjointness discipline: within ONE ``.set``
call, duplicate indices have unspecified write order in XLA, so every
``.set`` must either write unique positions or write *identical values*
to every duplicated position.  ``.add``/``.min``/``.max`` are order-independent and always
safe.  Sequential ``.at[]`` calls are ordered (later wins), so
cross-call overlap — e.g. the allocator reusing just-freed pop slots —
is the *design*, not a race.

This pass drives the REAL commit functions eagerly under a recorder
that intercepts ``jax.numpy``'s ``.at[]`` update methods, resolves each
call's concrete write positions (honoring ``mode="drop"`` out-of-range
routing), and proves per-call disjointness-or-idempotence, reporting
the overlapping lane pair and index when the proof fails.
"""
from __future__ import annotations

import traceback
from dataclasses import dataclass, field

import numpy as np

CORE_MARK = "repro/core"


@dataclass
class ScatterRecord:
    method: str         # set | add | min | max | mul | ...
    site: str           # "eventq.py:516" — innermost repro/core frame
    index: object       # concrete index as passed to .at[...]
    values: object      # concrete update operand
    shape: tuple        # buffer shape
    mode: str | None


@dataclass
class SiteReport:
    site: str
    method: str
    shape: tuple
    n_writes: int
    n_unique: int
    verdict: str        # disjoint | idempotent-duplicate | order-independent
                        # | trivial | unanalyzed | RACE
    detail: str = ""

    def to_dict(self) -> dict:
        return {"site": self.site, "method": self.method,
                "shape": list(self.shape), "n_writes": self.n_writes,
                "n_unique": self.n_unique, "verdict": self.verdict,
                "detail": self.detail}


class ScatterRecorder:
    """Context manager: monkeypatch ``_IndexUpdateRef`` update methods to
    record every ``.at[]`` call made while active (eager mode only —
    indices and values must be concrete)."""

    METHODS = ("set", "add", "min", "max", "mul", "multiply", "divide",
               "subtract", "power")

    def __init__(self):
        self.records: list[ScatterRecord] = []
        self._orig = {}

    def _site(self) -> str:
        for fr in reversed(traceback.extract_stack()):
            if CORE_MARK in fr.filename.replace("\\", "/"):
                return f"{fr.filename.rsplit('/', 1)[-1]}:{fr.lineno}"
        return "<unknown>"

    def __enter__(self):
        from jax._src.numpy import array_methods as AM
        self._AM = AM
        for name in self.METHODS:
            orig = getattr(AM._IndexUpdateRef, name, None)
            if orig is None:
                continue
            self._orig[name] = orig

            def wrapped(ref, values=None, *a, _name=name, _orig=orig, **kw):
                try:
                    self.records.append(ScatterRecord(
                        method=_name, site=self._site(),
                        index=ref.index,
                        values=values, shape=tuple(ref.array.shape),
                        mode=kw.get("mode")))
                except Exception:
                    pass            # recording must never change semantics
                if values is None:
                    return _orig(ref, *a, **kw)
                return _orig(ref, values, *a, **kw)

            setattr(AM._IndexUpdateRef, name, wrapped)
        return self

    def __exit__(self, *exc):
        for name, orig in self._orig.items():
            setattr(self._AM._IndexUpdateRef, name, orig)
        return False


def _lane_positions(rec: ScatterRecord):
    """-> (positions, per_lane_values) or (None, reason).

    positions: list of hashable write positions, one per lane, with
    ``mode="drop"`` out-of-range lanes removed.  per_lane_values: list of
    np arrays (the value each lane writes), aligned with positions.
    Slices / scalar ints cannot self-overlap -> ([], "trivial").
    """
    idx = rec.index if isinstance(rec.index, tuple) else (rec.index,)
    # classify components: "arr" (vector index), int, or slice/ellipsis
    comps = []
    arrays = []
    for c in idx:
        if isinstance(c, slice) or c is Ellipsis or c is None:
            comps.append(("slice", None))
            continue
        a = np.asarray(c)
        if a.dtype == bool:
            return None, "boolean mask index"
        if a.ndim == 0:
            comps.append(("int", int(a)))
        else:
            comps.append(("arr", len(arrays)))
            arrays.append(a)
    if not arrays:
        return [], None                    # ints/slices only: trivial
    try:
        b = np.broadcast_arrays(*arrays)
    except ValueError:
        return None, "unbroadcastable index components"
    if b[0].ndim > 1:
        return None, f"{b[0].ndim}-d index"
    n = b[0].shape[0]
    lane_pos = []
    keep = []
    for lane in range(n):
        pos = []
        oob = False
        for dim, (kind, v) in enumerate(comps):
            if kind == "slice":
                pos.append(":")
                continue
            if kind == "arr":
                v = int(b[v][lane])
            if dim < len(rec.shape) and not (0 <= v < rec.shape[dim]):
                oob = True
            pos.append(v)
        if oob and rec.mode == "drop":
            continue                       # dropped lane: writes nothing
        lane_pos.append(tuple(str(p) for p in pos))
        keep.append(lane)
    # per-lane values
    vals = np.asarray(rec.values)
    if vals.ndim > 0 and vals.shape[:1] == (n,):
        per_lane = [np.asarray(vals[i]) for i in keep]
    else:
        per_lane = [np.asarray(vals) for _ in keep]
    return list(zip(lane_pos, keep, per_lane)), None


def analyze_records(records: list) -> tuple:
    """-> (site_reports, findings).  A finding is a dict naming the
    site, the overlapping lane pair, the index, and the two values."""
    reports = []
    findings = []
    for rec in records:
        if rec.method != "set":
            reports.append(SiteReport(
                site=rec.site, method=rec.method, shape=rec.shape,
                n_writes=-1, n_unique=-1, verdict="order-independent"))
            continue
        lanes, reason = _lane_positions(rec)
        if lanes is None:
            reports.append(SiteReport(
                site=rec.site, method=rec.method, shape=rec.shape,
                n_writes=-1, n_unique=-1, verdict="unanalyzed",
                detail=reason))
            continue
        if not lanes:
            reports.append(SiteReport(
                site=rec.site, method=rec.method, shape=rec.shape,
                n_writes=0, n_unique=0, verdict="trivial"))
            continue
        seen: dict = {}
        verdict = "disjoint"
        detail = ""
        for pos, lane, val in lanes:
            if pos not in seen:
                seen[pos] = (lane, val)
                continue
            lane0, val0 = seen[pos]
            if np.array_equal(np.asarray(val0), np.asarray(val)):
                if verdict == "disjoint":
                    verdict = "idempotent-duplicate"
                    detail = (f"lanes {lane0} and {lane} both write index "
                              f"{pos} with identical values")
            else:
                verdict = "RACE"
                detail = (f"lanes {lane0} and {lane} write index {pos} "
                          f"with different values "
                          f"({np.asarray(val0).tolist()} vs "
                          f"{np.asarray(val).tolist()})")
                findings.append({
                    "check": "scatter-alias",
                    "site": rec.site, "shape": list(rec.shape),
                    "index": list(pos), "lanes": [lane0, lane],
                    "values": [np.asarray(val0).tolist(),
                               np.asarray(val).tolist()],
                    "detail": f"{rec.site}: {detail}"})
                break
        reports.append(SiteReport(
            site=rec.site, method=rec.method, shape=rec.shape,
            n_writes=len(lanes), n_unique=len(seen), verdict=verdict,
            detail=detail))
    return reports, findings


# --------------------------------------------------------------------------
# Drivers: exercise the real commit chains over adversarial scenarios.
# --------------------------------------------------------------------------

def _push_args(n, times, typ=3.0):
    import jax.numpy as jnp
    times = jnp.asarray(times, jnp.float32)
    mask = times < 1e18
    z = jnp.zeros((n,), jnp.float32)
    return mask, times, jnp.full((n,), typ, jnp.float32), z, z, z


def check_commit_chains(queue_cap: int = 64, batch_pop: int = 8) -> dict:
    """Drive tree commit, calendar commit, and the trace-ring commit
    eagerly over adversarial scenarios (pop+push slot reuse, duplicate
    push times, overflow, INF pushes, all-dropped lanes) and prove every
    ``.set`` site disjoint-or-idempotent."""
    import jax.numpy as jnp
    from repro.core import eventq as EQ
    from repro.core import trace as TR

    rec = ScatterRecorder()
    with rec:
        # -- tree: prefill, then pop batches + pushes with reuse ---------
        st = EQ.empty(queue_cap)
        n = queue_cap
        t0 = np.full(n, 1e18, np.float32)
        t0[:40] = 10.0 * (np.arange(40) % 7 + 1)       # heavy time ties
        st = EQ.bulk_push(st, *_push_args(n, t0), queue_cap)
        pop_slots = jnp.asarray([0, 1, 2, 7, 7, 63], jnp.int32)
        pop_ok = jnp.asarray([1, 1, 1, 1, 0, 1], bool)  # dup slot masked
        t1 = np.full(n, 1e18, np.float32)
        t1[:8] = [5.0, 5.0, 5.0, 70.0, 70.0, 1e18, 2.0, 2.0]
        st = EQ.commit(st, pop_slots, pop_ok, *_push_args(n, t1),
                       queue_cap)
        # overflow: push a full queue's worth into the near-full tree
        st = EQ.commit(st, jnp.zeros((0,), jnp.int32),
                       jnp.zeros((0,), bool),
                       *_push_args(n, np.full(n, 3.0, np.float32)),
                       queue_cap)
        # all-dropped pop lanes
        st = EQ.commit(st, pop_slots, jnp.zeros((6,), bool),
                       *_push_args(n, np.full(n, 1e18, np.float32)),
                       queue_cap)

        # -- calendar: same scenarios through cal_commit -----------------
        cs = EQ.cal_empty(queue_cap)
        cs = EQ.cal_bulk_push(cs, *_push_args(n, t0), queue_cap, 16.0)
        cs = EQ.cal_commit(cs, pop_slots, pop_ok, jnp.float32(10.0),
                           *_push_args(n, t1), queue_cap, 16.0)
        cs = EQ.cal_commit(cs, jnp.zeros((0,), jnp.int32),
                           jnp.zeros((0,), bool), jnp.float32(0.0),
                           *_push_args(n, np.full(n, 3.0, np.float32)),
                           queue_cap, 16.0)

        # -- trace ring: partial batches, wraparound-to-full -------------
        spec = TR.TraceSpec(ring_cap=8, sample_every=4, n_samples=4,
                            hist_bins=8, bins_per_octave=2)
        ts = TR.trace_state(spec, k=2)
        okl = jnp.asarray([1, 0, 1, 1, 0, 1, 1, 1], bool)
        lanes = jnp.arange(8, dtype=jnp.int32)
        for step in range(3):                  # 3rd batch overflows cap=8
            ts = TR.ring_commit(ts, spec, jnp.float32(step),
                                okl, lanes, lanes, lanes, lanes,
                                jnp.float32(1.5))
    reports, findings = analyze_records(rec.records)
    races = [r for r in reports if r.verdict == "RACE"]
    unanalyzed = [r for r in reports if r.verdict == "unanalyzed"]
    return {
        "sites": [r.to_dict() for r in reports],
        "n_set_sites": sum(1 for r in reports if r.method == "set"),
        "n_races": len(races),
        "n_unanalyzed": len(unanalyzed),
        "findings": findings,
    }
