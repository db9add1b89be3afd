"""HLO loop-body copy lint — DESIGN.md §11 as a machine-checked invariant.

The §11 discovery: a scatter that XLA cannot prove in-place is "demoted"
to a whole-buffer ``copy`` per loop iteration, silently turning the
O(1) root-row pop into O(Q) traffic.  PR 7 killed the copies
with the fused end-of-body commit + the ``evq_root`` mirror; until now
the only guard was a 0.7x throughput gate.  This pass checks the
compiled artifact directly:

  1. locate the simulator's main event loop: the ``while`` op with the
     largest recursive op count (every nested scatter-expander loop is
     *inside* its body, so the main loop is maximal by construction),
     cross-checked against the queue buffer's expected carry shape;
  2. walk the body recursively (fusions, calls, conditional branches,
     nested whiles x trip count) collecting every ``copy``/``copy-start``
     with its per-main-iteration byte cost;
  3. flag any copy whose size scales with ``queue_cap`` or ``(k, k)``,
     tag scatter-demoted copies by their ``op_name`` metadata
     fingerprint, and compare the summed per-iteration copy bytes
     against a per-combo budget.

Pure text analysis — no JAX imports — so fixture tests stay cheap; the
drivers that lower real programs live in ``__main__``.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from repro.analysis import hlo_text as H

# (k,k)-scale copies only matter once k*k is big enough to dominate an
# iteration (k=256 -> 256 KiB/event); below this, (k,k) views riding in
# the carry are the design, not the pathology.
KK_FLOOR_ELEMS = 1024


@dataclass
class CopySite:
    op: str                 # HLO op name (named-op diagnostic)
    computation: str        # computation the op lives in
    kind: str               # "copy" | "copy-start"
    type_str: str           # result type, e.g. "f32[1033,6]{1,0}"
    elems: int
    bytes_one: int          # bytes for ONE execution of the op
    trips: int              # nested-loop multiplier within one main iter
    scatter_demoted: bool   # op_name metadata fingerprints a scatter
    metadata: str = ""

    @property
    def bytes_per_iter(self) -> int:
        return self.bytes_one * self.trips

    def to_dict(self) -> dict:
        return {"op": self.op, "computation": self.computation,
                "kind": self.kind, "type": self.type_str,
                "elems": self.elems, "bytes_per_iter": self.bytes_per_iter,
                "trips": self.trips,
                "scatter_demoted": self.scatter_demoted}


@dataclass
class LoopReport:
    main_while: str
    body: str
    carry_bytes: int
    copy_sites: list = field(default_factory=list)    # all CopySites
    copy_bytes_per_iter: int = 0
    findings: list = field(default_factory=list)      # list of dicts

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_dict(self) -> dict:
        return {"main_while": self.main_while, "body": self.body,
                "carry_bytes": self.carry_bytes,
                "copy_count": len(self.copy_sites),
                "copy_bytes_per_iter": self.copy_bytes_per_iter,
                "findings": self.findings}


def _recursive_op_count(name: str, comps: dict, memo: dict) -> int:
    if name in memo:
        return memo[name]
    memo[name] = 0                       # guards cycles
    comp = comps.get(name)
    if comp is None:
        return 0
    total = len(comp.order)
    for op_name in comp.order:
        for callee in H.called_computations(comp.ops[op_name]):
            total += _recursive_op_count(callee, comps, memo)
    memo[name] = total
    return total


def find_main_while(comps: dict):
    """-> (Op, host Computation) of the simulator's main event loop.

    The main loop's body transitively contains every nested loop
    (scatter expanders, scans), so it has the strictly largest
    recursive op count among all while ops in the module.
    """
    memo: dict = {}
    best = None
    best_count = -1
    for comp in list(comps.values()):
        for op_name in comp.order:
            op = comp.ops[op_name]
            if op.kind != "while":
                continue
            m = H.BODY_RE.search(op.line)
            if not m:
                continue
            n = _recursive_op_count(m.group(1), comps, memo)
            if n > best_count:
                best, best_count = (op, comp), n
    if best is None:
        raise ValueError("no while op found in HLO module")
    return best


def _scatter_fingerprint(op: H.Op, comp: H.Computation) -> bool:
    """True if the copy's metadata — or a direct consumer's — names a
    scatter: the signature of copy-insertion demoting an in-place
    scatter to copy-then-write."""
    if "scatter" in op.metadata_op_name:
        return True
    for other_name in comp.order:
        other = comp.ops[other_name]
        if op.name in other.operands and "scatter" in other.metadata_op_name:
            return True
    return False


def _collect_copies(comp_name: str, comps: dict, memo: dict):
    """-> (bytes_per_exec, [CopySite]) for one execution of comp_name.

    Whiles multiply by trip count; conditional *budgets* take the
    max-cost branch (exactly one branch runs) while *sites* union all
    branches (a copy in any branch is reachable).
    """
    if comp_name in memo:
        return memo[comp_name]
    memo[comp_name] = (0, [])            # guards cycles
    comp = comps.get(comp_name)
    if comp is None:
        return 0, []
    total = 0
    sites: list = []
    for op_name in comp.order:
        op = comp.ops[op_name]
        if op.kind in ("copy", "copy-start"):
            elems, nbytes = H.shape_elems_bytes(op.type_str)
            sites.append(CopySite(
                op=op.name, computation=comp_name, kind=op.kind,
                type_str=op.type_str.strip(), elems=elems,
                bytes_one=nbytes, trips=1,
                scatter_demoted=_scatter_fingerprint(op, comp),
                metadata=op.metadata_op_name))
            total += nbytes
        callees = H.called_computations(op)
        if not callees:
            continue
        if op.kind == "while":
            trips = max(H.while_trips(op, comps), 1)
            b, s = _collect_copies(callees[0], comps, memo)
            total += b * trips
            sites.extend(_scaled(s, trips))
        elif op.kind == "conditional":
            branch = [_collect_copies(c, comps, memo) for c in callees]
            if branch:
                total += max(b for b, _ in branch)
                for _, s in branch:
                    sites.extend(s)
        else:                            # fusion / call / custom-call
            for c in callees:
                b, s = _collect_copies(c, comps, memo)
                total += b
                sites.extend(s)
    memo[comp_name] = (total, sites)
    return total, sites


def _scaled(sites: list, trips: int) -> list:
    if trips == 1:
        return list(sites)
    return [CopySite(op=s.op, computation=s.computation, kind=s.kind,
                     type_str=s.type_str, elems=s.elems,
                     bytes_one=s.bytes_one, trips=s.trips * trips,
                     scatter_demoted=s.scatter_demoted,
                     metadata=s.metadata)
            for s in sites]


def lint_loop_body(hlo_text: str, *, queue_cap: int, k: int,
                   budget_bytes: int | None = None) -> LoopReport:
    """Lint the main loop body of a compiled simulator module.

    Findings (each a dict with named-op diagnostics):
      whole-buffer-copy   — copy with >= queue_cap elements per iteration
      kk-copy             — copy with >= k*k elements (k*k >= KK_FLOOR)
      copy-budget         — summed per-iteration copy bytes > budget
    ``scatter_demoted`` on a site marks the §11 demotion fingerprint.
    """
    comps = H.parse_module(hlo_text)
    main_op, _host = find_main_while(comps)
    body_name = H.BODY_RE.search(main_op.line).group(1)
    _, carry_bytes = H.shape_elems_bytes(main_op.type_str)
    total, sites = _collect_copies(body_name, comps, {})
    report = LoopReport(main_while=main_op.name, body=body_name,
                        carry_bytes=carry_bytes, copy_sites=sites,
                        copy_bytes_per_iter=total)
    kk = k * k
    for s in sites:
        if s.elems >= queue_cap:
            report.findings.append({
                "check": "whole-buffer-copy",
                "detail": (f"{s.kind} %{s.op} in %{s.computation} moves "
                           f"{s.type_str} ({s.elems} elems >= queue_cap="
                           f"{queue_cap}) every iteration"
                           + (" [scatter-demoted]" if s.scatter_demoted
                              else "")),
                **s.to_dict()})
        elif kk >= KK_FLOOR_ELEMS and s.elems >= kk:
            report.findings.append({
                "check": "kk-copy",
                "detail": (f"{s.kind} %{s.op} in %{s.computation} moves "
                           f"{s.type_str} ({s.elems} elems >= k*k={kk}) "
                           f"every iteration"
                           + (" [scatter-demoted]" if s.scatter_demoted
                              else "")),
                **s.to_dict()})
    if budget_bytes is not None and total > budget_bytes:
        report.findings.append({
            "check": "copy-budget",
            "detail": (f"loop body copies {total} B/iter > budget "
                       f"{budget_bytes} B/iter "
                       f"({len(sites)} copy sites; largest: "
                       + ", ".join(f"%{s.op}:{s.bytes_per_iter}B"
                                   for s in sorted(
                                       sites,
                                       key=lambda x: -x.bytes_per_iter)[:3])
                       + ")"),
            "bytes_per_iter": total, "budget": budget_bytes})
    return report


def copy_budget_bytes(*, queue_cap: int, k: int, batch_pop: int,
                      row_w: int = 6, m: int = 0,
                      n_childs: int = 0) -> int:
    """Per-iteration copy-byte budget for one (queue_impl, batch_pop,
    policy, topology) combo.

    Healthy bodies copy only lane-scale data — never the O(queue_cap)
    buffer or the (k,k) view.  The legitimate traffic has three
    structural sources (measured at the paper point):

      * lane scale: batch_pop x row_w pop rows, k-vectors, scalar loop
        plumbing (4x headroom);
      * fork expansion: the n_childs-trip child loop copies a k-vector
        per trip -> k * n_childs elems (2x headroom; zero when
        ``n_childs`` is not given);
      * slot compaction: nested expanders shuffle (m + n_childs)-wide
        index vectors across O(tens) of trips (2x headroom over a
        32-trip allowance; zero when ``m`` is not given).

    The total is clamped strictly below HALF a whole-buffer copy, so a
    §11 regression (one O(queue_cap) copy per iteration) can never fit
    under the budget regardless of the structural terms.
    """
    lane_scale = 4 * (row_w * (batch_pop + 8) + 8 * k + 512)
    expansion = 4 * k * n_childs
    compaction = 4 * (m + n_childs) * 32
    budget = 4 * lane_scale + 2 * expansion + 2 * compaction
    whole_buffer = 4 * row_w * queue_cap
    return min(budget, max(whole_buffer // 2, 1024))
