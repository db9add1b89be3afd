"""Copy bytes per iteration of a compiled program's main loop.

The counting arithmetic of the program's HLO loop lint, kept with the
benchmark: parse ``compiled.as_text()``, take the ``while`` op with the
largest recursive op count as the main event loop, and sum the bytes of
every ``copy``/``copy-start`` its body executes once per iteration
(nested whiles times their trip count; of a conditional's branches the
costliest, since one runs).  Pure text analysis.
"""
from __future__ import annotations

import re

DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
    "token": 0, "opaque": 0,
}
SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\](?:\{[^}]*\})?")
DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*?)\s+([\w\-]+)\(")
HEAD_RE = re.compile(r"^\s*(?:ROOT\s+)?%[\w.\-]+\s*=")
CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
BODY_RE = re.compile(r"body=%?([\w.\-]+)")
COND_RE = re.compile(r"condition=%?([\w.\-]+)")
BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
TRIP_ATTR_RE = re.compile(r'known_trip_count[^}]*?"n":"(\d+)"')


def shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES.get(dt, 0)
    return total


def parse_module(text: str) -> dict:
    """-> {computation name: [(op kind, result type, line), ...]}"""
    comps, cur = {}, None
    for raw in text.splitlines():
        line = raw.strip()
        if not line:
            continue
        if line.endswith("{") and "->" in line and not HEAD_RE.match(line):
            hdr = line[5:] if line.startswith("ENTRY") else line
            cur = comps.setdefault(
                hdr.strip().lstrip("%").split(" ")[0].split("(")[0], [])
            continue
        m = DEF_RE.match(line) if cur is not None else None
        if m:
            cur.append((m.group(3), m.group(2), line))
    return comps


def _callees(kind: str, line: str) -> list:
    if kind == "while":
        m = BODY_RE.search(line)
        return [m.group(1)] if m else []
    names = []
    m = BRANCHES_RE.search(line)
    if m:
        names += [n.strip().lstrip("%") for n in m.group(1).split(",")
                  if n.strip()]
    m = CALLS_RE.search(line)
    if m:
        names.append(m.group(1))
    return names


def _trips(line: str, comps: dict) -> int:
    m = TRIP_ATTR_RE.search(line)
    if m:
        return int(m.group(1))
    cond = COND_RE.search(line)
    best = 1
    for kind, _, l in comps.get(cond.group(1) if cond else "", []):
        c = re.search(r"constant\((-?\d+)\)", l) if kind == "constant" \
            else None
        if c:
            best = max(best, int(c.group(1)))
    return best


def _op_count(name: str, comps: dict, memo: dict) -> int:
    if name not in memo:
        memo[name] = 0
        ops = comps.get(name, [])
        memo[name] = len(ops) + sum(_op_count(c, comps, memo)
                                    for kind, _, line in ops
                                    for c in _callees(kind, line))
    return memo[name]


def _copy_bytes(name: str, comps: dict, memo: dict) -> int:
    if name in memo:
        return memo[name]
    memo[name] = 0
    total = 0
    for kind, type_str, line in comps.get(name, []):
        if kind in ("copy", "copy-start"):
            total += shape_bytes(type_str)
        callees = _callees(kind, line)
        if kind == "while" and callees:
            total += _copy_bytes(callees[0], comps, memo) \
                * max(_trips(line, comps), 1)
        elif kind == "conditional" and callees:
            total += max(_copy_bytes(c, comps, memo) for c in callees)
        else:
            total += sum(_copy_bytes(c, comps, memo) for c in callees)
    memo[name] = total
    return total


def main_loop_copy_bytes(text: str) -> int:
    """Bytes copied per iteration of the module's main while loop."""
    comps = parse_module(text)
    memo, best, best_n = {}, None, -1
    for ops in comps.values():
        for kind, _, line in ops:
            m = BODY_RE.search(line) if kind == "while" else None
            if m and _op_count(m.group(1), comps, memo) > best_n:
                best, best_n = m.group(1), _op_count(m.group(1), comps, memo)
    if best is None:
        raise ValueError("no while op in the HLO module")
    return _copy_bytes(best, comps, {})
