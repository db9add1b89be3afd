"""Device time of each named part of the sim loop, from a profiler trace
of one grid.

The program names the parts of its event loop with ``jax.named_scope``
(``sim.pop``, ``sim.rx``, ``sim.detector``, ``sim.handlers``,
``sim.commit``, ``sim.trace_ring``; the handler branches and the beacon
fan-out nest inside ``sim.handlers``), counts the loop's trips in the
``iterations`` state leaf.

The scopes live in the compiled program, as each instruction's
``op_name`` metadata; the trace names instructions.  ``instruction_scopes``
reads the compiled HLO text once and gives each instruction the
outermost ``sim.`` scope of its own ``op_name``, else the most common one
among the instructions it calls (a fusion), else that of the first
operand that has one (instructions a compiler rewrite made without a
name).  The loop's lane select, which JAX's lowering of a vmapped while
names after the loop itself, takes no scope.  ``partition`` then splits
the device self time of the ``_sweep`` program: every copy to ``copy``,
every other instruction to its scope, the rest to ``None``.

``reading`` splits the harness's own trace of the traced grid (the
lists ``trace_reduce.extract`` returns), once for every reader of this
module.  A grid of several groups is several programs on several chips,
and reads nothing here.
"""
from __future__ import annotations

import functools
import re
from collections import Counter

import numpy as np

import hlo_copies
import trace_reduce as TRACE

COPY_KINDS = ("copy", "copy-start", "copy-done")
SWEEP = "_sweep"
_SCOPE_RE = re.compile(r"(?:^|[/(])(sim\.[A-Za-z_]+)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def scope_of(op_name: str) -> str | None:
    """The outermost ``sim.`` scope named in an ``op_name``."""
    m = _SCOPE_RE.search(op_name)
    return m.group(1) if m else None


def _parse(text: str) -> dict:
    """-> {instruction: (kind, op_name, operands, callees)}, and the
    instructions of each computation."""
    instrs, comps = {}, {}
    for comp, ops in hlo_copies.parse_module(text).items():
        for kind, _, line in ops:
            name = hlo_copies.DEF_RE.match(line).group(1)
            args = line.split(f"{kind}(", 1)[1].split(")", 1)[0]
            op_name = _OP_NAME_RE.search(line)
            instrs[name] = (kind, op_name.group(1) if op_name else "",
                            _OPERAND_RE.findall(args),
                            hlo_copies._callees(kind, line))
            comps.setdefault(comp, []).append(name)
    return {"instrs": instrs, "comps": comps}


def instruction_scopes(text: str) -> dict:
    """-> {instruction name: (kind, outermost sim scope or None)} for
    every instruction of a compiled module's HLO text."""
    mod = _parse(text)
    instrs, comps = mod["instrs"], mod["comps"]
    memo = {}

    def called(name):
        votes = Counter(scope_of(instrs[i][1]) for c in instrs[name][3]
                        for i in comps.get(c, ()))
        votes.pop(None, None)
        return votes.most_common(1)[0][0] if votes else None

    def resolve(name):
        if name in memo:
            return memo[name]
        memo[name] = None                    # cycles end here
        kind, op_name, operands, _ = instrs[name]
        scope = scope_of(op_name)
        if scope is None and re.search(r"/while$", op_name):
            return None                      # the loop, its lane select
        scope = scope or called(name)
        if scope is None:
            for o in operands:
                if o in instrs and instrs[o][0] not in ("parameter",
                                                        "constant"):
                    scope = resolve(o)
                    if scope:
                        break
        memo[name] = scope
        return scope

    return {n: (instrs[n][0], resolve(n)) for n in instrs}


def sweep_ops(ev: dict, chip: int = 0) -> list:
    """Chip ``chip``'s operations inside the window that ran within a
    ``_sweep`` program, as ``(name, start, end)``."""
    win = [(s, s + d) for n, s, d in ev["spans"] if n == TRACE.WINDOW_SPAN]
    (w0, w1), = win
    progs = [(s, e) for n, s, e in TRACE._clip(ev["modules"][chip], w0, w1)
             if SWEEP in n]
    return [(n, s, e) for n, s, e in TRACE._clip(ev["ops"][chip], w0, w1)
            if any(ps <= s < pe for ps, pe in progs)]


def partition(ops: list, scopes: dict) -> dict:
    """Device self seconds of ``ops`` (as ``sweep_ops`` returns them) by
    ``"copy"``, scope, or None for what no scope claims."""
    out = {}
    for long_name, t in TRACE._self_times(ops).items():
        kind, _, name = TRACE.short_name(long_name).partition(" ")
        name = name.lstrip("%") or kind
        kind, scope = scopes.get(name, (kind, None))
        key = "copy" if kind in COPY_KINDS else scope
        out[key] = out.get(key, 0.0) + t / 1e9
    return out


def summarize(ev: dict, scopes: dict, trips: int) -> dict:
    """The readings of one profiled grid: device self seconds of each part
    of the loop per trip."""
    return {"trips": trips,
            "per_trip_s": {k: v / trips for k, v in
                           partition(sweep_ops(ev), scopes).items()}}


def max_iterations(state: dict) -> int | None:
    """The loop's trips for a grid: the most any lane took."""
    if "iterations" not in state:
        return None
    return int(np.max(np.asarray(state["iterations"])))


def pace_group(run) -> dict | None:
    """The traced grid's group that ran on the chip that sets the pace
    (``trace_reduce.summarize``'s ``pace_chip``); a grid's only group
    wherever it ran.  None where no one group ran on that chip."""
    groups = run.grids[0]["groups"]
    if len(groups) == 1:
        return groups[0]
    on = [g for g in groups if g["chip"] == run.trace["pace_chip"]]
    return on[0] if len(on) == 1 else None


@functools.lru_cache(maxsize=1)
def reading(run) -> dict | None:
    """The split of the traced grid's loop by scope.  None where the grid
    is several groups, the program keeps no ``iterations`` leaf, or the
    run was not traced."""
    groups = run.grids[0]["groups"]
    if len(groups) > 1:
        return None
    trips = max_iterations(groups[0]["state"])
    if trips is None or run.events is None:
        return None
    return summarize(run.events, instruction_scopes(run.compiled().as_text()),
                     trips)


def per_trip_us(run, key) -> float | None:
    r = reading(run)
    if r is None or not r["per_trip_s"]:
        return None
    return 1e6 * r["per_trip_s"].get(key, 0.0)
