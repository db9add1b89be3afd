"""Device time of each named part of the sim loop, and the host steps of
one grid, from a profiler trace of that grid.

The program names the parts of its event loop with ``jax.named_scope``
(``sim.pop``, ``sim.rx``, ``sim.detector``, ``sim.handlers``,
``sim.commit``, ``sim.trace_ring``; the handler branches and the beacon
fan-out nest inside ``sim.handlers``), counts the loop's trips in the
``iterations`` state leaf, and wraps each step of a grid in a host span
(``experiment.build``, ``experiment.dispatch``, ``experiment.execute``,
``experiment.fetch``, inside ``experiment.group``).

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

The harness reduces its own trace to a summary that keeps neither all
operations nor these spans, and removes the trace before the readers
run; so ``reading`` profiles the traced grid's stimulus set once more,
in a trace of its own, and every reader of this module reads that.
"""
from __future__ import annotations

import functools
import glob
import os
import re
import shutil
import tempfile
from collections import Counter

import numpy as np

import hlo_copies
import trace_reduce as TRACE

SPAN_PREFIX = "experiment."
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


def host_spans(profile_dir: str) -> list:
    """``(name, start_ns, duration_ns)`` of the program's ``experiment.``
    spans in a profile."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [(e.name, e.start_ns, e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events if e.name.startswith(SPAN_PREFIX)]


def summarize(ev: dict, spans: list, scopes: dict, trips: int) -> dict:
    """The readings of one profiled grid: device self seconds of each part
    of the loop per trip, and host seconds of each step."""
    steps = {}
    for name, _, d in spans:
        steps[name] = steps.get(name, 0.0) + d / 1e9
    return {"trips": trips,
            "per_trip_s": {k: v / trips for k, v in
                           partition(sweep_ops(ev), scopes).items()},
            "steps_s": steps}


def max_iterations(state: dict) -> int | None:
    """The loop's trips for a grid: the most any lane took."""
    if "iterations" not in state:
        return None
    return int(np.max(np.asarray(state["iterations"])))


@functools.lru_cache(maxsize=1)
def reading(run) -> dict | None:
    """Profile the traced grid's stimulus set once more and reduce it.
    None where the program keeps no ``iterations`` leaf."""
    import jax
    import run as RUN
    if max_iterations(run.grids[0]["state"]) is None:
        return None
    config, traffic = run.cell["config"], run.cell["traffic"]
    spec = RUN.make_spec(config, traffic, run.grids[0]["seeds"],
                         config["sim_len"])
    profile_dir = tempfile.mkdtemp(prefix="bench_scopes_")
    try:
        jax.profiler.start_trace(profile_dir)
        try:
            with jax.profiler.TraceAnnotation(TRACE.WINDOW_SPAN):
                frame = spec.run()
        finally:
            jax.profiler.stop_trace()
        ev = TRACE.extract(profile_dir, 1)
        spans = host_spans(profile_dir)
    finally:
        shutil.rmtree(profile_dir, ignore_errors=True)
    (group,) = frame.groups
    return summarize(ev, spans, instruction_scopes(run.compiled().as_text()),
                     max_iterations(group.state))


def per_trip_us(run, key) -> float | None:
    r = reading(run)
    if r is None or not r["per_trip_s"]:
        return None
    return 1e6 * r["per_trip_s"].get(key, 0.0)


def step_ms(run, *names) -> float | None:
    r = reading(run)
    if r is None or not all(n in r["steps_s"] for n in names):
        return None
    return 1e3 * sum(r["steps_s"][n] for n in names)
