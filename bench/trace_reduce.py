"""Reduce a profiler trace to device busy time, program time and idle gaps.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
JAX's own ``ProfileData``, into three plain lists of ``(name, start_ns,
duration_ns)``: the operations of each traced chip (the ``XLA Ops`` line
of ``/device:TPU:<n>``), the programs (``XLA Modules``), and the host
spans of the harness (``TraceAnnotation`` names starting ``bench.``) and
of the program's steps (``experiment.build``, ``.dispatch``,
``.execute``, ``.fetch``, one of each per group, ``.group`` around them
outside pmap).  ``summarize`` works on those lists alone, so a small
recorded fixture checks it without a chip.
"""
from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "bench."
STEP_PREFIX = "experiment."
WINDOW_SPAN = "bench.window"
_DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")


def extract(profile_dir: str, chips: int) -> dict:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {profile_dir}, "
                           f"found {paths}")
    data = ProfileData.from_file(paths[0])
    ops = {}
    modules = {}
    spans = []
    for plane in data.planes:
        m = _DEVICE_RE.match(plane.name)
        if m and int(m.group(1)) < chips:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev] = [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
                elif line.name == "XLA Modules":
                    modules[dev] = [(e.name, e.start_ns, e.duration_ns)
                                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events
                          if e.name.startswith((SPAN_PREFIX, STEP_PREFIX))]
    return {"ops": [ops.get(d, []) for d in range(chips)],
            "modules": [modules.get(d, []) for d in range(chips)],
            "spans": spans}


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events, w0, w1):
    return [(n, max(s, w0), min(s + d, w1)) for n, s, d in events
            if s < w1 and s + d > w0]


def short_name(hlo: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion %fusion.3``: the
    op's kind and name, without its types and operands."""
    name, _, rest = hlo.partition(" = ")
    if not rest:
        return hlo
    depth, i = 0, len(rest)
    for i, ch in enumerate(rest):               # skip the result type
        depth += ch in "([{"
        depth -= ch in ")]}"
        if depth == 0 and ch == " ":
            break
    kind = rest[i + 1:].split("(", 1)[0]
    return f"{kind} {name}" if kind else name


def _self_times(events) -> dict:
    """Device seconds of each op less the ops nested inside it (a loop's
    event spans its body's operations)."""
    out, stack = {}, []

    def pop():
        end, name, start, child = stack.pop()
        out[name] = out.get(name, 0) + (end - start) - child

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            pop()
        if stack:
            stack[-1][3] += min(e, stack[-1][0]) - s
        stack.append([e, name, s, 0])
    while stack:
        pop()
    return out


def step_seconds(spans: list, w0: int, w1: int) -> dict:
    """Host seconds of each of the program's steps inside the window,
    summed over the grid's groups."""
    out = {}
    for name, s, e in _clip(spans, w0, w1):
        if name.startswith(STEP_PREFIX):
            out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return out


def summarize(ev: dict, top: int = 10) -> dict | None:
    """Busy and window seconds (busy averaged over the traced chips), each
    chip's busy seconds, the host seconds of each of the program's steps,
    and, on the chip that sets
    the pace (the most busy time in the window; the first of equals), the
    device seconds of each program, the operations that took most device
    self time, and the longest idle gaps, each labelled by the innermost
    harness span open at its middle.  With one chip, that chip is chip 0.
    None when the trace holds no window span or no device operation."""
    win = [(s, s + d) for n, s, d in ev["spans"] if n == WINDOW_SPAN]
    if len(win) != 1 or not any(ev["ops"]):
        return None
    w0, w1 = win[0]
    busy = []
    for ops in ev["ops"]:
        iv = _merged((s, e) for _, s, e in _clip(ops, w0, w1))
        busy.append(sum(e - s for s, e in iv))
    pace = busy.index(max(busy))
    per_module = {}
    for name, s, e in _clip(ev["modules"][pace], w0, w1):
        per_module[name] = per_module.get(name, 0) + (e - s)
    pace_ops = _clip(ev["ops"][pace], w0, w1)
    per_op = {}
    for name, t in _self_times(pace_ops).items():
        per_op[short_name(name)] = per_op.get(short_name(name), 0) + t
    iv = _merged((s, e) for _, s, e in pace_ops)
    edges = [w0] + [x for s, e in iv for x in (s, e)] + [w1]
    spans = sorted((s, -(s + d), n) for n, s, d in ev["spans"]
                   if n.startswith(SPAN_PREFIX) and n != WINDOW_SPAN)
    gaps = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b > a:
            mid = (a + b) / 2
            open_ = [n for s, e, n in spans if s <= mid < -e]
            gaps.append((open_[-1] if open_ else "untracked", (b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "busy_s_per_chip": [b / 1e9 for b in busy],
        "steps_s": step_seconds(ev["spans"], w0, w1),
        "pace_chip": pace,
        "module_s": {n: t / 1e9 for n, t in per_module.items()},
        "device_ops": [[n, t / 1e9] for n, t in
                       sorted(per_op.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [list(g) for g in gaps[:top]],
    }


def step_ms(run, *names) -> float | None:
    """Host ms of the named steps of the traced grid, from a reader's
    run; None where a step is missing."""
    t = run.trace
    if t is None or not all(n in t["steps_s"] for n in names):
        return None
    return 1e3 * sum(t["steps_s"][n] for n in names)
