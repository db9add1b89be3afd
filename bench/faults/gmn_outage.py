"""The ``gmn_outage`` fault schedule, for the plain reference.

A copy of the arithmetic of the program's ``FaultSpec.gmn_outage``, kept
here so that the reference gets its schedule from the benchmark.
"""
import numpy as np


def generate(k: int, *, t_down: float, t_heal: float, frac: float):
    """The managers after the first ceil(k * frac) fail together at
    ``t_down`` and heal together at ``t_heal``: a list of (t, kind, g, 0)
    in schedule order (kind 2 = fail, 3 = heal)."""
    a = max(1, int(np.ceil(k * frac)))
    events = []
    for g in range(min(a, k), k):
        events.append((np.float32(t_down), 2, g, 0))
        events.append((np.float32(t_heal), 3, g, 0))
    return sorted(events)
