"""The control of the check, and faults planted under the timed path.

The configurations state no numeric precision; they state a guarantee,
that no event is dropped.  The control breaks it: the plain reference,
put in the program's place, run with an event queue a sixteenth of the
configured size, the saving a later change might be tempted by (an
eighth still held the outage cell's peak of 1,008 events).  It drops
events, so its lanes differ from the full-size reference, and the check
must say not correct.

Each fault replaces ``ExperimentSpec.run`` with a broken copy of the
timed path: a step that returns its state unchanged (nothing simulated),
half of the lanes left out (the other half copied in their place), one
answer altered where it is produced, a (k, k) plane of times kept in
bfloat16, either returned so or rounded through it and back to f32, and,
for a configuration of several groups, two groups' states swapped or the
last group left out.
"""
from __future__ import annotations

import numpy as np

CONTROL_QUEUE_CUT = 16


def control_lane(run, cell, shape, knobs, seed):
    return run.reference_lane(cell, shape, knobs, seed,
                              queue_cap=shape["queue_cap"]
                              // CONTROL_QUEUE_CUT)


def _restate(frame, fn):
    """Each group's state, as NumPy, through ``fn(state, group index)``."""
    for i, g in enumerate(frame.groups):
        g.state = fn({k: np.array(v) for k, v in g.state.items()}, i)
    return frame


def control_run(run, cell):
    """``ExperimentSpec.run`` with the control's lanes in the program's,
    each group's at its own shape."""
    from repro.core.experiment import ExperimentSpec
    real = ExperimentSpec.run
    shapes = run.group_shapes(cell["config"])

    def patched(spec, mode=None):
        frame = real(spec, mode)
        knobs = run.lane_knobs(spec)

        def fill(st, i):
            seeds = [lane["seed"] for lane in frame.groups[i].lanes]
            for b, kn in enumerate(knobs):
                for s, seed in enumerate(seeds):
                    lane = control_lane(run, cell, shapes[i], kn, seed)
                    for key, v in lane.items():
                        if key in st:
                            st[key][b, s] = v
            return st
        return _restate(frame, fill)
    return patched


def fault_run(kind):
    """``ExperimentSpec.run`` broken by one planted fault."""
    import dataclasses

    import jax.numpy as jnp
    from repro.core.experiment import ExperimentSpec
    real = ExperimentSpec.run

    def unchanged(spec, mode=None):
        return real(dataclasses.replace(spec, sim_len=0.0), mode)

    def half_lanes(spec, mode=None):
        def fill(st, _):
            for v in st.values():
                half = v.shape[1] // 2
                v[:, half:2 * half] = v[:, :half]
            return st
        return _restate(real(spec, mode), fill)

    def altered(spec, mode=None):
        def fill(st, i):
            if i == 0:
                st["app_done"][0, 0, 0] += np.float32(8.0)
            return st
        return _restate(real(spec, mode), fill)

    def plane_bf16(spec, mode=None):
        def fill(st, _):
            st["view_t"] = st["view_t"].astype(jnp.bfloat16)
            return st
        return _restate(real(spec, mode), fill)

    def plane_bf16_rounded(spec, mode=None):
        def fill(st, _):
            st["view_t"] = st["view_t"].astype(jnp.bfloat16).astype(
                np.float32)
            return st
        return _restate(real(spec, mode), fill)

    def groups_swapped(spec, mode=None):
        frame = real(spec, mode)
        g0, g1 = frame.groups[:2]
        g0.state, g1.state = g1.state, g0.state
        return frame

    def group_missing(spec, mode=None):
        frame = real(spec, mode)
        frame.groups = frame.groups[:-1]
        return frame

    return {"state_unchanged": unchanged, "half_lanes": half_lanes,
            "answer_altered": altered, "plane_bf16": plane_bf16,
            "plane_bf16_rounded": plane_bf16_rounded,
            "groups_swapped": groups_swapped,
            "group_missing": group_missing}[kind]
