"""Device time of the sweep program on the chip that sets the pace, per
trip of the event loop of the group that ran there on the traced grid
(the most ``iterations`` of any of its lanes), in us."""
import scopes


def read(run):
    t = run.trace
    if t is None:
        return None
    group = scopes.pace_group(run)
    trips = None if group is None else scopes.max_iterations(group["state"])
    if not trips:
        return None
    sweep_s = sum(s for name, s in t["module_s"].items()
                  if scopes.SWEEP in name)
    if sweep_s <= 0:
        return None
    return 1e6 * sweep_s / trips
