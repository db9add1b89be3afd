"""Device time of the sweep program per trip of its event loop on the
traced grid (the most ``iterations`` of any lane), in us."""
import scopes


def read(run):
    t = run.trace
    trips = scopes.max_iterations(run.grids[0]["state"])
    if t is None or not trips:
        return None
    sweep_s = sum(s for name, s in t["module_s"].items()
                  if scopes.SWEEP in name)
    if sweep_s <= 0:
        return None
    return 1e6 * sweep_s / trips
