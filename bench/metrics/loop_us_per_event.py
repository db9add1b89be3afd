"""Device time of the sweep program per simulated event of the traced
grid, in us."""


def read(run):
    t = run.trace
    if t is None or not run.traced_events:
        return None
    sweep_s = sum(s for name, s in t["module_s"].items() if "_sweep" in name)
    if sweep_s <= 0:
        return None
    return 1e6 * sweep_s / run.traced_events
