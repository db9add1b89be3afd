"""Device time of the sweep program on the chip that sets the pace, per
simulated event of the group that ran there on the traced grid, in us."""
import scopes


def read(run):
    t = run.trace
    if t is None:
        return None
    group = scopes.pace_group(run)
    if group is None or not group["events"]:
        return None
    sweep_s = sum(s for name, s in t["module_s"].items()
                  if scopes.SWEEP in name)
    if sweep_s <= 0:
        return None
    return 1e6 * sweep_s / group["events"]
