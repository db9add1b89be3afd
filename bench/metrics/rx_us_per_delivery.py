"""Device self time of the pop and the delivery of beacons per delivery,
in us: the traced grid's ``sim.pop`` plus ``sim.rx`` seconds, copies left
out (``bench/scopes.py``, per trip times trips), over the grid's
``beacons_rx`` summed over its lanes."""
import numpy as np

import scopes


def read(run):
    r = scopes.reading(run)
    if r is None or not r["per_trip_s"]:
        return None
    (group,) = run.grids[0]["groups"]
    rx = int(np.sum(group["state"]["beacons_rx"]))
    if not rx:
        return None
    per_trip = r["per_trip_s"].get("sim.pop", 0.0) + \
        r["per_trip_s"].get("sim.rx", 0.0)
    return 1e6 * per_trip * r["trips"] / rx
