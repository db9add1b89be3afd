"""Device self time per loop trip of the operations under the
``sim.handlers`` scope, copies left out, in us (``bench/scopes.py``)."""
import scopes


def read(run):
    return scopes.per_trip_us(run, "sim.handlers")
