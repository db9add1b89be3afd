"""Device self time per loop trip of every copy of the sweep program,
whatever its scope, in us (``bench/scopes.py``): the time beside
``loop_copy_kib_per_iter``'s bytes."""
import scopes


def read(run):
    return scopes.per_trip_us(run, "copy")
