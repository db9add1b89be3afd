"""Host time from the start of a grid to its programs' dispatch: the
``experiment.build`` and ``experiment.dispatch`` spans of each group of
the traced grid, in ms (``bench/trace_reduce.py``)."""
import trace_reduce


def read(run):
    return trace_reduce.step_ms(run, "experiment.build", "experiment.dispatch")
