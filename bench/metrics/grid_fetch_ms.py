"""Host time to bring a grid's final state to the host: the
``experiment.fetch`` span of each group of the traced grid, in ms
(``bench/trace_reduce.py``)."""
import trace_reduce


def read(run):
    return trace_reduce.step_ms(run, "experiment.fetch")
