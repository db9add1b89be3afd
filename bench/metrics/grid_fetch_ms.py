"""Host time to bring a grid's final state to the host: the
``experiment.fetch`` span, in ms (``bench/scopes.py``)."""
import scopes


def read(run):
    return scopes.step_ms(run, "experiment.fetch")
