"""Host time from the start of a grid to its program's dispatch: the
``experiment.build`` and ``experiment.dispatch`` spans, in ms
(``bench/scopes.py``)."""
import scopes


def read(run):
    return scopes.step_ms(run, "experiment.build", "experiment.dispatch")
