"""Share of the traced window in which the cell's chips ran no
operation, averaged over them, in %: on several chips, those that finish
their group early wait for the one that sets the pace."""


def read(run):
    t = run.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
