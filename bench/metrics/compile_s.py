"""Host seconds of the set-up run that compiles (or loads from the
persistent cache) the cell's program."""


def read(run):
    return run.compile_s
