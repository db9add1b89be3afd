"""KiB copied per iteration of the main loop of the cell's compiled
program, counted in its HLO text."""
import hlo_copies


def read(run):
    return hlo_copies.main_loop_copy_bytes(run.compiled().as_text()) / 1024.0
