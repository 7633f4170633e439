"""Per round, the device-idle ms inside the program's ``hsfl.presample``
span (``core/hsfl.py``): the fleet moves, the batch gather into the
round's inputs and the per-user constants."""
from benchlib import program_trace


def read(run):
    return program_trace.stage_idle(run, "hsfl.presample")
