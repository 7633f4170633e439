"""Per round, the device-idle ms inside the program's ``hsfl.schedule``
span (``core/hsfl.py``): the fading resample and the greedy selection."""
from benchlib import program_trace


def read(run):
    return program_trace.stage_idle(run, "hsfl.schedule")
