"""Per round, the device-idle ms inside the program's ``hsfl.readback``
span (``core/hsfl.py``): the wait for the round program and the copies
of its stats back to the host."""
from benchlib import program_trace


def read(run):
    return program_trace.stage_idle(run, "hsfl.readback")
