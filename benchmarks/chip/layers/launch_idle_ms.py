"""Per round, the device-idle ms inside the program's ``hsfl.launch``
span (``core/hsfl.py``): the host-to-device copies, the straggler carry
where the scheme keeps one, and the dispatch of the round program."""
from benchlib import program_trace


def read(run):
    return program_trace.stage_idle(run, "hsfl.launch")
