"""Mean MB staged to the device per launched round: the ``staged_bytes``
stat of the window's ``hsfl.launch`` spans (the batches, labels and
channel arrays, ``core/hsfl.py``)."""
from benchlib import program_trace


def read(run):
    return program_trace.launch_counter(run, program_trace.staged_mb)
