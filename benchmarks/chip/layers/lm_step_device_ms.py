"""Device-busy ms inside the benchmark's ``run_round`` spans per SGD step
trained: the window's busy time over the ``steps`` stats of its
``hsfl.launch`` spans (users x local epochs x steps per epoch,
``core/hsfl.py``)."""
from benchlib import lm_trace


def read(run):
    spans = lm_trace.spans()
    if spans is None:
        return None
    _, busy_s = run.trace.busy_in("run_round")
    return lm_trace.step_device_ms(spans, busy_s, run.trace.window)
