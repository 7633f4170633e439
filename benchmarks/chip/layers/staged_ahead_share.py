"""Share of the window's launched rounds whose inputs were staged while
the previous round's program ran: 100 x the sum of the ``ahead`` stat over
the number of the window's ``hsfl.launch`` spans (``core/hsfl.py``; 1
where the round was scheduled, presampled and staged during the round
before it, 0 where it prepared itself).  A program whose launches carry
no ``ahead`` stat gives nothing."""
from benchlib import program_trace


def share(spans, window):
    launches = program_trace.spans_named(spans, "hsfl.launch", window)
    if not launches or any("ahead" not in st for *_, st in launches):
        return None
    return 100.0 * sum(st["ahead"] for *_, st in launches) / len(launches)


def read(run):
    return program_trace.launch_counter(run, share)
