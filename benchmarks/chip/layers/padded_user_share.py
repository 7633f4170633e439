"""Share of the stacked user slots that train no user: 100 x the sum of
(K - users) over the sum of K, over the window's ``hsfl.launch`` spans
(stats ``K`` and ``users``, ``core/hsfl.py``)."""
from benchlib import program_trace


def read(run):
    return program_trace.launch_counter(run,
                                        program_trace.padded_user_share)
