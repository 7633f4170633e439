"""Load of the busiest held routed expert against the mean: the sum of
``held_max`` over the sum of ``held_mean`` of the window's
``hsfl.experts`` spans (``core/hsfl.py``); 1 is perfectly even."""
from benchlib import lm_trace


def read(run):
    spans = lm_trace.spans()
    return None if spans is None else lm_trace.held_expert_load(
        spans, run.trace.window)
