"""Reductions of a language-model cell's program spans (``core/hsfl.py``):
the ``steps`` stat of ``hsfl.launch`` (users x local epochs x steps per
epoch) and the ``hsfl.experts`` span after the readback, whose stats
``held_max``, ``held_mean`` and ``routed`` count the (token, slot) pairs
the held routed experts took in the round.  They take plain
``(name, start_ns, end_ns, stats)`` tuples and return ``None`` where the
window holds no such span or stat, as a program without them gives."""
from __future__ import annotations

from . import program_trace


def steps_in(spans, window):
    """SGD steps launched in the window, or None without the stat."""
    launches = [sp for sp in program_trace.spans_named(spans, "hsfl.launch",
                                                       window)
                if "steps" in sp[3]]
    return sum(st["steps"] for *_, st in launches) if launches else None


def step_device_ms(spans, busy_s: float, window):
    """Device-busy ms per SGD step: ``busy_s`` over the window's steps."""
    steps = steps_in(spans, window)
    return None if not steps or busy_s <= 0 else busy_s * 1e3 / steps


def held_expert_load(spans, window):
    """sum of held_max / sum of held_mean over the window's experts spans:
    how far the busiest held expert of a layer runs above the mean."""
    ex = program_trace.spans_named(spans, "hsfl.experts", window)
    mean = sum(st["held_mean"] for *_, st in ex)
    return None if not ex or mean <= 0 else \
        sum(st["held_max"] for *_, st in ex) / mean


def spans():
    """The traced run's ``hsfl.*`` spans, or None where it holds none."""
    return program_trace.load() or None
