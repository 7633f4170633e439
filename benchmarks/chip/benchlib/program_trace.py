"""The program's own host spans in a traced run.

``core/hsfl.py`` wraps each fused round in the host span ``hsfl.round``
(stat ``round``) and its stages in ``hsfl.schedule``, ``hsfl.presample``,
``hsfl.launch`` (stats ``users``, ``K``, ``staged_bytes``) and
``hsfl.readback``.  They share the profiler's clock with the device's
``XLA Ops`` line, so each idle gap of the device falls in one stage.

The spans are read from the newest ``.xplane.pb`` under the harness's
trace directory once (cached by path and mtime); the device's busy
intervals come from the benchmark's own ``tracing.Trace``.  The
reductions take plain ``(name, start_ns, end_ns, stats)`` tuples so that
they can be checked on a hand-built trace without a chip.  Each returns
``None`` when the trace holds no ``hsfl.*`` span, as a program without
the spans gives, rather than 0.

The device scopes of ``core/fused_round.py`` (``local_epoch``, ``probe``,
``aggregate``) are not read: the events of a TPU trace's ``XLA Ops``
line carry no ``op_name`` metadata, only the instruction's text and its
device offset and duration."""
from __future__ import annotations

import glob
import os
import sys

from . import harness
from .tracing import gaps

PREFIX = "hsfl."
ROUND = "hsfl.round"
STAGES = ("hsfl.schedule", "hsfl.presample", "hsfl.launch",
          "hsfl.readback")


def read_spans(path: str) -> list:
    """(name, start, end, stats) of every ``hsfl.*`` event on the host."""
    from jax.profiler import ProfileData

    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PREFIX)]


_cache: dict = {}


def load(trace_dir=None) -> list:
    """The ``hsfl.*`` spans of the newest trace under ``trace_dir`` (the
    harness's by default); empty where there is none."""
    files = glob.glob(os.path.join(str(trace_dir or harness.TRACE_DIR),
                                   "**", "*.xplane.pb"), recursive=True)
    if not files:
        return []
    path = max(files, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = read_spans(path)
    return _cache[key]


# -- reductions on plain tuples (ns on the profiler clock) ------------------

def spans_named(spans, name: str, window):
    lo, hi = window
    return [sp for sp in spans if sp[0] == name and sp[1] >= lo
            and sp[2] <= hi]


def stage_idle_ms(spans, busy, window, name: str):
    """Device-idle ms inside the window's ``name`` spans, per round (per
    ``hsfl.round`` span in the window)."""
    rounds = spans_named(spans, ROUND, window)
    if not rounds:
        return None
    idle = sum(e - s for _, s0, e0, _ in spans_named(spans, name, window)
               for s, e in gaps(busy, s0, e0))
    return idle / len(rounds) * 1e-6


def tail_idle_ms(spans, busy, window, name: str):
    """The part of ``stage_idle_ms`` that follows the device's last op in
    each span: for ``hsfl.readback``, the host's own work once the round
    program is done, apart from the gaps between the program's ops."""
    rounds = spans_named(spans, ROUND, window)
    if not rounds:
        return None
    tail = 0
    for _, s0, e0, _ in spans_named(spans, name, window):
        idle = gaps(busy, s0, e0)
        if idle and idle[-1][1] == e0:
            tail += e0 - idle[-1][0]
    return tail / len(rounds) * 1e-6


def padded_user_share(spans, window):
    """100 x the stacked user slots that hold no user, over all slots, in
    the window's ``hsfl.launch`` spans."""
    launches = spans_named(spans, "hsfl.launch", window)
    slots = sum(st["K"] for *_, st in launches)
    if not slots:
        return None
    return 100.0 * (slots - sum(st["users"] for *_, st in launches)) / slots


def staged_mb(spans, window):
    """Mean bytes staged to the device per launch, in MB."""
    launches = spans_named(spans, "hsfl.launch", window)
    if not launches:
        return None
    return sum(st["staged_bytes"] for *_, st in launches) / len(launches) \
        * 1e-6


# -- what the layer readers call ---------------------------------------------

_logged = None          # the spans whose remainder is logged already


def _program(run):
    """The run's ``hsfl.*`` spans, or ``None`` where the trace holds none;
    the first call on a trace logs what the stages leave unexplained."""
    global _logged
    spans = load()
    if not spans:
        return None
    if spans is not _logged:
        _logged = spans
        _log_remainder(run, spans)
    return spans


def _log_remainder(run, spans) -> None:
    """On standard error: ``host_ms_per_round`` less the four stages, and
    how much of the readback's idle time follows the round program."""
    w, busy = run.trace.window, run.trace.busy(0)
    stages = {n: stage_idle_ms(spans, busy, w, n) for n in STAGES}
    if None in stages.values():
        return
    host = harness.layer_reader("host_ms_per_round")(run)
    msg = "idle ms per round " + " ".join(f"{n}={v:.3f}"
                                          for n, v in stages.items())
    if host is not None:
        rest = host - sum(stages.values())
        msg += (f"; host_ms_per_round={host:.3f} less the four stages "
                f"leaves {rest:.3f} ms unexplained "
                f"({100 * rest / host:.1f} %)")
    tail = tail_idle_ms(spans, busy, w, "hsfl.readback")
    msg += f"; of hsfl.readback, {tail:.3f} ms follow the device's last op"
    print(f"[program_trace] {msg}", file=sys.stderr, flush=True)


def stage_idle(run, name: str):
    spans = _program(run)
    return None if spans is None else stage_idle_ms(
        spans, run.trace.busy(0), run.trace.window, name)


def launch_counter(run, reduce):
    spans = _program(run)
    return None if spans is None else reduce(spans, run.trace.window)
