"""Host spans of the benchmark's own calls into the program.

Each span is kept in memory (name, start and end on the host's monotonic
clock in ns, and a small dict of attributes) and, while a profiler trace
is running, also written into it as a ``TraceAnnotation`` so that the
trace reducer can lay the device's idle gaps over what the host was doing.
"""
from __future__ import annotations

import contextlib
import time

import jax


class Spans:
    def __init__(self) -> None:
        self.records = []          # (name, t0_ns, t1_ns, attrs)

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        t0 = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(name):
            yield attrs
        self.records.append((name, t0, time.perf_counter_ns(), attrs))
