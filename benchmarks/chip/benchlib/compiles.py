"""Count XLA compiles from JAX's compile log.

JAX's dispatch logger emits "Finished XLA compilation of <name> in ..."
once per compile on both dispatch paths (eager jit calls and AOT
``lower().compile()``, from any thread), persistent-cache reads included;
in-memory cache hits are silent."""
from __future__ import annotations

import logging
import re
import threading

import jax

_LOGGERS = ("jax._src.dispatch",)
# loggers that ``jax_log_compiles`` also opens, kept off the console
_QUIET = ("jax._src.interpreters.pxla", "jax._src.compiler")
_COMPILE_RE = re.compile(
    r"Finished XLA compilation of (?:jit\()?([\w<>\[\]()., -]+?)\)? in ")


def _drop(record) -> bool:
    return False


class CompileCounter(logging.Handler):
    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.names = []
        self._lock = threading.Lock()
        self._prev = None

    def emit(self, record: logging.LogRecord) -> None:
        m = _COMPILE_RE.search(record.getMessage())
        if m:
            with self._lock:
                self.names.append(m.group(1))

    def count(self) -> int:
        with self._lock:
            return len(self.names)

    def __enter__(self) -> "CompileCounter":
        # the global flag: the context-manager form is thread-local
        self._prev = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        self._propagate = {}
        for name in _LOGGERS:
            logger = logging.getLogger(name)
            logger.addHandler(self)
            # the records reach this handler only, not the console
            self._propagate[name] = logger.propagate
            logger.propagate = False
        for name in _QUIET:
            logging.getLogger(name).addFilter(_drop)
        return self

    def __exit__(self, *exc) -> None:
        for name in _LOGGERS:
            logger = logging.getLogger(name)
            logger.removeHandler(self)
            logger.propagate = self._propagate[name]
        for name in _QUIET:
            logging.getLogger(name).removeFilter(_drop)
        jax.config.update("jax_log_compiles", bool(self._prev))
