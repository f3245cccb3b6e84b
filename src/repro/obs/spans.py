"""Nested host spans on the JAX profiler's clock.

A span is one region of host code (``with span("serve.fetch"): ...``). It
pushes its name on a thread-local stack (``current()``, which
:mod:`repro.obs.sync_audit` attributes syncs to) and opens a
``jax.profiler.TraceAnnotation``. While a JAX profile is being captured
(``jax.profiler.start_trace`` or ``trace``, the launch CLIs' ``--trace-out``,
a benchmark's traced run), the span lands on the host timeline of that
profile beside the device's ops, so an idle gap of the device can be put down
to what the host was doing. Whether anything is recorded is the profiler's
own switch, not :mod:`repro.obs.state`: with no profile running a span
builds no annotation and costs the stack push and pop.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from repro.obs import state

_recording = TraceAnnotation.is_enabled     # the profiler's TraceMe switch


class _Local(threading.local):
    def __init__(self):
        self.stack: List[str] = []          # names of the active spans


_tls = _Local()


class Span:
    """One active region: a stack entry and, while profiling, an
    annotation."""
    __slots__ = ("name", "_stack", "_ann")

    def __init__(self, name: str, args: Dict[str, Any]):
        self.name = name
        self._stack = _tls.stack
        self._ann: Optional[TraceAnnotation] = \
            TraceAnnotation(name, **args) if _recording() else None

    def __enter__(self) -> "Span":
        self._stack.append(self.name)
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def __exit__(self, typ, val, tb) -> bool:
        if self._ann is not None:
            self._ann.__exit__(typ, val, tb)
        self._stack.pop()
        return False


def span(name: str, **args: Any) -> Span:
    """Open a nested span; ``args`` become the annotation's metadata."""
    return Span(name, args)


def instant(name: str, **args: Any) -> None:
    """A zero-length annotation (e.g. a request lifecycle edge); only while
    :mod:`repro.obs` is enabled."""
    if state.enabled() and _recording():
        with TraceAnnotation(name, **args):
            pass


def current() -> str:
    """Name of the innermost active span on this thread ("" outside any)."""
    stack = _tls.stack
    return stack[-1] if stack else ""
