"""Global on/off switch for the observability layer.

Everything in ``repro.obs`` is built to cost next to nothing when disabled:
metric mutations and instants early-return after one boolean check, and the
sync auditor's jax patches are only installed while an audit context is
active. Spans follow the JAX profiler's own switch instead (see
:mod:`repro.obs.spans`). The switch is process-wide
(the launch CLIs flip it from ``--metrics``/``--trace-out``); instrumented
hot loops may additionally guard multi-call blocks with ``enabled()`` to pay
the boolean once instead of per call.
"""
from __future__ import annotations

_enabled = False


def enable() -> None:
    """Turn metric and instant recording on, process-wide."""
    global _enabled
    _enabled = True


def disable() -> None:
    """Turn metric and instant recording off (data is kept)."""
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled
