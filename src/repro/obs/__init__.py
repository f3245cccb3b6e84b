"""repro.obs — zero-overhead-when-disabled observability.

Three pillars, one switch:

- ``spans``      — nested host spans and instant events as
                   ``jax.profiler.TraceAnnotation``s: on the profiler's
                   clock, beside the device's ops, in any JAX profile.
- ``metrics``    — labeled counter/gauge/histogram registry, exported as
                   Prometheus text (``write_prometheus``) or JSONL.
- ``sync_audit`` — a context manager counting host<->device synchronization
                   points (blocking reads, coalesced into round-trip epochs
                   at ``mark_dispatch`` boundaries) — the empirical check of
                   the paper's CA-k sync-per-k-steps claim. ``mark_dispatch``
                   returns a ticket; a double-buffered host loop announces
                   the ticket it is about to block on via ``mark_fetch``,
                   and epochs that fetch a stale ticket (newer device work
                   already in flight) are counted as ``overlap_epochs`` —
                   *hidden* syncs, as opposed to blocking pipeline stalls.

``enable()`` turns metric and instant recording on (the launch CLIs do this
from ``--metrics``/``--trace-out``); while disabled every such point costs one
boolean check. Spans do not read the switch: the profiler records them while
a profile is being captured, and otherwise a span is a push and a pop of a
thread-local name stack. ``sync_audit()`` is independent of the switch: the
context itself opts in, and its jax patches exist only while it is active.
"""
from repro.obs.state import enable, disable, enabled
from repro.obs.spans import span, instant, current
from repro.obs import metrics
from repro.obs.metrics import (REGISTRY, counter, gauge, histogram,
                               to_prometheus, to_jsonl, write_prometheus,
                               write_jsonl)
from repro.obs.sync_audit import (SyncAudit, sync_audit, mark_dispatch,
                                  mark_fetch)


def metrics_snapshot() -> dict:
    """Flat ``{name{labels}: value}`` view of every recorded metric."""
    return REGISTRY.snapshot()


def reset() -> None:
    """Clear metric values (handles stay valid)."""
    REGISTRY.reset()


__all__ = [
    "enable", "disable", "enabled", "reset",
    "span", "instant", "current",
    "metrics", "REGISTRY", "counter", "gauge", "histogram",
    "to_prometheus", "to_jsonl", "write_prometheus", "write_jsonl",
    "metrics_snapshot",
    "SyncAudit", "sync_audit", "mark_dispatch", "mark_fetch",
]
