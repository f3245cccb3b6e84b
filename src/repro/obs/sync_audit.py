"""Host<->device synchronization audit.

``with sync_audit() as audit:`` counts the host-blocking device reads the
wrapped host code performs — ``jax.block_until_ready`` / ``jax.device_get``
calls and ``np.asarray``/``float``/``int``/``bool`` conversions of committed
``jax.Array`` values — by patching those entry points for the duration of
the context, plus the jit dispatches instrumented call sites announce via
:func:`mark_dispatch`. It is the empirical check of the paper's CA-k claim:
the k-step fused decode must make one host round trip per k steps, and the
audit measures that at the jax boundary instead of trusting the engine's own
``EngineStats.syncs`` bookkeeping.

Counting semantics (the paper's alpha-beta cost split):

* ``transfers`` counts every intercepted device read — the *words* side.
* ``syncs`` counts round-trip *epochs* — the latency (alpha) side, the term
  CA-k divides by k. Consecutive reads coalesce into one sync until a
  dispatch boundary (:func:`mark_dispatch`) closes the epoch: once one
  result of a dispatched computation has been fetched, fetching its siblings
  costs bandwidth but no extra round trip. Instrumented host loops (the
  serve engine, the training runner) mark their dispatch sites; the markers
  are unconditional no-ops outside an active audit.
* ``dispatches`` counts those announced dispatch boundaries.
* ``overlap_epochs`` counts *hidden* syncs: epochs whose reads fetch the
  results of a dispatch that is no longer the latest one — i.e. the host had
  already dispatched newer device work before blocking, so the wait was
  (partly) covered by useful compute. :func:`mark_dispatch` returns a
  monotonically increasing ticket; a host loop that double-buffers announces
  which dispatch it is about to fetch via :func:`mark_fetch(ticket)
  <mark_fetch>`, and the next epoch counts as hidden iff the ticket is older
  than the latest dispatch. A loop that always fetches its own latest
  dispatch (the classic blocking schedule) never produces hidden epochs.
* ``by_span`` attributes each sync to the innermost active
  :mod:`repro.obs.spans` span at the moment it was counted.

Counting happens at dispatch boundaries only, never inside traced code: a
read whose argument is a ``jax.core.Tracer`` is ignored, because it happens
once per trace, not once per execution.

Patches are installed when the first audit enters and removed when the last
exits — code outside any audit pays nothing. Nested audits each receive all
events.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.obs import spans

_audits: List["SyncAudit"] = []
_patch_lock = threading.Lock()
_saved: dict = {}
_tls = threading.local()                # .in_read: reentrancy guard
_dispatch_seq = 0                       # monotonic mark_dispatch ticket

#: (holder, attribute) module-level functions to wrap; each call is one read
_FN_PATCHES = (("block_until_ready", jax), ("device_get", jax))
#: conversion methods of the concrete array class that block on device
#: results. NOTE: numpy 2 converts jax arrays via the buffer protocol and
#: never calls ``__array__``, hence the additional ``_NP_PATCHES`` below;
#: these dunders still matter for ``float(x)``/``int(x)``/``bool(x)`` and
#: explicit ``x.__array__()``.
_METHOD_PATCHES = ("__array__", "__float__", "__int__", "__bool__")
#: numpy entry points that pull device arrays to host (counted only when the
#: first argument is a concrete jax array)
_NP_PATCHES = ("asarray", "array")


def _traced(tree) -> bool:
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree.leaves(tree))


class SyncAudit:
    """Counters for one audited region (see module docstring)."""

    def __init__(self):
        self.syncs = 0              # coalesced round-trip epochs (alpha term)
        self.transfers = 0          # raw intercepted device reads (beta term)
        self.dispatches = 0         # mark_dispatch() boundaries
        self.overlap_epochs = 0     # hidden syncs (fetch of a stale ticket)
        self.block_until_ready = 0
        self.device_get = 0
        self.by_span: Dict[str, int] = {}
        self._epoch_open = False
        self._last_seq: Optional[int] = None    # latest dispatch ticket seen
        self._fetch_hidden = False              # next epoch is a hidden sync

    def _read(self, kind: str) -> None:
        self.transfers += 1
        if kind == "block_until_ready":
            self.block_until_ready += 1
        elif kind == "device_get":
            self.device_get += 1
        if not self._epoch_open:
            self._epoch_open = True
            self.syncs += 1
            if self._fetch_hidden:
                self.overlap_epochs += 1
                self._fetch_hidden = False
            name = spans.current()
            self.by_span[name] = self.by_span.get(name, 0) + 1

    def _dispatch(self, seq: int) -> None:
        self.dispatches += 1
        self._epoch_open = False
        self._last_seq = seq
        self._fetch_hidden = False  # a newer dispatch voids the announcement

    def _fetch(self, ticket: Optional[int]) -> None:
        # a fetch boundary is also an epoch boundary: reads coalesce only
        # within one dispatched computation's result set, and this announces
        # the results of a *specific* dispatch are about to be read (e.g.
        # back-to-back completions at the tail of a double-buffered drain
        # are separate round trips, not siblings of one sync)
        self._epoch_open = False
        # the next epoch is hidden iff it fetches results of a dispatch that
        # is no longer the latest: newer device work was already in flight
        self._fetch_hidden = (ticket is not None
                              and self._last_seq is not None
                              and ticket < self._last_seq)

    @property
    def blocking_syncs(self) -> int:
        """Epochs with nothing newer in flight — true pipeline stalls."""
        return self.syncs - self.overlap_epochs

    def as_dict(self) -> dict:
        return dict(syncs=self.syncs, transfers=self.transfers,
                    dispatches=self.dispatches,
                    overlap_epochs=self.overlap_epochs,
                    block_until_ready=self.block_until_ready,
                    device_get=self.device_get, by_span=dict(self.by_span))


def _count_read(kind: str, arg=None) -> None:
    if not _audits or getattr(_tls, "in_read", False):
        return
    if _traced(arg):
        return                      # inside a trace: per-compile, not per-run
    for a in _audits:
        a._read(kind)


def mark_dispatch(site: str = "") -> int:
    """Announce a host->device dispatch boundary (closes the read epoch).

    Instrumented host loops call this immediately before dispatching a
    jitted computation whose results they will fetch. Returns a monotonic
    ticket identifying the dispatch; a double-buffered loop hands the ticket
    to :func:`mark_fetch` when it later blocks on the results, so the audit
    can classify the sync as hidden vs blocking. Near-no-op (one integer
    increment + truthiness check) when no audit is active.
    """
    global _dispatch_seq
    _dispatch_seq += 1
    if _audits:
        for a in _audits:
            a._dispatch(_dispatch_seq)
    return _dispatch_seq


def mark_fetch(ticket: Optional[int] = None) -> None:
    """Announce that the upcoming device reads fetch the results of the
    dispatch identified by ``ticket`` (from :func:`mark_dispatch`).

    If newer work was dispatched since — ``ticket`` is stale — the epoch the
    reads open counts toward ``overlap_epochs``: the host had productive
    device work in flight while it waited, so the round trip was hidden
    rather than a stall. No-op when no audit is active or ticket is None.
    """
    if not _audits:
        return
    for a in _audits:
        a._fetch(ticket)


@contextlib.contextmanager
def _reentrancy_guard():
    prev = getattr(_tls, "in_read", False)
    _tls.in_read = True
    try:
        yield
    finally:
        _tls.in_read = prev


def _wrap_fn(orig, kind):
    def wrapper(*args, **kwargs):
        _count_read(kind, args)
        with _reentrancy_guard():   # device_get re-enters __array__ per leaf
            return orig(*args, **kwargs)
    wrapper.__wrapped__ = orig
    return wrapper


def _wrap_method(orig, kind):
    def wrapper(self, *args, **kwargs):
        _count_read(kind)
        with _reentrancy_guard():
            return orig(self, *args, **kwargs)
    wrapper.__wrapped__ = orig
    return wrapper


def _wrap_np(orig):
    """Wrap a numpy conversion entry point: count iff converting a device
    array (numpy 2 reads those through the buffer protocol, bypassing any
    ``__array__`` patch, so interception must happen at the numpy call)."""
    def wrapper(a, *args, **kwargs):
        if (isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer)
                and not getattr(_tls, "in_read", False)):
            _count_read("convert")
            with _reentrancy_guard():
                return orig(a, *args, **kwargs)
        return orig(a, *args, **kwargs)
    wrapper.__wrapped__ = orig
    return wrapper


def _install() -> None:
    for name, holder in _FN_PATCHES:
        orig = getattr(holder, name)
        _saved[(id(holder), name)] = (holder, orig)
        setattr(holder, name, _wrap_fn(orig, name))
    for name in _NP_PATCHES:
        orig = getattr(np, name)
        _saved[(id(np), name)] = (np, orig)
        setattr(np, name, _wrap_np(orig))
    array_type = type(jax.device_put(0))     # jax.Array's concrete class
    for name in _METHOD_PATCHES:
        orig = getattr(array_type, name, None)
        if orig is None:
            continue
        _saved[(id(array_type), name)] = (array_type, orig)
        setattr(array_type, name, _wrap_method(orig, "convert"))


def _uninstall() -> None:
    for (holder, orig), key in [(v, k) for k, v in _saved.items()]:
        setattr(holder, key[1], orig)
    _saved.clear()


@contextlib.contextmanager
def sync_audit():
    """Audit host<->device syncs in the wrapped region (see module doc)."""
    audit = SyncAudit()
    with _patch_lock:
        if not _audits:
            _install()
        _audits.append(audit)
    try:
        yield audit
    finally:
        with _patch_lock:
            _audits.remove(audit)
            if not _audits:
                _uninstall()
