"""Unified kernel registry + backend dispatch.

One named-op table for every compute hot spot (``gram``, ``prox_step``,
``prox_loop``, ``flash_attention``, ``ssd``, ``held_experts_ffn``). Each op
registers one implementation per *backend* (``pallas`` — the TPU kernels,
interpret-mode on CPU; ``xla`` — the pure-XLA/jnp paths that compile
anywhere), together with capability predicates, and every layer of the
repo (solvers, models, serve, launch) picks its implementation through
:func:`dispatch` instead of threading ``use_kernel``/``backend`` booleans
through call signatures.

Backend policy resolution order (first match wins):

1. the innermost active ``with registry.use("..."):`` context,
2. a process-wide :func:`set_backend` call,
3. the ``REPRO_BACKEND`` environment variable,
4. ``auto``: ``pallas`` when running on TPU, ``xla`` otherwise.

Dispatch semantics:

* A requested backend whose impl is missing, unavailable on this process, or
  whose per-call ``supports`` predicate rejects the arguments falls back to
  ``xla`` — forcing ``REPRO_BACKEND=pallas`` runs the Pallas kernels
  wherever they apply and the XLA paths everywhere else (e.g. decode steps
  with a dynamic ``kv_valid_len``, which the static-masked kernel cannot do).
  A fallback is never silent: the first one per (op, requested backend)
  warns, and every one counts in ``repro_kernel_fallback_total``.
* An impl registered with a ``vjp=(fwd, bwd)`` pair is wired through
  :func:`jax.custom_vjp` at registration (see :func:`custom_vjp_fn`), so the
  kernels are differentiable end-to-end — ``jax.grad`` through a dispatch
  traces the registered backward kernels instead of attempting (and failing)
  to differentiate a ``pallas_call``. Inside :func:`grad_safe` (entered by
  ``models.loss_fn``) the few impls that still carry ``differentiable=False``
  (no VJP) are skipped — a narrow per-impl guard, not a training-wide XLA
  switch.
* Policy is resolved at *trace* time. jit-ted entry points therefore pin the
  resolved backend for the whole trace (see the solver wrappers in
  ``repro.core``, which also key their jit cache by the resolved name so a
  policy change re-traces instead of reusing a stale executable).

Autotuning: :func:`autotune` times an op's registered block-size candidates
over caller-given shapes and persists the winners to a JSON cache
(``$REPRO_AUTOTUNE_CACHE`` or ``~/.cache/repro/autotune.json``). At dispatch
time the cache fills any tunable kwarg the caller left as ``None``; explicit
kwargs always win.

Cache file format — one entry per (op, backend, shape, device kind)::

    {"gram|pallas|54x5810|cpu": {"params": {"bd": 64, "bm": 512},
                                 "us": 812.4, "schema_version": 2,
                                 "device": "cpu"}}

Entries carry ``schema_version`` (see :data:`SCHEMA_VERSION`) and the device
kind they were tuned on; dispatch skips entries from another schema version
(reported as ``stale`` lookups, distinguishable from genuine misses) rather
than feeding an old schema's params to a new impl.

Backward block sizes are tunables of their own: ``autotune(op, shapes,
grad=True)`` times a ``jax.grad`` through the dispatch and persists winners
under a separate ``<op>+bwd|backend|shape|device`` key, from which dispatch
fills the impl's ``bwd_tunables`` (e.g. flash attention's ``bq_bwd`` /
``bk_bwd``). Entries keyed by an unresolved device kind (``unknown``) are
never persisted — the kind is re-resolved lazily at every lookup, so a cache
written before backend init cannot poison later real-device runs.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib
import json
import os
import threading
import time
import warnings
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import obs

#: dispatch observability (zero-cost while repro.obs is disabled): dispatches
#: by op x backend, the xla fallbacks the policy docs promise, and autotune
#: cache lookup outcomes (hit / miss / stale schema)
_M_DISPATCH = obs.counter("repro_kernel_dispatch_total",
                          "kernel dispatches by op and backend")
_M_FALLBACK = obs.counter("repro_kernel_fallback_total",
                          "fallbacks to xla by op and requested backend")
_M_TUNE_LOOKUP = obs.counter("repro_autotune_lookup_total",
                             "autotune cache lookups by outcome "
                             "(hit/miss/stale)")

#: canonical backend names, in "auto" preference order on TPU
BACKENDS = ("pallas", "xla")
#: accepted spellings that map onto a canonical backend
_ALIASES = {"ref": "xla", "jnp": "xla", "interpret": "pallas"}

#: modules whose import registers every op implementation. Kept as lazy
#: string references so the registry itself has no import-time dependency on
#: the kernels or models packages (they import *us* for the decorators).
_IMPL_MODULES = (
    "repro.kernels.gram.ops",       # registers "gram"
    "repro.kernels.prox_step.ops",  # registers "prox_step", "prox_loop"
    "repro.kernels.ssd.ops",        # registers "ssd"
    "repro.kernels.moe.ops",        # registers "held_experts_ffn"
    "repro.models.attention",       # registers "flash_attention" (model
                                    # layout; wraps kernels/flash_attention)
)


def _always_true(*_args: Any, **_kw: Any) -> bool:
    return True


@dataclasses.dataclass(frozen=True)
class Impl:
    """One backend implementation of a registered op."""
    backend: str
    #: the callable dispatch runs. When the impl was registered with a
    #: ``vjp`` pair this is the custom_vjp-wrapped function, not the raw one.
    fn: Callable
    #: process-level capability (e.g. a future GPU backend probing its
    #: toolchain). Checked once per dispatch.
    available: Callable[[], bool]
    #: per-call capability over the actual arguments (e.g. the prox kernel's
    #: VMEM d-limit, flash attention's static-mask-only constraint).
    supports: Callable[..., bool]
    #: False for kernels without a custom VJP; skipped under grad_safe().
    differentiable: bool = True
    #: kwarg names the autotuner may fill when the caller passes None.
    tunables: Tuple[str, ...] = ()
    #: the (fwd, bwd) pair registration wired through jax.custom_vjp, kept
    #: for introspection (None for natively-differentiable impls).
    vjp: Optional[Tuple[Callable, Callable]] = None
    #: backward-pass kwarg names the grad-mode autotuner may fill (their
    #: winners live under the separate "<op>+bwd|..." cache keys).
    bwd_tunables: Tuple[str, ...] = ()


@dataclasses.dataclass
class Op:
    """A named op: its impls plus autotune/test metadata."""
    name: str
    impls: Dict[str, Impl] = dataclasses.field(default_factory=dict)
    #: shape tuple canonically identifying a call (for the autotune cache
    #: key), derived from real arguments at dispatch time.
    shape_of: Optional[Callable[..., Tuple[int, ...]]] = None
    #: (shape, dtype=float32) -> (args, kwargs): random representative inputs.
    #: Shared by autotune and the registry parity tests.
    make_inputs: Optional[Callable] = None
    #: (backend, shape) -> [kwargs, ...] candidate tunable settings.
    candidates: Optional[Callable] = None
    #: (backend, shape) -> [kwargs, ...] candidate backward-tunable settings
    #: (consumed by ``autotune(grad=True)``).
    bwd_candidates: Optional[Callable] = None

    def backends(self) -> List[str]:
        return [b for b in BACKENDS if b in self.impls]


_OPS: Dict[str, Op] = {}
_loaded = False
_load_lock = threading.Lock()

_tls = threading.local()            # .stack: list[str], .grad_depth: int
_process_backend: Optional[str] = None
_warned_fallbacks: set = set()      # (op, requested backend) already warned


# --------------------------------------------------------------------------
# registration
# --------------------------------------------------------------------------

def _canon(name: str) -> str:
    low = str(name).lower()
    low = _ALIASES.get(low, low)
    if low not in BACKENDS and low != "auto":
        raise ValueError(
            f"unknown backend {name!r}; expected one of {BACKENDS + ('auto',)}"
            f" or aliases {tuple(_ALIASES)}")
    return low


def _op(name: str) -> Op:
    return _OPS.setdefault(name, Op(name))


def custom_vjp_fn(fn: Callable, fwd: Callable, bwd: Callable) -> Callable:
    """Wrap ``fn`` with :func:`jax.custom_vjp`, binding kwargs as static
    configuration.

    ``jax.custom_vjp`` does not accept keyword arguments, but registry ops
    take their differentiable operands positionally and their configuration
    (block sizes, flags) as keywords. This helper partials ``fn``/``fwd``/
    ``bwd`` over each call's kwargs (one wrapper per distinct hashable kwargs
    combination, cached) so only the positional args are primals.

    Conventions: ``fwd(*args, **kw) -> (out, residuals)``;
    ``bwd(residuals, cotangent, **kw) -> tuple`` of one cotangent per
    positional arg. Static integers that steer trace-time control flow (e.g.
    ``prox_loop``'s ``Q``) must be passed as kwargs by differentiated call
    sites, or they become traced primals.
    """
    cache: Dict[Any, Callable] = {}

    @functools.wraps(fn)
    def call(*args: Any, **kwargs: Any):
        try:
            key = tuple(sorted(kwargs.items()))
            wrapped = cache.get(key)
        except TypeError:                      # unhashable kwarg: no caching
            key, wrapped = None, None
        if wrapped is None:
            wrapped = jax.custom_vjp(functools.partial(fn, **kwargs))
            wrapped.defvjp(functools.partial(fwd, **kwargs),
                           functools.partial(bwd, **kwargs))
            if key is not None:
                cache[key] = wrapped
        return wrapped(*args)
    return call


def register(op_name: str, backend: str, *, available: Callable[[], bool] = _always_true,
             supports: Callable[..., bool] = _always_true,
             differentiable: bool = True, tunables: Sequence[str] = (),
             vjp: Optional[Tuple[Callable, Callable]] = None,
             bwd_tunables: Sequence[str] = ()):
    """Decorator: register ``fn`` as ``op_name``'s ``backend`` implementation.

    All impls of one op must share a call signature (each accepts the union
    of kwargs and ignores what it does not use) so call sites are
    backend-oblivious. A ``vjp=(fwd, bwd)`` pair makes the impl
    differentiable: dispatch runs the :func:`custom_vjp_fn`-wrapped function,
    so ``jax.grad`` traces ``bwd`` instead of the impl's internals.
    """
    backend = _canon(backend)
    if vjp is not None and not differentiable:
        raise ValueError(f"{op_name}/{backend}: a vjp pair implies "
                         "differentiable=True")

    def deco(fn: Callable) -> Callable:
        dispatch_fn = custom_vjp_fn(fn, *vjp) if vjp is not None else fn
        _op(op_name).impls[backend] = Impl(
            backend=backend, fn=dispatch_fn, available=available,
            supports=supports, differentiable=differentiable,
            tunables=tuple(tunables), vjp=vjp,
            bwd_tunables=tuple(bwd_tunables))
        return fn
    return deco


def describe(op_name: str, *, shape_of: Optional[Callable] = None,
             make_inputs: Optional[Callable] = None,
             candidates: Optional[Callable] = None,
             bwd_candidates: Optional[Callable] = None) -> None:
    """Attach autotune/test metadata to an op (see :class:`Op`)."""
    op = _op(op_name)
    op.shape_of = shape_of or op.shape_of
    op.make_inputs = make_inputs or op.make_inputs
    op.candidates = candidates or op.candidates
    op.bwd_candidates = bwd_candidates or op.bwd_candidates


def _ensure_loaded() -> None:
    global _loaded
    if _loaded:
        return
    with _load_lock:
        if _loaded:
            return
        for mod in _IMPL_MODULES:
            importlib.import_module(mod)
        _loaded = True


def ops() -> List[str]:
    """Sorted names of every registered op."""
    _ensure_loaded()
    return sorted(_OPS)


def get_op(name: str) -> Op:
    _ensure_loaded()
    if name not in _OPS:
        raise KeyError(f"unknown op {name!r}; registered: {sorted(_OPS)}")
    return _OPS[name]


def backends_of(name: str) -> List[str]:
    """Backends with a registered impl for ``name``, canonical order."""
    return get_op(name).backends()


# --------------------------------------------------------------------------
# backend policy
# --------------------------------------------------------------------------

def _stack() -> List[str]:
    if not hasattr(_tls, "stack"):
        _tls.stack = []
    return _tls.stack


def set_backend(name: Optional[str]) -> None:
    """Set (or with ``None`` clear) the process-wide backend policy.

    Overrides ``REPRO_BACKEND``; overridden by ``with use(...)`` contexts.
    Only affects traces that happen after the call — already-jitted
    executables keep the backend they were traced with.
    """
    global _process_backend
    _process_backend = _canon(name) if name is not None else None


def policy() -> str:
    """The active policy name, possibly ``"auto"`` (not yet resolved)."""
    stack = _stack()
    if stack:
        return stack[-1]
    if _process_backend is not None:
        return _process_backend
    env = os.environ.get("REPRO_BACKEND", "").strip()
    if env:
        return _canon(env)
    return "auto"


def resolved_backend() -> str:
    """The concrete backend the active policy selects on this process."""
    p = policy()
    if p == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return p


@contextlib.contextmanager
def use(backend: Optional[str]):
    """Scoped backend override: ``with registry.use("pallas"): ...``.

    Beats :func:`set_backend` and ``REPRO_BACKEND`` while active; restores the
    previous policy on exit (also on exception). ``use(None)`` is a no-op
    pass-through so deprecated-kwarg shims can forward unconditionally.
    """
    if backend is None:
        yield
        return
    stack = _stack()
    stack.append(_canon(backend))
    try:
        yield
    finally:
        stack.pop()


@contextlib.contextmanager
def grad_safe():
    """Scope in which dispatch skips impls without a VJP (``differentiable=
    False``). Entered by loss functions as a narrow per-impl guard: impls
    registered with a ``vjp`` pair (all the stock Pallas kernels) pass
    through and their backward kernels are traced; only the rare VJP-less
    impl is routed to its ``xla`` fallback."""
    _tls.grad_depth = getattr(_tls, "grad_depth", 0) + 1
    try:
        yield
    finally:
        _tls.grad_depth -= 1


def _in_grad_safe() -> bool:
    return getattr(_tls, "grad_depth", 0) > 0


# --------------------------------------------------------------------------
# dispatch
# --------------------------------------------------------------------------

def _usable(impl: Optional[Impl], args, kwargs) -> bool:
    return (impl is not None and impl.available()
            and (impl.differentiable or not _in_grad_safe())
            and impl.supports(*args, **kwargs))


def select(name: str, *args: Any, **kwargs: Any) -> Impl:
    """The impl :func:`dispatch` would run for this call under the active
    policy (requested backend, else the ``xla`` fallback)."""
    op = get_op(name)
    backend = resolved_backend()
    impl = op.impls.get(backend)
    if _usable(impl, args, kwargs):
        return impl
    fallback = op.impls.get("xla")
    if backend != "xla" and _usable(fallback, args, kwargs):
        _M_FALLBACK.inc(op=name, requested=backend)
        if (name, backend) not in _warned_fallbacks:
            _warned_fallbacks.add((name, backend))
            warnings.warn(f"op {name!r}: the {backend} impl cannot serve "
                          "this call; running its xla impl", stacklevel=3)
        return fallback
    raise NotImplementedError(
        f"op {name!r}: no usable implementation (policy={policy()!r}, "
        f"registered={op.backends()}, grad_safe={_in_grad_safe()})")


def dispatch(name: str, *args: Any, **kwargs: Any) -> Any:
    """Run op ``name`` under the active backend policy.

    Tunable kwargs the caller passed as ``None`` (or omitted) are filled from
    the autotune cache when an entry matches this op/backend/shape/device.
    """
    op = get_op(name)
    impl = select(name, *args, **kwargs)
    _M_DISPATCH.inc(op=name, backend=impl.backend)
    if op.shape_of is not None:
        for tunables, suffix in ((impl.tunables, ""),
                                 (impl.bwd_tunables, BWD_KEY_SUFFIX)):
            if not tunables:
                continue
            entry = _tuned_entry(op, impl, args, kwargs, suffix=suffix)
            if entry:
                kwargs = dict(kwargs)
                for key in tunables:
                    if kwargs.get(key) is None and key in entry["params"]:
                        kwargs[key] = entry["params"][key]
    return impl.fn(*args, **kwargs)


# --------------------------------------------------------------------------
# autotune cache
# --------------------------------------------------------------------------

_TUNED: Optional[Dict[str, dict]] = None
_DEVICE_KIND: Optional[str] = None

#: cache-key op suffix for backward-pass tunables ("flash_attention+bwd|...")
BWD_KEY_SUFFIX = "+bwd"
#: device-kind placeholder while the backend is uninitialized; entries keyed
#: by it are process-local only (never persisted)
UNKNOWN_DEVICE = "unknown"
#: entry schema version. Bumped when the meaning of ``params`` changes for
#: any op (e.g. a renamed tunable); entries written under another version
#: are *stale*, not misses — dispatch skips them instead of feeding an old
#: schema's params to a new impl, and the lookup counter reports them as
#: ``outcome="stale"`` so a cache wiped by a schema bump is distinguishable
#: from one that was never tuned. Version 2 added the ``schema_version`` and
#: ``device`` fields themselves, so v1 entries are exactly the field-less
#: legacy ones.
SCHEMA_VERSION = 2


def cache_path() -> str:
    """Autotune cache location (``$REPRO_AUTOTUNE_CACHE`` overrides)."""
    return os.environ.get("REPRO_AUTOTUNE_CACHE") or os.path.join(
        os.path.expanduser("~"), ".cache", "repro", "autotune.json")


def _device_kind() -> str:
    """The device kind, resolved lazily at every lookup and memoized only
    once real (an early failed probe must not bake ``unknown`` into keys
    used for the rest of the process)."""
    global _DEVICE_KIND
    if _DEVICE_KIND is None:
        try:
            _DEVICE_KIND = jax.devices()[0].device_kind.replace(" ", "_").lower()
        except Exception:                               # uninitialized backend
            return UNKNOWN_DEVICE
    return _DEVICE_KIND


def _is_persistable(key: str) -> bool:
    return not key.endswith(f"|{UNKNOWN_DEVICE}")


def _cache_key(op_name: str, backend: str, shape: Tuple[int, ...]) -> str:
    return f"{op_name}|{backend}|{'x'.join(map(str, shape))}|{_device_kind()}"


def _read_cache_file(path: str) -> Dict[str, dict]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        return {}
    except (OSError, json.JSONDecodeError) as e:
        warnings.warn(f"ignoring unreadable autotune cache {path}: {e}")
        return {}


def _tuned() -> Dict[str, dict]:
    global _TUNED
    if _TUNED is None:
        # legacy unknown-device entries can never match a lazily-resolved
        # lookup key honestly, so drop them on load
        _TUNED = {k: v for k, v in _read_cache_file(cache_path()).items()
                  if _is_persistable(k)}
    return _TUNED


def reload_tuned() -> None:
    """Drop the in-memory autotune table; next dispatch re-reads the file."""
    global _TUNED
    _TUNED = None


def _tuned_entry(op: Op, impl: Impl, args, kwargs,
                 suffix: str = "") -> Optional[dict]:
    table = _tuned()
    if not table:
        return None
    try:
        shape = tuple(op.shape_of(*args, **kwargs))
    except Exception:
        return None
    entry = table.get(_cache_key(op.name + suffix, impl.backend, shape))
    if entry is None:
        _M_TUNE_LOOKUP.inc(op=op.name, outcome="miss")
        return None
    if entry.get("schema_version") != SCHEMA_VERSION:
        # written under another schema: its params may not mean what this
        # impl's tunables mean, so skip it — but report "stale", not "miss"
        _M_TUNE_LOOKUP.inc(op=op.name, outcome="stale")
        return None
    _M_TUNE_LOOKUP.inc(op=op.name, outcome="hit")
    return entry


def _save_cache(path: str, fresh: Dict[str, dict]) -> None:
    """Persist the in-memory table, merging concurrent writers' entries.

    The write is read-merge-replace under a per-pid tmp file: the on-disk
    file is re-read immediately before the atomic replace so two processes
    tuning concurrently (the CI matrix) union their entries instead of
    clobbering each other. Only the on-disk table and this call's ``fresh``
    entries are written (fresh wins on conflict): a concurrent writer's
    newer result for a key we merely *loaded* is not reverted by our stale
    in-memory copy, and entries from earlier ``save=False`` calls stay
    process-local. Unknown-device keys stay in memory only.
    """
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    persistable = lambda d: {k: v for k, v in d.items() if _is_persistable(k)}
    merged = {**persistable(_read_cache_file(path)), **persistable(fresh)}
    _tuned().update(merged)      # adopt the merge outcome in memory too
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(merged, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def _time_call(fn: Callable, args, kwargs, iters: int, warmup: int) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kwargs))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        best = min(best, time.perf_counter() - t0)
    return best


def _sum_leaves(out: Any) -> jax.Array:
    return sum(jnp.sum(jnp.asarray(leaf).astype(jnp.float32))
               for leaf in jax.tree.leaves(out))


def grad_argnums(args: Sequence[Any]) -> Tuple[int, ...]:
    """Positions of the floating-point array args — the argnums a grad-mode
    timing must differentiate. Differentiating only arg 0 would let jit
    dead-code-eliminate whole backward kernels (e.g. flash attention's dkv)
    and rank candidates on a fraction of the real backward."""
    return tuple(i for i, a in enumerate(args)
                 if hasattr(a, "dtype") and jnp.issubdtype(a.dtype,
                                                           jnp.floating))


def autotune(op_name: str, shapes: Iterable[Sequence[int]], *,
             backends: Optional[Sequence[str]] = None, iters: int = 3,
             warmup: int = 1, save: bool = True,
             grad: bool = False) -> Dict[str, dict]:
    """Time each registered block-size candidate of ``op_name`` over
    ``shapes`` and persist the winners.

    ``grad=False`` sweeps the impl's forward ``tunables``; ``grad=True``
    times a ``jax.grad`` through the impl instead, sweeps its
    ``bwd_tunables`` (backward block sizes), and stores winners under the
    separate ``<op>+bwd`` cache keys.

    Returns the new cache entries ``{key: {"params": ..., "us": ...}}``; the
    same entries are merged into the on-disk JSON cache (see
    :func:`cache_path`) that :func:`dispatch` consults. Candidates that fail
    to execute (e.g. a block size invalid for the shape) are skipped.
    """
    op = get_op(op_name)
    if op.make_inputs is None:
        raise ValueError(f"op {op_name!r} has no autotune metadata "
                         "(registry.describe(make_inputs=...))")
    wanted = [_canon(b) for b in backends] if backends else op.backends()
    key_op = op_name + (BWD_KEY_SUFFIX if grad else "")
    results: Dict[str, dict] = {}
    for shape in shapes:
        shape = tuple(int(s) for s in shape)
        args, base_kw = op.make_inputs(shape)
        # key by the canonical dispatch-time shape, which may differ from the
        # make_inputs descriptor (e.g. prox ops describe (d,) but key (d, d))
        key_shape = tuple(op.shape_of(*args, **base_kw)) if op.shape_of \
            else shape
        for bname in wanted:
            impl = op.impls.get(bname)
            if not _usable(impl, args, base_kw):
                continue
            tunables = impl.bwd_tunables if grad else impl.tunables
            if not tunables or (grad and not impl.differentiable):
                continue
            cand_fn = op.bwd_candidates if grad else op.candidates
            cands = cand_fn(bname, shape) if cand_fn else [{}]
            best: Optional[Tuple[float, dict]] = None
            for cand in cands or [{}]:
                kw = {**base_kw,
                      **{k: v for k, v in cand.items() if k in tunables}}
                try:
                    # time the compiled call: tunables are keyword-bound so
                    # they stay static (some feed static args of inner jits),
                    # and eager-mode Python overhead doesn't skew the ranking
                    target = functools.partial(impl.fn, **kw)
                    if grad:
                        fn = jax.jit(jax.grad(
                            lambda *a: _sum_leaves(target(*a)),
                            argnums=grad_argnums(args)))
                    else:
                        fn = jax.jit(target)
                    t = _time_call(fn, args, {}, iters, warmup)
                except Exception:
                    continue
                if best is None or t < best[0]:
                    best = (t, dict(cand))
            if best is not None:
                key = _cache_key(key_op, bname, key_shape)
                entry = dict(params=best[1], us=round(best[0] * 1e6, 2),
                             schema_version=SCHEMA_VERSION,
                             device=_device_kind())
                _tuned()[key] = entry
                results[key] = entry
    if save and any(_is_persistable(k) for k in results):
        _save_cache(cache_path(), results)
    return results
