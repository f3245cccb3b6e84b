"""Public jit'd wrapper for the held-experts kernel: lays the (token, held
expert) pairs out by expert, dispatches Pallas (interpret on CPU, compiled
on TPU), returns each token's gate-weighted sum over its held experts.

Registers the ``held_experts_ffn`` op: ``pallas`` is the grouped kernel,
``xla`` the pure-jnp oracle (every held expert over every token, weighted
by zero where the token did not choose it). Both drop no token. Neither
carries a VJP: the training path keeps its capacity formulation
(``models/moe.py``)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import pad, registry
from repro.kernels.moe import kernel as _k
from repro.kernels.moe import ref as _ref

#: VMEM the kernel may take (the v5e's scoped default is 16 MiB)
VMEM_BUDGET = 12 * 1024 * 1024


def rows_bound(T: int, k: int, n_held: int, tm: int = _k.TM) -> int:
    """Rows of the pair layout: every held pair (a token chooses an expert
    at most once), each expert's group padded to whole tiles."""
    return pad.round_up(T * min(k, n_held) + n_held * (tm - 1), tm)


def layout(sel, *, first_expert: int, n_held: int, tm: int = _k.TM):
    """The pairs sorted by held expert: for each of the T * k pairs its row
    (``rows_bound`` where the expert is not held: dropped on scatter), and
    the kernel's ``order``, ``tiles`` and ``start`` (see ``kernel.py``)."""
    T, k = sel.shape
    local = (sel - first_expert).reshape(T * k)
    oh = (local[:, None] == jnp.arange(n_held)).astype(jnp.int32)  # (P, E)
    held = oh.sum(1) > 0
    counts = oh.sum(0)
    rank = ((jnp.cumsum(oh, 0) - oh) * oh).sum(1)      # place in its group
    padded = -(-counts // tm) * tm                     # whole tiles
    starts = jnp.cumsum(padded) - padded
    row = jnp.where(held, (starts * oh).sum(1) + rank,
                    rows_bound(T, k, n_held, tm))
    hit = counts > 0
    n_hit = hit.sum()
    order = jnp.argsort(~hit, stable=True)             # hit experts first
    visit = jnp.arange(n_held) < n_hit
    order = jnp.where(visit, order, order[jnp.maximum(n_hit - 1, 0)])
    tiles = jnp.where(visit, padded[order] // tm, 0)
    return (order.astype(jnp.int32), tiles.astype(jnp.int32),
            starts[order].astype(jnp.int32), row)


def _vmem_bytes(T: int, k: int, d: int, F: int, E: int, itemsize: int):
    M, Tp = rows_bound(T, k, E), pad.round_up(T, 8)
    bf = _k.feature_block(d, F, itemsize)
    return (2 * (M * d * itemsize + Tp * M * 4 + Tp * d * 4)
            + 2 * 3 * d * bf * itemsize + M * d * 4)


@functools.partial(jax.jit, static_argnames=("first_expert", "interpret"))
def held_experts_ffn(x, sel, gates, w_gate, w_up, w_down, layer, *,
                     first_expert: int = 0, interpret: bool | None = None):
    """x (T, d); sel (T, k) int32 expert ids over all experts; gates (T, k)
    their weights; w_gate, w_up (L, E, d, F), w_down (L, E, F, d) the held
    experts ``first_expert .. first_expert + E - 1`` of every layer, of
    which layer ``layer`` (int32 scalar) is used, read in place. Returns
    (T, d) float32: each token's sum over its held experts of gate x
    SwiGLU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    T, d = x.shape
    k = sel.shape[1]
    E, _, F = w_gate.shape[1:]
    wdt = w_gate.dtype
    order, tiles, start, row = layout(sel, first_expert=first_expert,
                                      n_held=E)
    M, Tp = rows_bound(T, k, E), pad.round_up(T, 8)
    tok = jnp.repeat(jnp.arange(T), k)
    xs = jnp.zeros((M, d), wdt).at[row].set(x[tok].astype(wdt), mode="drop")
    comb = jnp.zeros((Tp, M), jnp.float32).at[tok, row].set(
        gates.reshape(T * k).astype(jnp.float32), mode="drop")
    out = _k.held_experts_ffn(jnp.reshape(layer, (1,)).astype(jnp.int32),
                              order, tiles, start, xs, comb, w_gate,
                              w_up.astype(wdt), w_down.astype(wdt),
                              tm=_k.TM, bf=_k.feature_block(
                                  d, F, jnp.dtype(wdt).itemsize),
                              interpret=interpret)
    return out[:T]


def _held_xla(x, sel, gates, w_gate, w_up, w_down, layer, *,
              first_expert=0, interpret=None):
    del interpret                                   # pallas-only
    wdt = w_gate.dtype
    wg, wu, wd = (jax.lax.dynamic_index_in_dim(w, layer, 0, False)
                  for w in (w_gate, w_up, w_down))
    return _ref.held_experts_ffn(x.astype(wdt), sel, gates, wg,
                                 wu.astype(wdt), wd.astype(wdt),
                                 first_expert=first_expert)


def _supports(x, sel, gates, w_gate, *a, **kw):
    E, d, F = w_gate.shape[1:]
    return _vmem_bytes(x.shape[0], sel.shape[1], d, F, E,
                       jnp.dtype(w_gate.dtype).itemsize) <= VMEM_BUDGET


# ------------------------------------------------------------ registry ----

def _make_inputs(shape, dtype=jnp.float32):
    """shape = (T, d, F, n_experts, n_held, k, first_expert); two layers,
    the second used."""
    T, d, F, n_exp, E, k, first = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (T, d), dtype)
    gates, sel = jax.lax.top_k(jax.nn.softmax(
        jax.random.normal(ks[1], (T, n_exp)), -1), k)
    w = lambda key, s, fan: (jax.random.normal(key, (2, *s)) * fan ** -.5
                             ).astype(dtype)
    args = (x, sel.astype(jnp.int32), gates, w(ks[2], (E, d, F), d),
            w(ks[3], (E, d, F), d), w(ks[4], (E, F, d), F), jnp.int32(1))
    return args, dict(first_expert=first)


registry.describe(
    "held_experts_ffn",
    shape_of=lambda x, sel, gates, wg, *a, **kw: (
        x.shape[0], sel.shape[1], *wg.shape[1:]),
    make_inputs=_make_inputs)
registry.register("held_experts_ffn", "pallas", supports=_supports,
                  differentiable=False)(held_experts_ffn)
registry.register("held_experts_ffn", "xla")(_held_xla)
