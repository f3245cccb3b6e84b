"""Plain reference for the LASSO cells, independent of the program's code.

- :func:`make_data`: the covtype-shaped problem from the seed, in one jitted
  call on the device (X (d, n) with unit-variance columns, a sparse w*,
  y = X^T w* + noise, the recipe of ``repro.data.make_lasso_data``), with y
  scaled so that lambda_max = ||X y / n||_inf is the configuration's
  ``lam_max`` on every seed: lambda is then one number, as the program
  compiles it into the solver.
- :func:`replay`: stochastic FISTA with sampled Gram matrices, written out
  from the paper's Algorithm I: the same T draws of m = floor(b n) columns
  (``randint`` from ``split(key, T)``; on P shards each shard draws from its
  own n/P columns with ``fold_in(key, shard)``), step 1/(1.05 L) with L from
  50 power iterations on X X^T / n, and the gradient at the extrapolated
  point, in float32 at the highest matmul precision. ``quant`` rounds every
  matmul operand first: ``"bf16"`` as one bfloat16 pass does (the TPU's
  default precision, which the program runs at), ``"int8"`` with a
  symmetric absmax scale per row (the control, one step below).
"""
from __future__ import annotations

from functools import partial

import jax
import numpy as np


def make_data(seed: int, d: int, n: int, cfg: dict):
    import jax.numpy as jnp

    @partial(jax.jit, static_argnums=(1, 2))
    def gen(key, d, n):
        kx, kw, kn, km = jax.random.split(key, 4)
        X = jax.random.normal(kx, (d, n), jnp.float32) / np.sqrt(d)
        w = jax.random.normal(kw, (d,), jnp.float32)
        w = jnp.where(jax.random.bernoulli(km, cfg["sparsity"], (d,)), w, 0.)
        y = X.T @ w + cfg["noise"] * jax.random.normal(kn, (n,), jnp.float32)
        y = y * (cfg["lam_max"] / jnp.max(jnp.abs(X @ y / n)))
        return X, y

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) + 1)
    return gen(key, d, n)


def step_size(X, iters: int = 50) -> float:
    """1/(1.05 L), L from ``iters`` power iterations on X X^T / n from a
    fixed start (normal draws of ``PRNGKey(0)``), in float64: the solvers'
    stated step rule. The Gram's top eigenvalues of this data lie within
    about 2 % of each other, so the iteration stops short of the top one,
    and the exact eigenvalue would give another step."""
    Xh = np.asarray(X, np.float64)
    G = Xh @ Xh.T / Xh.shape[1]
    v = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (G.shape[0],)),
                   np.float64)
    v /= np.linalg.norm(v)
    for _ in range(iters):
        v = G @ v
        v /= np.linalg.norm(v)
    return 1.0 / (1.05 * (v @ G @ v))


def draws(key, T: int, n: int, b: float, shards: int = 1):
    """(T, m) column indices of every iteration, over all shards."""
    import jax.numpy as jnp
    n_loc = n // shards
    m_loc = max(int(b * n_loc), 1)

    def one(s):
        ks = key if shards == 1 else jax.random.fold_in(key, s)
        keys = jax.random.split(ks, T)
        idx = jax.vmap(lambda kk: jax.random.randint(kk, (m_loc,), 0,
                                                     n_loc))(keys)
        return idx + s * n_loc
    return jnp.concatenate([one(s) for s in range(shards)], axis=1)


def q8(x, axis=-1):
    """Round to int8 with a symmetric absmax scale along ``axis``."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def bf16(x, axis=-1):
    """Round to bfloat16: what one bfloat16 pass of an f32 matmul does to
    its operands (the TPU's default precision)."""
    import jax.numpy as jnp
    return x.astype(jnp.bfloat16).astype(jnp.float32)


ROUND = {None: lambda a, axis=-1: a, "bf16": bf16, "int8": q8}


@partial(jax.jit, static_argnames=("quant",))
def _fista(X, y, idx, t, lam, *, quant):
    import jax.numpy as jnp
    m = idx.shape[1]
    r = ROUND[quant]

    def dot(a, b):
        return jnp.matmul(r(a), r(b, 0) if b.ndim == 2 else r(b),
                          precision="highest")

    def body(carry, xs):
        w_prev, w = carry
        j, ix = xs
        jf = j.astype(jnp.float32)
        mom = jnp.maximum((jf - 2.0) / jf, 0.0)
        v = w + mom * (w - w_prev)
        Xs, ys = X[:, ix], y[ix]
        G = dot(Xs, Xs.T) / m
        R = dot(Xs, ys) / m
        u = v - t * (dot(G, v) - R)
        w_new = jnp.sign(u) * jnp.maximum(jnp.abs(u) - lam * t, 0)
        return (w, w_new), None

    w0 = jnp.zeros((X.shape[0],), jnp.float32)
    T = idx.shape[0]
    (_, w), _ = jax.lax.scan(body, (w0, w0),
                             (jnp.arange(1, T + 1, dtype=jnp.int32), idx))
    return w


def replay(X, y, lam: float, key, *, T: int, b: float, shards: int = 1,
           quant=None) -> np.ndarray:
    """The stochastic solve of ``key``, by the reference (``quant="int8"``:
    the control)."""
    idx = draws(key, T, X.shape[1], b, shards)
    w = _fista(X, y, idx, step_size(X), lam, quant=quant)
    return np.asarray(w, np.float64)


def rel_gap(w, w_ref) -> float:
    w, w_ref = np.asarray(w, np.float64), np.asarray(w_ref, np.float64)
    return float(np.linalg.norm(w - w_ref) / max(np.linalg.norm(w_ref),
                                                 1e-30))
