"""Convergence metrics: the paper's relative solution error (§V-A).

rel_err(w) = ||w - w_opt|| / ||w_opt||, with w_opt from a high-accuracy
deterministic full-batch run (standing in for TFOCS at tol 1e-8, which is
not available offline). ``composite_reference`` is the generic oracle: plain
FISTA on the problem's ``full_stats()`` with its own ``prox_params()``
element-wise prox — for LASSO this is arithmetically the historical
``fista_reference``; for the dual SVM (box prox) it is projected FISTA."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.soft_threshold import fista_momentum, prox_elem


@partial(jax.jit, static_argnames=("iters",))
def composite_reference(problem, iters: int = 4000, step_size=None):
    """Deterministic full-batch FISTA on any composite problem (b=1, no
    sampling): the oracle every stochastic solver is scored against."""
    G, R = problem.full_stats()
    variant, lam, mu, lo, hi = problem.prox_params()
    if step_size is None:
        # 1/(1.05 * eigmax(G)) by power iteration — mirrors
        # problem.lipschitz_step's arithmetic on the full-batch Gram
        v = jax.random.normal(jax.random.PRNGKey(0), (G.shape[0],),
                              dtype=G.dtype)

        def body(_, v):
            v = G @ v
            return v / jnp.linalg.norm(v)

        v = jax.lax.fori_loop(0, 100, body, v / jnp.linalg.norm(v))
        t = 1.0 / (1.05 * jnp.vdot(v, G @ v))
    else:
        t = jnp.asarray(step_size, G.dtype)

    def step(state, j):
        w_prev, w = state
        mom = fista_momentum(j)
        z = w + mom * (w - w_prev)
        w_new = prox_elem(z - t * (G @ z - R), t, variant=variant, lam=lam,
                          mu=mu, lo=lo, hi=hi)
        return (w, w_new), None

    z0 = jnp.zeros((G.shape[0],), G.dtype)
    (_, w), _ = jax.lax.scan(step, (z0, z0), jnp.arange(1, iters + 1))
    return w


def solve_reference(problem, iters: int = 4000):
    """High-accuracy solution w_opt (the TFOCS stand-in)."""
    return composite_reference(problem, iters=iters)


def lasso_reference_f64(problem, iters: int = 4000) -> np.ndarray:
    """w_opt of a LASSO problem by full-batch FISTA in float64 NumPy on the
    host: independent of the solvers' code and of the device's matmul
    precision (a TPU multiplies f32 in bf16 passes unless told otherwise)."""
    X = np.asarray(problem.X, np.float64)
    y = np.asarray(problem.y, np.float64)
    G, R = X @ X.T / X.shape[1], X @ y / X.shape[1]
    t = 1.0 / np.linalg.eigvalsh(G)[-1]
    w_prev = w = np.zeros(G.shape[0])
    for j in range(1, iters + 1):
        z = w + max((j - 2.0) / j, 0.0) * (w - w_prev)
        u = z - t * (G @ z - R)
        w_prev, w = w, np.sign(u) * np.maximum(np.abs(u) - problem.lam * t,
                                               0.0)
    return w


def relative_solution_error(w, w_opt):
    return jnp.linalg.norm(w - w_opt) / jnp.maximum(jnp.linalg.norm(w_opt), 1e-30)


def objective_history(problem, history):
    """F(w_j) for a (T, dim) iterate history (vectorized)."""
    return jax.vmap(problem.objective)(history)
