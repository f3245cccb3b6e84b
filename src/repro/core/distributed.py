"""Distributed solvers via shard_map (paper Algorithm V).

Data layout (paper §III): X (d, n) is partitioned column-wise over the ``data``
mesh axis (each processor holds n/P samples, matching the "same number of
nonzeros" assumption for dense data); y likewise; the iterates w, v are
replicated. For the gram-schedule solvers each shard samples from *its own*
columns (paper §IV-B: "randomly selecting b.n different subset of the columns
by each processor"); for BCD the coordinate draws are SHARED across shards
(coordinates of the replicated iterate are not data-parallel — folding the
shard index into the key would make shards update different coordinates and
diverge).

The only cross-device communication is the psum of the local statistics:
  - classical gram: one psum of (d^2 + d) words  per iteration      -> T collectives
  - CA gram:        one psum of k*(d^2 + d) words per k iterations  -> T/k collectives
  - classical BCD:  one psum of (m_c^2 + m_c) words per iteration   -> T collectives
  - CA BCD:         one psum of ((k m_c)^2 + k m_c) per k iterations-> T/k collectives
Bandwidth (words moved) and flops are unchanged for the gram family — exactly
Table I of the paper; CA-BCD trades a factor-k word inflation of its (small)
cross-Gram for the factor-k message reduction (1612.04003 §3). The reduction
in collective *count* is asserted structurally from the compiled HLO in
tests/test_hlo_collectives.py.

All distributed solvers run the LASSO/l1 framing of the problem (the module's
(X, y, lam) API); the dual SVM is not data-parallel in this layout — its
iterate lives on the sample axis — and is intentionally unsupported here.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.problem import SolverConfig
from repro.core.sampling import sample_index_batch
from repro.core.gram import sampled_gram, gram_blocks
from repro.core.soft_threshold import prox_elem
from repro.core.update_rules import (init_state, init_pdhg_state,
                                     fista_update, pnm_update, pdhg_update)
from repro.kernels import registry

GRAM_ALGORITHMS = ("sfista", "spnm", "pdhg", "ca_sfista", "ca_spnm",
                   "ca_pdhg")
COORD_ALGORITHMS = ("bcd", "ca_bcd")
ALGORITHMS = GRAM_ALGORITHMS + COORD_ALGORITHMS


def _gram_local_solver(algorithm: str, cfg: SolverConfig, lam: float,
                       data_axes: tuple):
    """Per-shard body for the gram-schedule family (fista/pnm/pdhg)."""
    ca = algorithm.startswith("ca_")
    rule = algorithm.removeprefix("ca_")

    def update(G, R, state, t):
        if rule == "spnm":
            return pnm_update(G, R, state, t, lam, cfg.Q)
        if rule == "pdhg":
            sigma = (jnp.asarray(cfg.sigma, t.dtype)
                     if cfg.sigma is not None else 0.5 / t)
            return pdhg_update(G, R, state, t, sigma, lam)
        return fista_update(G, R, state, t, lam)

    init = init_pdhg_state if rule == "pdhg" else init_state

    def solve_local(X_local, y_local, w0, t, key):
        d, n_local = X_local.shape
        m_local = max(int(cfg.b * n_local), 1)
        # Per-shard independent draws: fold the shard's linear index into key.
        idx_lin = jnp.int32(0)
        for ax in data_axes:
            idx_lin = idx_lin * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        key = jax.random.fold_in(key, idx_lin)
        n_shards = 1
        for ax in data_axes:
            n_shards *= jax.lax.axis_size(ax)
        m_global = m_local * n_shards  # union of per-shard draws
        idx = sample_index_batch(key, cfg.T, n_local, m_local,
                                 cfg.with_replacement)

        if ca:
            idx = idx.reshape(cfg.T // cfg.k, cfg.k, m_local)

            def outer(state, idx_block):
                Gl, Rl = gram_blocks(X_local, y_local, idx_block, m_norm=m_global)
                # THE collective: one psum of k*(d^2+d) words per k iterations.
                G = jax.lax.psum(Gl, data_axes)
                R = jax.lax.psum(Rl, data_axes)

                def inner(st, gr):
                    return update(gr[0], gr[1], st, t), None

                state, _ = jax.lax.scan(inner, state, (G, R))
                return state, None

            state, _ = jax.lax.scan(outer, init(w0), idx)
        else:
            def step(state, idx_j):
                Gl, Rl = sampled_gram(X_local, y_local, idx_j, m_norm=m_global)
                # classical: psum of (d^2+d) words EVERY iteration.
                G = jax.lax.psum(Gl, data_axes)
                R = jax.lax.psum(Rl, data_axes)
                return update(G, R, state, t), None

            state, _ = jax.lax.scan(step, init(w0), idx)
        return state.w

    return solve_local


def _coord_local_solver(algorithm: str, cfg: SolverConfig, lam: float,
                        data_axes: tuple):
    """Per-shard body for (CA-)BCD: coordinates replicated, residual sharded.

    v = X^T w - y lives on the data axis, so v_local = X_local^T w - y_local
    is purely local; the per-block cross-Gram C = (1/n) X[U] X[U]^T and block
    gradient g0 = (1/n) X[U] v reduce over it — the one psum per outer block.
    The inner coordinate updates then replay with no communication, exactly
    as in ``sstep._coord_block``.
    """
    blk = cfg.k if algorithm.startswith("ca_") else 1

    def solve_local(X_local, y_local, w0, t, key):
        d, n_local = X_local.shape
        n_shards = 1
        for ax in data_axes:
            n_shards *= jax.lax.axis_size(ax)
        inv_rho = 1.0 / (n_local * n_shards)
        m_c = max(int(cfg.b * d), 1)
        # SHARED draws: every shard must update the same coordinates, so the
        # key is NOT folded with the shard index (contrast the gram family).
        idx = sample_index_batch(key, cfg.T, d, m_c, False)
        idx = idx.reshape(cfg.T // blk, blk, m_c)
        v0 = X_local.T @ w0 - y_local

        def outer(carry, idx_block):
            w, v = carry
            U = idx_block.reshape(-1)
            BU = jnp.take(X_local, U, axis=0)          # (blk*m_c, n_local)
            Cl = registry.dispatch("gram", BU) * inv_rho
            gl = (BU @ v) * inv_rho
            # THE collective: one psum of ((blk*m_c)^2 + blk*m_c) words.
            C = jax.lax.psum(Cl, data_axes)
            g0 = jax.lax.psum(gl, data_axes)

            def inner(carry, jj):
                w, delta = carry
                start = jj * m_c
                Uj = jax.lax.dynamic_slice_in_dim(U, start, m_c)
                Cj = jax.lax.dynamic_slice_in_dim(C, start, m_c, axis=0)
                gj = jax.lax.dynamic_slice_in_dim(g0, start, m_c)
                grad = gj + Cj @ delta
                wU = jnp.take(w, Uj)
                wU_new = prox_elem(wU - t * grad, t, variant="l1", lam=lam)
                w = w.at[Uj].set(wU_new)
                delta = jax.lax.dynamic_update_slice_in_dim(
                    delta, wU_new - wU, start, axis=0)
                return (w, delta), None

            (w, delta), _ = jax.lax.scan(
                inner, (w, jnp.zeros_like(U, w.dtype)), jnp.arange(blk))
            v = v + BU.T @ delta                       # local roll-forward
            return (w, v), None

        (w, _), _ = jax.lax.scan(outer, (w0, v0), idx)
        return w

    return solve_local


def _local_solver(algorithm: str, cfg: SolverConfig, lam: float,
                  axis: str, data_axes: tuple):
    """Build the per-shard function run under shard_map.

    Inside, every array is the local shard; psum over ``axis`` produces
    replicated global statistics.
    """
    if algorithm in COORD_ALGORITHMS:
        return _coord_local_solver(algorithm, cfg, lam, data_axes)
    return _gram_local_solver(algorithm, cfg, lam, data_axes)


def make_distributed_solver(algorithm: str, mesh: Mesh, cfg: SolverConfig,
                            lam: float, axis: str | tuple = "data") -> Callable:
    """Build a jitted distributed solver.

    algorithm: one of 'sfista' | 'spnm' | 'pdhg' | 'bcd' or its 'ca_'-prefixed
    k-step form. Returns solve(X, y, w0, t, key) operating on globally-sharded
    arrays: X sharded P(None, 'data'), y P('data'), w replicated. All
    algorithms solve the l1/LASSO composite (this module's (X, y, lam) API).
    """
    if algorithm not in ALGORITHMS:
        raise ValueError(f"unknown algorithm {algorithm!r}; "
                         f"expected one of {ALGORITHMS}")
    data_axes = (axis,) if isinstance(axis, str) else tuple(axis)
    local = _local_solver(algorithm, cfg, lam, axis, data_axes)
    spec_X = P(None, data_axes)
    spec_y = P(data_axes)
    rep = P()

    solve = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec_X, spec_y, rep, rep, rep),
        out_specs=rep,
        check_vma=False,
    )
    # Like the step builders in launch/steps.py, pin the registry backend at
    # build time: the trace runs under it, so the jitted solver cannot
    # silently diverge from a later policy change (the executable is cached;
    # rebuild the solver to re-resolve the policy).
    backend = registry.resolved_backend()

    def solve_pinned(X, y, w0, t, key):
        with registry.use(backend):
            return solve(X, y, w0, t, key)

    return jax.jit(solve_pinned)


def shard_problem(mesh: Mesh, X, y, axis: str | tuple = "data"):
    """Place (X, y) with the column-partitioned layout the solvers expect.

    The sample count is trimmed to a multiple of the data-axis size (jit
    argument shardings require exact divisibility); dropping < P samples is
    the standard distributed-data convention."""
    data_axes = (axis,) if isinstance(axis, str) else tuple(axis)
    P_ = 1
    for a in data_axes:
        P_ *= mesh.shape[a]
    n = (X.shape[1] // P_) * P_
    xs = jax.device_put(X[:, :n], NamedSharding(mesh, P(None, data_axes)))
    ys = jax.device_put(y[:n], NamedSharding(mesh, P(data_axes)))
    return xs, ys
