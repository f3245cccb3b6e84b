"""Stale-k asynchronous aggregation: staleness bound + distribution-level
convergence parity with synchronous CA local-SGD.

``ca_stale_k_solver`` (arXiv:1712.06047) consumes each round's all-reduced
aggregate one round late. Two properties pin it down:

* **Staleness bound** — round t sees collectives through round t-1 and
  nothing older/newer. A linear loss makes the gradient independent of the
  parameters, so the round an aggregate lands is directly observable.
* **Convergence parity** — with damping=1.0 the one-round pipeline is the
  synchronous ``ca_local_sgd_solver`` trajectory shifted by one round:
  per-round losses match to float tolerance and ``finalize`` after T rounds
  equals the synchronous parameters after T averages. Checked on the
  paper-side Lasso least-squares objective and on the LM tiny benchmark
  (PR-5-style distribution-level harness: same seeds, same batches, compare
  whole trajectories rather than single samples).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config
from repro.data import make_lasso_data, make_token_batch
from repro.models import init_params, loss_fn
from repro.optim import ca_local_sgd_solver, ca_stale_k_solver

NSHARDS = 8


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((NSHARDS,), ("data",))


# ------------------------------------------------------------ staleness bound
def test_staleness_bound_exactly_one_round(mesh):
    """With a linear loss the local delta is a constant per round, so the
    carry exposes exactly which round's aggregate has landed."""
    k, lr, damping = 2, 0.5, 0.5
    # grad of mean(b @ w) wrt w is mean over rows of b — independent of w,
    # so round i's per-shard delta is -lr * k * c_i for constant batch c_i
    solver = ca_stale_k_solver(lambda w, b: jnp.mean(b @ w), mesh,
                               k=k, lr=lr, damping=damping)
    carry = solver.init(jnp.zeros(3))
    batches = [jnp.full((k, NSHARDS, 3), float(i + 1)) for i in range(3)]
    deltas = [-lr * k * float(i + 1) for i in range(3)]

    carry, _ = solver.step(carry, batches[0])
    # round 0's aggregate is still in flight: params untouched
    np.testing.assert_array_equal(np.asarray(carry[0]), 0.0)
    np.testing.assert_allclose(np.asarray(carry[1]), deltas[0], rtol=1e-6)

    carry, _ = solver.step(carry, batches[1])
    # round 1 landed exactly round 0's aggregate, damped — nothing newer
    np.testing.assert_allclose(np.asarray(carry[0]), damping * deltas[0],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(carry[1]), deltas[1], rtol=1e-6)

    carry, _ = solver.step(carry, batches[2])
    np.testing.assert_allclose(
        np.asarray(carry[0]), damping * (deltas[0] + deltas[1]), rtol=1e-6)

    # finalize lands the last in-flight aggregate, once
    np.testing.assert_allclose(
        np.asarray(solver.finalize(carry)),
        damping * (deltas[0] + deltas[1] + deltas[2]), rtol=1e-6)


def test_finalize_is_pure(mesh):
    """finalize reads the carry without consuming it — calling it twice or
    mid-stream never perturbs the trajectory."""
    solver = ca_stale_k_solver(lambda w, b: jnp.mean((b @ w) ** 2), mesh,
                               k=2, lr=0.1)
    carry = solver.init(jnp.ones(3))
    batch = jnp.ones((2, NSHARDS, 3))
    carry, _ = solver.step(carry, batch)
    peek = solver.finalize(carry)
    carry2, _ = solver.step(carry, batch)
    np.testing.assert_array_equal(np.asarray(solver.finalize(carry)),
                                  np.asarray(peek))
    assert not np.array_equal(np.asarray(carry2[0]), np.asarray(carry[0]))


# -------------------------------------------------------- Lasso tiny parity
def test_stale_k_matches_sync_on_lasso(mesh):
    """Damping=1.0: stale-k per-round losses equal the synchronous CA
    local-SGD losses shifted by zero (same batches, same start => identical
    rounds), and finalize equals the synchronous parameters, on the paper's
    Lasso least-squares objective."""
    d, n, k, rounds = 8, 64 * NSHARDS, 4, 12
    prob, _ = make_lasso_data(jax.random.PRNGKey(0), d, n)
    X, y = np.asarray(prob.X), np.asarray(prob.y)   # X: (d, n)

    def loss(w, batch):
        xb, yb = batch                              # xb: (m, d)
        return jnp.mean((xb @ w - yb) ** 2)

    sync = ca_local_sgd_solver(loss, mesh, k=k, lr=0.05)
    stale = ca_stale_k_solver(loss, mesh, k=k, lr=0.05)

    rng = np.random.RandomState(0)
    w_sync = jnp.zeros(d)
    carry = stale.init(jnp.zeros(d))
    sync_losses, stale_losses = [], []
    for _ in range(rounds):
        idx = rng.randint(0, n, size=(k, NSHARDS * 8))
        batch = (jnp.asarray(X.T[idx]), jnp.asarray(y[idx]))
        w_sync, ls = sync(w_sync, batch)
        carry, lt = stale.step(carry, batch)
        sync_losses.append(float(ls))
        stale_losses.append(float(lt))
    # identical per-round losses (both trajectories take the same k local
    # steps from the same round-entry point) ...
    np.testing.assert_allclose(stale_losses, sync_losses, rtol=2e-5)
    # ... and identical end params once the last aggregate lands
    np.testing.assert_allclose(np.asarray(stale.finalize(carry)),
                               np.asarray(w_sync), atol=1e-5)
    # the trajectory actually optimizes (not vacuous parity of a fixpoint)
    assert stale_losses[-1] < stale_losses[0] * 0.5, stale_losses


def test_stale_k_damped_converges_on_lasso(mesh):
    """Damping < 1 breaks exact equivalence but must still drive the loss
    down — the 1712.06047 configuration for real asynchrony."""
    d, n, k, rounds = 8, 64 * NSHARDS, 4, 16
    prob, _ = make_lasso_data(jax.random.PRNGKey(1), d, n)
    X, y = np.asarray(prob.X), np.asarray(prob.y)

    def loss(w, batch):
        xb, yb = batch
        return jnp.mean((xb @ w - yb) ** 2)

    stale = ca_stale_k_solver(loss, mesh, k=k, lr=0.05, damping=0.5)
    rng = np.random.RandomState(1)
    carry = stale.init(jnp.zeros(d))
    losses = []
    for _ in range(rounds):
        idx = rng.randint(0, n, size=(k, NSHARDS * 8))
        carry, l = stale.step(carry, (jnp.asarray(X.T[idx]),
                                      jnp.asarray(y[idx])))
        losses.append(float(l))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.6, losses


# ----------------------------------------------------------- LM tiny parity
def test_stale_k_matches_sync_on_lm():
    """Distribution-level harness on the LM tiny benchmark: stale-k with
    damping=1.0 reproduces the synchronous local-SGD loss trajectory within
    tolerance on the smoke transformer."""
    mesh = jax.make_mesh((NSHARDS,), ("data",))
    cfg = smoke_config(ARCHS["internlm2-1.8b"])
    params = init_params(cfg, jax.random.PRNGKey(0))
    k, rounds, seq = 2, 6, 16

    lm_loss = lambda p, b: loss_fn(p, cfg, b)
    sync = ca_local_sgd_solver(lm_loss, mesh, k=k, lr=5e-3)
    stale = ca_stale_k_solver(lm_loss, mesh, k=k, lr=5e-3)

    def batch(t):
        toks, labels = make_token_batch(jax.random.PRNGKey(100 + t),
                                        k * NSHARDS, seq, cfg.vocab)
        return dict(tokens=toks.reshape(k, NSHARDS, seq),
                    labels=labels.reshape(k, NSHARDS, seq))

    p_sync = params
    carry = stale.init(params)
    sync_losses, stale_losses = [], []
    for t in range(rounds):
        b = batch(t)
        p_sync, ls = sync(p_sync, b)
        carry, lt = stale.step(carry, b)
        sync_losses.append(float(ls))
        stale_losses.append(float(lt))
    # the two schedules apply the same updates in a different order through a
    # bf16 model (unit roundoff 2^-9), so the losses match only as closely
    # as the parameters do: the same rtol=1e-3 as the parameter check below
    # (under jax 0.9 the worst round differs by 1.8e-4 relative)
    np.testing.assert_allclose(stale_losses, sync_losses, rtol=1e-3)
    for a, b in zip(jax.tree.leaves(stale.finalize(carry)),
                    jax.tree.leaves(p_sync)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-3)
    assert stale_losses[-1] < stale_losses[0], stale_losses
