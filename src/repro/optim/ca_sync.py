"""The paper's communication schedule lifted to LM training.

Three mechanisms, all first-class in the trainer:

1. **CA gradient accumulation (exact)** — the default train_step accumulates
   gradients over ``ca_k`` microbatches inside one jit step, so the gradient
   all-reduce fires once per k microbatches instead of once per microbatch
   (naive DDP). Like CA-SFISTA this is *arithmetically identical* to the
   classical schedule (gradients are linear in the batch) while cutting the
   collective count — and therefore latency cost — by k. Table-I-style
   verification (message counts from compiled HLO) lives in
   benchmarks/cost_table.py.

2. **CA local-SGD (k-AVG family, approximate)** — ``ca_local_sgd_solver``
   runs k *optimizer* steps on per-shard microbatches with zero communication
   and all-reduce-averages the parameters every k steps (shard_map over the
   data axes). Unlike (1) this changes the trajectory (the paper's
   exact-unrolling property is specific to Gram-linear iterations); it ships
   as an opt-in for latency-dominated meshes.

3. **Stale-k aggregation (synchronization-avoiding)** — ``ca_stale_k_solver``
   removes the remaining *synchronization point* the way the companion paper
   does (Devarakonda et al., arXiv:1712.06047, "Avoiding Synchronization in
   First-Order Methods"): round t applies the aggregate that round t-1
   *launched* — the current round's all-reduce is consumed only at the start
   of round t+1, so its collective can execute while the shards are already
   busy with the next k local steps. The staleness is bounded at exactly one
   round, and a ``damping`` factor scales the stale aggregate on arrival
   (1712.06047's step-size damping, gamma ~ 1/(1 + staleness)).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def ca_local_sgd_solver(loss_fn: Callable, mesh: Mesh, *, k: int, lr: float,
                        data_axes=("data",)):
    """Build step(params, batches) -> (params, mean_loss).

    loss_fn(params, batch) -> scalar. ``batches`` is a pytree whose leaves
    have leading dims (k, local_batch*P, ...) sharded over data_axes on dim 1.
    Each shard runs k SGD steps on its local slice, then parameters are
    averaged once — one collective per k steps.
    """
    axes = tuple(data_axes)

    def local(params, batches):
        nshards = 1
        for ax in axes:
            nshards *= jax.lax.axis_size(ax)

        def one(params, batch):
            loss, g = jax.value_and_grad(loss_fn)(params, batch)
            params = jax.tree.map(lambda p, gg: p - lr * gg, params, g)
            return params, loss

        params, losses = jax.lax.scan(one, params, batches)
        # THE collective: one parameter average per k local steps.
        params = jax.tree.map(
            lambda p: jax.lax.psum(p, axes) / nshards, params)
        loss = jax.lax.psum(losses.mean(), axes) / nshards
        return params, loss

    batch_spec = P(None, axes)   # prefix spec: applies to every batch leaf
    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), batch_spec),
        out_specs=(P(), P()),
        check_vma=False,
    ))


class StaleKSolver(NamedTuple):
    """``ca_stale_k_solver`` handle: ``carry = init(params)``, then
    ``carry, loss = step(carry, batches)`` per round, and
    ``params = finalize(carry)`` to land the last in-flight aggregate."""
    init: Callable
    step: Callable
    finalize: Callable


def ca_stale_k_solver(loss_fn: Callable, mesh: Mesh, *, k: int, lr: float,
                      damping: float = 1.0, data_axes=("data",)
                      ) -> StaleKSolver:
    """Stale-k asynchronous aggregation: local-SGD whose collective result
    is consumed one round late (arXiv:1712.06047).

    Carry is ``(params, inflight)``: ``inflight`` is the all-reduced k-step
    aggregate the previous round launched — semantically still on the wire.
    Each round first lands it (``params += damping * inflight``), then runs
    k local SGD steps on per-shard microbatches with zero communication, and
    finally launches the next aggregate (``psum`` of the mean local delta).
    Nothing downstream of the psum is needed until the *next* round's entry,
    so the collective overlaps the next round's dispatch instead of
    synchronizing every shard at the round boundary — the training-side twin
    of the serve engine's double-buffered host loop. The staleness bound is
    exactly one round: round t's gradients see collectives through round
    t-1 and nothing older.

    ``damping`` scales the stale aggregate on arrival (1712.06047's
    step-size damping, gamma ~ 1/(1 + staleness)). With ``damping=1.0``
    this deterministic one-round pipeline reproduces synchronous
    ``ca_local_sgd_solver`` exactly, shifted by one round — round t starts
    from the same point the synchronous solver reaches after t averages, so
    per-round losses match to float tolerance and ``finalize`` after T
    rounds equals the synchronous parameters after T averages. Damping < 1
    trades that equivalence for robustness when real asynchrony reorders
    arrivals.

    ``loss_fn(params, batch) -> scalar``; ``batches`` leaves are
    ``(k, local_batch * P, ...)`` sharded over ``data_axes`` on dim 1, as in
    :func:`ca_local_sgd_solver`.
    """
    axes = tuple(data_axes)
    damping = float(damping)

    def local(params, inflight, batches):
        nshards = 1
        for ax in axes:
            nshards *= jax.lax.axis_size(ax)
        # the previous round's collective lands (one-round staleness)
        params = jax.tree.map(lambda p, d: p + damping * d, params, inflight)

        def one(p, batch):
            loss, g = jax.value_and_grad(loss_fn)(p, batch)
            return jax.tree.map(lambda pp, gg: pp - lr * gg, p, g), loss

        moved, losses = jax.lax.scan(one, params, batches)
        delta = jax.tree.map(lambda a, b: a - b, moved, params)
        # THE collective: launched here, consumed at the next round's entry —
        # no shard blocks on its result inside this round
        delta = jax.tree.map(
            lambda d: jax.lax.psum(d, axes) / nshards, delta)
        loss = jax.lax.psum(losses.mean(), axes) / nshards
        return (params, delta), loss

    batch_spec = P(None, axes)
    sharded = jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), batch_spec),
        out_specs=((P(), P()), P()),
        check_vma=False,
    ))

    def init(params):
        return params, jax.tree.map(jnp.zeros_like, params)

    def step(carry, batches):
        params, inflight = carry
        return sharded(params, inflight, batches)

    def finalize(carry):
        """Land the final round's still-in-flight aggregate."""
        params, inflight = carry
        return jax.tree.map(lambda p, d: p + damping * d, params, inflight)

    return StaleKSolver(init=init, step=step, finalize=finalize)
