"""jit-able train_step / serve_step builders shared by the trainer, the
server, and the multi-pod dry-run.

train_step: microbatched gradient accumulation (lax.scan) + remat + AdamW on
FSDP-sharded fp32 masters. The accumulation loop IS the paper's CA schedule
(one gradient collective per ``ca_k`` microbatches — see optim/ca_sync.py).

serve_step: one-token decode against a sharded KV/SSM cache.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models import init_params, loss_fn, init_cache, decode_step
from repro.models.transformer import forward
from repro.optim import adamw_init, adamw_update, OptState, cosine_schedule
from repro.dist.sharding import Rules
from repro.kernels import registry


class TrainState(NamedTuple):
    params: dict
    opt: OptState


def make_train_step(cfg, rules: Optional[Rules], *, ca_k: int = 8,
                    peak_lr: float = 3e-4, warmup: int = 100,
                    total_steps: int = 10_000, remat: bool = True,
                    sync_every_microbatch=False):
    """Returns train_step(state, batch) -> (state, metrics).

    batch leaves have global batch dim B; it is split into ca_k microbatches
    accumulated locally (CA schedule). ``sync_every_microbatch=True`` builds
    the classical-DDP baseline instead: one optimizer update per microbatch,
    hence k collectives per global batch — used for HLO message-count
    comparisons (paper Table I analogue).

    Kernels dispatch through ``repro.kernels.registry``; the backend is
    resolved once here and pinned for every trace of the returned step (a
    later policy change does not retrace an existing step)."""
    backend = registry.resolved_backend()
    constrain = rules.constrain if rules is not None else (lambda x, s: x)

    def split_micro(batch):
        def f(x):
            B = x.shape[0]
            assert B % ca_k == 0, f"batch {B} % ca_k {ca_k}"
            return x.reshape(ca_k, B // ca_k, *x.shape[1:])
        return jax.tree.map(f, batch)

    def micro_loss(params, mb):
        return loss_fn(params, cfg, mb, constrain=constrain, remat=remat)

    def _train_step(state: TrainState, batch):
        lr = cosine_schedule(state.opt.step, peak_lr=peak_lr, warmup=warmup,
                             total=total_steps)
        micro = split_micro(batch)

        if sync_every_microbatch:
            # classical: optimizer (and collective) per microbatch
            def body(st, mb):
                loss, g = jax.value_and_grad(micro_loss)(st.params, mb)
                p, opt, gn = adamw_update(st.params, g, st.opt, lr=lr)
                return TrainState(p, opt), (loss, gn)
            state, (losses, gns) = jax.lax.scan(body, state, micro)
            return state, dict(loss=losses.mean(), grad_norm=gns.mean(), lr=lr)

        # CA schedule: accumulate ca_k microbatch grads, ONE update/collective.
        # The bf16 parameter all-gather is hoisted OUT of the microbatch loop
        # (gather once per step instead of per microbatch — the same
        # communication hoist as the paper's k-step Gram unrolling), and the
        # gradient reduce-scatter back to the fsdp layout fires once.
        if rules is not None:
            from repro.dist.sharding import param_specs
            g_spec = param_specs(state.params, rules, gather_fsdp=True)
            s_spec = param_specs(state.params, rules)
            import jax.sharding as jsh
            p_comp = jax.tree.map(
                lambda p, sp: jax.lax.with_sharding_constraint(
                    p.astype(jnp.bfloat16) if p.dtype == jnp.float32 else p,
                    jsh.NamedSharding(rules.mesh, sp)),
                state.params, g_spec)
        else:
            p_comp = jax.tree.map(
                lambda p: p.astype(jnp.bfloat16)
                if p.dtype == jnp.float32 else p, state.params)

        # The accumulator lives in the SHARDED (fsdp x tp) layout: each
        # microbatch grad is reduce-scattered before the add, so the fp32
        # accumulation buffer is 1/|mesh| per device (a replicated-over-data
        # accumulator for llama3-8b costs ~2 GB/chip and pushes the step
        # over HBM; the per-microbatch reduce-scatter is the classic ZeRO
        # trade and is bandwidth-optimal — same total bytes as one final
        # all-reduce, paid incrementally and overlappable with compute).
        def shard_grads(g):
            if rules is None:
                return g
            return jax.tree.map(
                lambda x, sp: jax.lax.with_sharding_constraint(
                    x, jsh.NamedSharding(rules.mesh, sp)),
                g, s_spec)

        def body(acc, mb):
            loss, g = jax.value_and_grad(micro_loss)(p_comp, mb)
            g = shard_grads(g)
            acc_loss, acc_g = acc
            return (acc_loss + loss, jax.tree.map(jnp.add, acc_g, g)), None

        zero = (jnp.zeros((), jnp.float32),
                shard_grads(jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), state.params)))
        (loss_sum, gsum), _ = jax.lax.scan(body, zero, micro)
        grads = jax.tree.map(lambda g: g / ca_k, gsum)
        params, opt, gnorm = adamw_update(state.params, grads, state.opt,
                                          lr=lr)
        return TrainState(params, opt), dict(loss=loss_sum / ca_k,
                                             grad_norm=gnorm, lr=lr)

    def train_step(state: TrainState, batch):
        with registry.use(backend):
            return _train_step(state, batch)

    return train_step


def make_serve_step(cfg, rules: Optional[Rules], *, greedy: bool = True):
    """Returns serve_step(params, cache, tokens, positions=None,
    page_table=None) -> (next_tokens, logits, cache).

    positions: optional (B,) per-slot decode depths — see
    ``repro.models.decode_step``; the continuous-batching engine
    (``repro.serve``) drives this, the classic whole-batch path omits it.
    page_table: (B, pages_per_slot) int32, with per-row positions, when
    the cache K/V leaves are a paged pool (``repro.serve.paging``).

    Kernels dispatch through ``repro.kernels.registry`` (backend pinned at
    build time)."""
    backend = registry.resolved_backend()
    constrain = rules.constrain if rules is not None else (lambda x, s: x)

    def serve_step(params, cache, tokens, positions=None, page_table=None):
        with registry.use(backend):
            logits, cache = decode_step(params, cfg, cache, tokens,
                                        positions=positions,
                                        constrain=constrain,
                                        page_table=page_table)
        with jax.named_scope("decode.head"):
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return nxt[:, None], logits, cache

    return serve_step


def init_train_state(cfg, key, rules: Optional[Rules] = None) -> TrainState:
    params = init_params(cfg, key)
    return TrainState(params=params, opt=adamw_init(params))
