"""Mixture-of-Experts FFN: token-choice top-k routing, shared experts
(DeepSeek-MoE style), in two formulations.

- :func:`moe_ffn` (training, and prefill over every expert): capacity
  dropping over an (E, C, d) expert buffer, expert-parallel over the
  ``model`` mesh axis. Dispatch/combine use scatter/gather against the
  buffer — the GSPMD-friendly formulation: tokens stay sharded over the
  data axes, the buffer is constrained to experts-over-model so XLA
  materializes the dispatch as an all-to-all style reshard rather than a
  full all-gather.
- :func:`route` + :func:`held_experts` (decode, and a chip's share of the
  experts): dropless. The router scores every expert; the chip computes the
  SwiGLU of each expert it holds over exactly the tokens routed to it
  (``held_experts_ffn`` kernel) and weights each by its gate. What the
  experts held elsewhere add is left out: their chips add it.

The router runs in fp32 (standard practice for stability); the top-k gate
weights are renormalised only where the configuration says so
(``norm_topk_prob``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.models.layers import dense_init
from repro.models.mlp import init_swiglu, swiglu


def init_moe(key, d: int, moe_ff: int, n_experts: int, n_shared: int,
             shared_ff: int, dtype=jnp.float32, n_held: int = 0):
    """Router over all ``n_experts``; stacked weights of the ``n_held``
    experts this chip holds (0: all of them)."""
    n_held = n_held or n_experts
    ks = jax.random.split(key, 5)
    params = dict(
        router=dense_init(ks[0], (d, n_experts), dtype=dtype),
        # stacked expert weights (E, d, ff) / (E, ff, d)
        w_gate=dense_init(ks[1], (n_held, d, moe_ff), in_axis=1, dtype=dtype),
        w_up=dense_init(ks[2], (n_held, d, moe_ff), in_axis=1, dtype=dtype),
        w_down=dense_init(ks[3], (n_held, moe_ff, d), in_axis=1, dtype=dtype),
    )
    if n_shared:
        params["shared"] = init_swiglu(ks[4], d, shared_ff, dtype)
    return params


def route(router, x, *, top_k: int, norm_topk_prob: bool = True):
    """fp32 softmax over every expert, then top-k. Returns (gates (..., k)
    float32, sel (..., k) int32, probs (..., E))."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, sel = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, sel.astype(jnp.int32), probs


def expert_counts(sel, first_expert: int, n_held: int) -> dict:
    """The held experts' work for routing ``sel``: (token, held expert)
    pairs, and held experts with at least one token."""
    local = sel.reshape(-1, sel.shape[-1]) - first_expert
    chose = local[..., None] == jnp.arange(n_held)           # (T, k, E)
    return dict(assignments=chose.sum(dtype=jnp.int32),
                experts_hit=chose.any((0, 1)).sum(dtype=jnp.int32))


#: the held experts' weights, stacked (E, ...) per layer
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def held_experts(params, x, gates, sel, *, first_expert: int = 0,
                 layer=None):
    """x (B, S, d) -> (B, S, d) float32: each token's sum, over the experts
    this chip holds that it chose, of gate x SwiGLU. Drops no token.

    layer: None when ``params`` holds one layer's experts; else the index
    of this layer in expert weights stacked over every layer (L, E, ...),
    which the kernel then reads in place."""
    B, S, d = x.shape
    k = sel.shape[-1]
    w = [params[n] for n in EXPERT_WEIGHTS]
    if layer is None:
        w, layer = [a[None] for a in w], jnp.int32(0)
    out = registry.dispatch(
        "held_experts_ffn", x.reshape(B * S, d), sel.reshape(B * S, k),
        gates.reshape(B * S, k), *w, layer, first_expert=first_expert)
    return out.reshape(B, S, d)


@jax.custom_vjp
def _combine(ye, sel, pos, w):
    """out[b,s] = sum_k w[b,s,k] * ye[b, sel[b,s,k], pos[b,s,k]]."""
    def row(ye_r, sel_r, pos_r, w_r):
        return jnp.einsum("skd,sk->sd", ye_r[sel_r, pos_r], w_r)
    return jax.vmap(row)(ye, sel, pos, w)


def _combine_fwd(ye, sel, pos, w):
    return _combine(ye, sel, pos, w), (ye, sel, pos, w)


def _combine_bwd(res, dout):
    ye, sel, pos, w = res

    def g_ye_row(d_r, sel_r, pos_r, w_r):
        upd = d_r[:, None, :] * w_r[..., None]                  # (S,k,d)
        return jnp.zeros(ye.shape[1:], dout.dtype).at[sel_r, pos_r].add(
            upd, mode="drop")

    def g_w_row(ye_r, sel_r, pos_r, d_r):
        return jnp.einsum("skd,sd->sk", ye_r[sel_r, pos_r], d_r)

    g_ye = jax.vmap(g_ye_row)(dout, sel, pos, w)
    g_w = jax.vmap(g_w_row)(ye, sel, pos, dout)
    return g_ye, None, None, g_w


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe_ffn(params, x, *, top_k: int, capacity_factor: float = 1.25,
            norm_topk_prob: bool = True, constrain=lambda x, spec: x):
    """x (B, S, d) -> (B, S, d), plus the load-balance aux loss. Holds
    every expert (the router's width equals the stacked experts).

    Each batch row is a routing group; capacity C = ceil(S*top_k/E * cf).
    Dropped tokens (over capacity) fall back to the shared experts/residual.
    """
    B, S, d = x.shape
    E = params["router"].shape[1]
    C = max(int(S * top_k / E * capacity_factor), 4)

    weights, sel, gates = route(params["router"], x, top_k=top_k,
                                norm_topk_prob=norm_topk_prob)  # (B,S,k)

    # position of each (token, slot) within its expert's capacity buffer
    oh = jax.nn.one_hot(sel, E, dtype=jnp.int32)                # (B,S,k,E)
    flat = oh.reshape(B, S * top_k, E)
    pos = jnp.cumsum(flat, axis=1) - flat                       # pre-count
    pos_tok = (pos * flat).sum(-1).reshape(B, S, top_k)         # (B,S,k)
    keep = pos_tok < C                                          # capacity mask

    # dispatch: scatter tokens into the expert buffer (B, E, C, d).
    # vmap over the batch row makes the scatter/gather carry explicit
    # operand-batching dims, which GSPMD partitions along the data axes —
    # without it the scatter runs batch-replicated and the combine gather
    # lowers to a full-batch fp32 all-reduce per layer (measured 16x worse
    # collective volume).
    pos_clip = jnp.where(keep, pos_tok, C - 1)                  # drops collide
    src = jnp.where(keep[..., None], x[:, :, None, :], 0.0).astype(x.dtype)

    def dispatch_row(sel_r, pos_r, src_r):
        buf_r = jnp.zeros((E, C, d), x.dtype)
        return buf_r.at[sel_r, pos_r].add(src_r, mode="drop")

    buf = jax.vmap(dispatch_row)(sel, pos_clip, src)            # (B,E,C,d)
    buf = constrain(buf, ("batch", "tp", None, None))           # EP reshard

    # expert SwiGLU on the buffer
    wg, wu, wd = (params[k].astype(x.dtype) for k in ("w_gate", "w_up", "w_down"))
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf, wg))
    h = h * jnp.einsum("becd,edf->becf", buf, wu)
    ye = jnp.einsum("becf,efd->becd", h, wd)                    # (B,E,C,d)
    # all-gather ye over the expert (model) axis HERE, in bf16: the combine
    # gather below then stays shard-local. Left expert-sharded, GSPMD
    # implements the gather as replicate+all-reduce of fp32 per-slot tensors
    # (measured 7.5x more collective volume).
    ye = constrain(ye, ("batch", None, None, None))

    # combine: gather each slot's output, weight and sum over k INSIDE the
    # vmapped row function — the psum over the expert (model) axis then
    # happens on the summed (S, d) bf16 tensor instead of the per-slot fp32
    # (S, k, d) one. _combine's custom VJP makes the backward the mirror
    # image of the forward dispatch (vmap scatter with batching dims) —
    # without it GSPMD all-reduces full per-slot fp32 gradients per layer.
    wk = jnp.where(keep, weights, 0.0).astype(x.dtype)          # (B,S,k)
    out = _combine(ye, sel, pos_clip, wk)
    out = constrain(out, ("batch", None, None))

    if "shared" in params:
        out = out + swiglu(params["shared"], x, constrain)

    # GShard load-balance aux loss: E * sum_e f_e * p_e
    frac = (oh.sum(axis=2).reshape(B * S, E).mean(0)).astype(jnp.float32)
    prob = gates.reshape(B * S, E).mean(0)
    aux = E * jnp.sum(frac * prob)
    return out, aux
