"""Pallas TPU kernel: reverse chunk-scan backward pass for the SSD operator.

Recompute-based (the Mamba-2 backward): the forward is re-run once with
``return_states=True`` to recover each chunk's incoming state h_in (cheap —
the per-chunk summaries are a byproduct of the forward sweep), then this
kernel sweeps the chunks in REVERSE grid order, carrying the state cotangent
dh (P x N fp32) in VMEM scratch the same way the forward carries h. All
chunk-local tensors (scores, decays) are recomputed from x/a/B/C inside the
kernel — nothing O(S x S) is saved.

Per chunk with inclusive log-decay cumsum cs, e = exp(cs), w = exp(cs_L-cs),
decay_{t,s} = 1[t>=s] exp(cs_t-cs_s), CB = C B^T and DYX = dy xdt^T:

    dxdt = (decay CB)^T dy + w * (B dh^T)
    dC   = (decay DYX) B + e * (dy h_in)
    dB   = (decay DYX)^T C + (w xdt) dh
    dh'  = exp(cs_L) dh + (e dy)^T C                       (carried to s-1)
    da   = revcumsum( rowsum(E) - colsum(E) + <dy, y_inter> - w dw )
           + [all rows] w dw + exp(cs_L) <h_in, dh>        (E = decay CB DYX)

Grid: (B*H, S/L) with chunk index maps reversed (program ic reads chunk
nchunks-1-ic). dB/dC come out per *head*; the caller reduces heads -> the
shared (single-group) B/C, mirroring how the forward broadcasts them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ssd import kernel as _k


def _ssd_bwd_kernel(xdt_ref, a_ref, b_ref, c_ref, dy_ref, hins_ref, dhf_ref,
                    dxdt_ref, da_ref, db_ref, dc_ref, dh_ref, *,
                    nchunks: int):
    ic = pl.program_id(1)                       # 0 == LAST chunk (reversed)

    @pl.when(ic == 0)
    def _init():
        dh_ref[...] = dhf_ref[0]                # cotangent of the final state

    f32 = jnp.float32
    xdt = xdt_ref[0, 0].astype(f32)             # (L, P)
    a = a_ref[0, 0].astype(f32)                 # (L, 1)
    Bm = b_ref[0, 0].astype(f32)                # (L, N)
    Cm = c_ref[0, 0].astype(f32)                # (L, N)
    dy = dy_ref[0, 0].astype(f32)               # (L, P)
    h_in = hins_ref[0, 0]                       # (P, N)
    dh = dh_ref[...]                            # (P, N)
    L = xdt.shape[0]

    tril = _k.causal_mask(L)
    cs = _k.tri_dot(tril, a, ((1,), (0,)))      # (L, 1) inclusive cumsum
    cs_row = _k.tri_dot(a, tril, ((0,), (1,)))  # (1, L) the same, as a row
    cs_L = cs[L - 1, 0]
    e = jnp.exp(cs)                             # (L, 1)
    w = jnp.exp(cs_L - cs)                      # (L, 1)

    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=f32)
    decay = jnp.where(tril > 0, jnp.exp(cs - cs_row), 0.0)
    CB = dot(Cm, Bm, (((1,), (1,)), ((), ())))           # (L, L)
    DYX = dot(dy, xdt, (((1,), (1,)), ((), ())))         # (L, L)
    DD = decay * DYX

    V = dot(Bm, dh, (((1,), (1,)), ((), ())))            # (L, P) = B dh^T
    dxdt = dot(decay * CB, dy, (((0,), (0,)), ((), ()))) + w * V
    dC = dot(DD, Bm, (((1,), (0,)), ((), ()))) \
        + e * dot(dy, h_in, (((1,), (0,)), ((), ())))    # (L, N)
    dB = dot(DD, Cm, (((0,), (0,)), ((), ()))) \
        + dot(w * xdt, dh, (((1,), (0,)), ((), ())))     # (L, N)

    # log-decay gradient, collected as dcs then prefix-reversed to da
    E = DD * CB                                          # decay * CB * DYX
    ones = jnp.ones((L, 1), f32)
    r1 = jnp.sum(E, axis=1, keepdims=True)               # Σ_s E[t, s]
    c1 = dot(E, ones, (((0,), (0,)), ((), ())))          # Σ_t E[t, s]
    y_inter = e * dot(Cm, h_in, (((1,), (1,)), ((), ())))
    de = jnp.sum(dy * y_inter, axis=1, keepdims=True)
    dw = jnp.sum(xdt * V, axis=1, keepdims=True) * w
    dcs = r1 - c1 + de - dw
    # cs_L terms touch every a_r of the chunk: fold them into slot L-1 so the
    # reverse cumsum spreads them to all rows
    dcs_L = jnp.sum(dw) + jnp.exp(cs_L) * jnp.sum(h_in * dh)
    ridx = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0)
    dcs = dcs + jnp.where(ridx == L - 1, dcs_L, 0.0)
    # da_r = Σ_{t>=r} dcs_t: the reverse cumsum, tril^T @ dcs
    da = _k.tri_dot(tril, dcs, ((0,), (0,)))

    dh_ref[...] = jnp.exp(cs_L) * dh + dot(e * dy, Cm,
                                           (((0,), (0,)), ((), ())))

    dxdt_ref[0, 0, :, :] = dxdt
    da_ref[0, 0, :, :] = da
    db_ref[0, 0, :, :] = dB
    dc_ref[0, 0, :, :] = dC


@functools.partial(jax.jit, static_argnames=("chunk", "ngroups", "interpret"))
def ssd_bwd(xdt, a, Bm, Cm, dy, hins, dh_final, *, chunk: int,
            ngroups: int = 1, interpret: bool = True):
    """Reverse chunk-scan. Shapes as in ``kernel.ssd`` plus dy (Bt,H,S,P),
    hins (Bt*H, S/chunk, P, N), dh_final (Bt*H, P, N); S % chunk == 0.

    Returns (dxdt (Bt,H,S,P), da (Bt,H,S,1), dB (Bt,H,S,N), dC (Bt,H,S,N))
    — dB/dC per head, reduced to groups by the caller."""
    Bt, H, S, P = xdt.shape
    N = Bm.shape[-1]
    nchunks = S // chunk
    hpg = H // ngroups
    grid = (Bt * H, nchunks)

    rev = lambda ic: nchunks - 1 - ic
    chunk_spec = lambda d: pl.BlockSpec(
        (1, 1, chunk, d), lambda bh, ic: (bh // H, bh % H, rev(ic), 0))
    group_spec = pl.BlockSpec(
        (1, 1, chunk, N), lambda bh, ic: (bh // H, (bh % H) // hpg, rev(ic), 0))

    kernel = functools.partial(_ssd_bwd_kernel, nchunks=nchunks)
    f32 = jnp.float32
    dxdt, da, dB, dC = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            chunk_spec(P),                                   # xdt
            chunk_spec(1),                                   # a
            group_spec,                                      # B
            group_spec,                                      # C
            chunk_spec(P),                                   # dy
            pl.BlockSpec((1, 1, P, N),
                         lambda bh, ic: (bh, rev(ic), 0, 0)),  # hins
            pl.BlockSpec((1, P, N), lambda bh, ic: (bh, 0, 0)),  # dh_final
        ],
        out_specs=[chunk_spec(P), chunk_spec(1), chunk_spec(N),
                   chunk_spec(N)],
        out_shape=[
            jax.ShapeDtypeStruct((Bt, H, S, P), f32),
            jax.ShapeDtypeStruct((Bt, H, S, 1), f32),
            jax.ShapeDtypeStruct((Bt, H, S, N), f32),
            jax.ShapeDtypeStruct((Bt, H, S, N), f32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xdt, a, Bm, Cm, dy, hins, dh_final)
    return dxdt, da, dB, dC
