"""Pallas TPU kernel: Mamba-2 SSD chunked scan.

TPU adaptation of the Mamba-2 CUDA kernel (which leans on warp-level
primitives): the chunk-quadratic intra term is an MXU-friendly (L x L) @
(L x P) matmul chain; the recurrent state (P x N fp32) lives in VMEM scratch
and persists across the innermost (chunk) grid dimension, so the sequential
dependency never leaves the core. Per (batch x head) program, chunks stream
HBM->VMEM once; there is no inter-core communication.

Grid: (B*H, S/L). VMEM per program at L=128, P=64, N=128:
x/y tiles 2*L*P*4 + B/C tiles 2*L*N*4 + decay L*L*4 + state P*N*4 ~= 0.3 MiB.
Group-shared B/C (the Mamba-2 "ngroups" analogue of GQA) is expressed through
the BlockSpec index map — no HBM replication.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def causal_mask(L: int):
    """(L, L) f32 lower-triangular ones: ``mask[t, s] = 1[t >= s]``."""
    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    return (row >= col).astype(jnp.float32)


def tri_dot(x, y, dims):
    """Full-f32 matmul for the prefix sums: Mosaic has no ``cumsum``, so
    they run on the MXU as products with :func:`causal_mask`. Log-decays
    enter ``exp``, so the sums keep full f32 precision."""
    return jax.lax.dot_general(x, y, (dims, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _ssd_body(xdt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, hins_ref, h_ref,
              *, nchunks: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    xdt = xdt_ref[0, 0].astype(jnp.float32)       # (L, P)
    a = a_ref[0, 0].astype(jnp.float32)           # (L, 1) log-decay
    Bm = b_ref[0, 0].astype(jnp.float32)          # (L, N)
    Cm = c_ref[0, 0].astype(jnp.float32)          # (L, N)
    L = xdt.shape[0]
    h_in = h_ref[...]                             # (P, N) state entering chunk
    if hins_ref is not None:                      # residual for the backward
        hins_ref[0, 0, :, :] = h_in

    tril = causal_mask(L)
    cs = tri_dot(tril, a, ((1,), (0,)))           # (L, 1) inclusive cumsum
    cs_row = tri_dot(a, tril, ((0,), (1,)))       # (1, L) the same, as a row
    cs_L = cs[L - 1, 0]

    # intra-chunk: y_t += sum_{s<=t} exp(cs_t - cs_s) (C_t.B_s) xdt_s
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (L, L)
    decay = jnp.where(tril > 0, jnp.exp(cs - cs_row), 0.0)
    y = jax.lax.dot_general(CB * decay, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # (L, P)

    # inter-chunk: y_t += exp(cs_t) * C_t . h_in
    y += jnp.exp(cs) * jax.lax.dot_general(
        Cm, h_in, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # (L, P)

    # state update: h' = exp(cs_L) h_in + (xdt * exp(cs_L - cs))^T @ B
    w = jnp.exp(cs_L - cs)                        # (L, 1)
    h_ref[...] = jnp.exp(cs_L) * h_in + jax.lax.dot_general(
        xdt * w, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)       # (P, N)

    y_ref[0, 0, :, :] = y.astype(y_ref.dtype)

    @pl.when(ic == nchunks - 1)
    def _emit_state():
        hout_ref[0, :, :] = h_ref[...]


def _ssd_kernel(xdt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_ref, *,
                nchunks: int):
    _ssd_body(xdt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, None, h_ref,
              nchunks=nchunks)


def _ssd_kernel_states(xdt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref,
                       hins_ref, h_ref, *, nchunks: int):
    _ssd_body(xdt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, hins_ref, h_ref,
              nchunks=nchunks)


@functools.partial(jax.jit, static_argnames=("chunk", "ngroups", "interpret",
                                             "return_states"))
def ssd(xdt, a, Bm, Cm, *, chunk: int, ngroups: int = 1,
        interpret: bool = True, return_states: bool = False):
    """Chunked SSD. xdt (Bt,H,S,P) = x*dt; a (Bt,H,S,1) = dt*A;
    Bm, Cm (Bt,G,S,N). S % chunk == 0 (ops.py pads). Returns
    y (Bt,H,S,P) and final state (Bt*H, P, N).

    return_states: also return the per-chunk *incoming* states
    (Bt*H, S/chunk, P, N) fp32 — the residual the reverse chunk-scan
    backward kernel consumes."""
    Bt, H, S, P = xdt.shape
    N = Bm.shape[-1]
    nchunks = S // chunk
    hpg = H // ngroups                                 # heads per group
    grid = (Bt * H, nchunks)

    out_specs = [
        pl.BlockSpec((1, 1, chunk, P),
                     lambda bh, ic: (bh // H, bh % H, ic, 0)),
        pl.BlockSpec((1, P, N), lambda bh, ic: (bh, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct(xdt.shape, xdt.dtype),
        jax.ShapeDtypeStruct((Bt * H, P, N), jnp.float32),
    ]
    if return_states:
        kernel = functools.partial(_ssd_kernel_states, nchunks=nchunks)
        out_specs.append(pl.BlockSpec((1, 1, P, N),
                                      lambda bh, ic: (bh, ic, 0, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((Bt * H, nchunks, P, N), jnp.float32))
    else:
        kernel = functools.partial(_ssd_kernel, nchunks=nchunks)

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P),
                         lambda bh, ic: (bh // H, bh % H, ic, 0)),
            pl.BlockSpec((1, 1, chunk, 1),
                         lambda bh, ic: (bh // H, bh % H, ic, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda bh, ic: (bh // H, (bh % H) // hpg, ic, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda bh, ic: (bh // H, (bh % H) // hpg, ic, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xdt, a, Bm, Cm)
    y, h = outs[0], outs[1].reshape(Bt, H, P, N)
    return (y, h, outs[2]) if return_states else (y, h)
