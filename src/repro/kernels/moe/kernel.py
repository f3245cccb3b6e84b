"""Pallas TPU kernel: the SwiGLU of the experts one chip holds, over exactly
the tokens routed to them, and each token's gate-weighted sum.

``ops.py`` builds the layout: the (token, held expert) pairs sorted by
expert, each expert's group padded to a multiple of ``tm`` rows.

  x     (M, d)        the pairs' token rows (zeros in the padding)
  comb  (T, M)  f32   comb[t, m] = the gate weight of pair m if it is
                      token t's, else 0
  wg, wu (L, E, d, F), wd (L, E, F, d): the held experts' weights of every
                      layer, read in place at layer ``layer``

Scalar prefetch: ``layer`` (1,), ``order``, ``tiles``, ``start`` (E,)
int32. Grid step i visits expert ``order[i]``: ``tiles[i]`` row tiles from
row ``start[i]``. Experts with a token come first; the steps after them
keep the last block index and compute nothing, so the weights of an expert
that no token chose are never read.

Grid (E, F / bf): the feature blocks of one expert's gate, up and down
weights stream through VMEM, double-buffered, each read once; the pair
rows stay in VMEM. Each pair's output accumulates over the feature blocks
in an f32 scratch, and the last step writes ``out = comb @ y``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of one tile of pairs: the bf16 sublane tile
TM = 16
#: bytes of one grid step's gate, up and down feature blocks
BLOCK_BYTES = 2 * 1024 * 1024


def feature_block(d: int, F: int, itemsize: int) -> int:
    """Largest lane-aligned divisor of ``F`` whose three weight blocks fit
    ``BLOCK_BYTES`` (the whole of ``F`` where no multiple of 128 divides
    it)."""
    cands = [b for b in range(128, F + 1, 128)
             if F % b == 0 and 3 * d * b * itemsize <= BLOCK_BYTES]
    return max(cands) if cands else F


def _kernel(layer_ref, order_ref, tiles_ref, start_ref, x_ref, comb_ref,
            wg_ref, wu_ref, wd_ref, o_ref, y_ref, *, tm):
    i, f = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (f == 0))
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    def tile(j, carry):
        r = pl.multiple_of(start_ref[i] + j * tm, tm)
        xt = x_ref[pl.ds(r, tm), :]
        g = jnp.dot(xt, wg_ref[0, 0], preferred_element_type=jnp.float32)
        u = jnp.dot(xt, wu_ref[0, 0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(wd_ref.dtype)
        y_ref[pl.ds(r, tm), :] += jnp.dot(h, wd_ref[0, 0],
                                          preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, tiles_ref[i], tile, 0)

    @pl.when((i == pl.num_programs(0) - 1) & (f == pl.num_programs(1) - 1))
    def _combine():
        o_ref[...] = jax.lax.dot(comb_ref[...], y_ref[...],
                                 precision=jax.lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tm", "bf", "interpret"))
def held_experts_ffn(layer, order, tiles, start, x, comb, wg, wu, wd, *,
                     tm: int, bf: int, interpret: bool = True):
    """The kernel over a prepared layout (see the module docstring):
    returns (T, d) float32."""
    M, d = x.shape
    T = comb.shape[0]
    E, _, F = wg.shape[1:]
    nf = F // bf
    last = nf - 1

    # index maps receive (*grid_indices, *scalar_prefetch_refs); a step
    # with no tile keeps the previous step's block, so nothing is fetched
    def w_in(i, f, layer, order, tiles, start):
        return layer[0], order[i], 0, jnp.where(tiles[i] > 0, f, last)

    def w_out(i, f, layer, order, tiles, start):
        return layer[0], order[i], jnp.where(tiles[i] > 0, f, last), 0

    whole = lambda *shape: pl.BlockSpec(shape, lambda *_: (0,) * len(shape))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(E, nf),
        in_specs=[whole(M, d), whole(T, M),
                  pl.BlockSpec((1, 1, d, bf), w_in),
                  pl.BlockSpec((1, 1, d, bf), w_in),
                  pl.BlockSpec((1, 1, bf, d), w_out)],
        out_specs=whole(T, d),
        scratch_shapes=[pltpu.VMEM((M, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, d), jnp.float32),
        # the pair outputs accumulate across every step: run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="held_experts_ffn",
    )(layer, order, tiles, start, x, comb, wg, wu, wd)
