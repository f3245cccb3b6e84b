"""Public jit'd wrapper for the sampled-Gram kernel: pads to tile multiples,
dispatches Pallas (interpret on CPU, compiled on TPU), unpads.

Registers the ``gram`` op: ``pallas`` is the tiled SYRK kernel below,
``xla`` is the pure-jnp oracle (fp32 accumulation either way). G = Xs Xs^T
is symmetric-linear in Xs, so the pallas impl carries the analytic VJP
dXs = (dG + dG^T) Xs — no kernel recomputation needed."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import pad, registry
from repro.kernels.gram import kernel as _k
from repro.kernels.gram import ref as _ref


@functools.partial(jax.jit, static_argnames=("bd", "bm", "interpret"))
def gram(Xs: jax.Array, *, bd: int | None = None, bm: int | None = None,
         interpret: bool | None = None) -> jax.Array:
    """G = Xs @ Xs^T for arbitrary (d, m). Zero-padding the sample axis is
    exact (padded columns contribute 0 to the outer-product sum)."""
    d, m = Xs.shape
    bd = bd or min(_k.DEFAULT_BD, pad.round_up(d, 8))
    bm = bm or min(_k.DEFAULT_BM, pad.round_up(m, 128))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    dp, mp = pad.round_up(d, bd), pad.round_up(m, bm)
    Xp = pad.pad_dims(Xs.astype(jnp.float32), {0: dp, 1: mp})
    G = _k.gram(Xp, bd=bd, bm=bm, interpret=interpret)
    return pad.unpad_dims(G, {0: d, 1: d})


def _gram_xla(Xs: jax.Array, *, bd=None, bm=None, interpret=None) -> jax.Array:
    del bd, bm, interpret                       # pallas-only tunables
    return _ref.gram(Xs)


def _gram_fwd(Xs, **kw):
    return gram(Xs, **kw), Xs


def _gram_bwd(Xs, dG, **kw):
    dXs = jnp.dot(dG + dG.T, Xs.astype(dG.dtype),
                  preferred_element_type=jnp.float32)
    return (dXs.astype(Xs.dtype),)


# ------------------------------------------------------------ registry ----

def _make_inputs(shape, dtype=jnp.float32):
    d, m = shape
    Xs = jax.random.normal(jax.random.PRNGKey(0), (d, m), dtype)
    return (Xs,), {}


def _candidates(backend, shape):
    # the (bd, bd) output tile must be lane-aligned (bd % 128 == 0) or span
    # the whole padded d, so the only feature tile is the default one
    if backend != "pallas":
        return []
    d, m = shape
    return [dict(bm=bm) for bm in (128, 512) if bm <= pad.round_up(m, 128)]


registry.describe("gram", shape_of=lambda Xs, **kw: tuple(Xs.shape),
                  make_inputs=_make_inputs, candidates=_candidates)
registry.register("gram", "pallas", tunables=("bd", "bm"),
                  vjp=(_gram_fwd, _gram_bwd))(gram)
registry.register("gram", "xla")(_gram_xla)
