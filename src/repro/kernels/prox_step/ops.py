"""Public wrappers for the fused prox kernels: shape adaptation ((d,) vectors
-> (d,1) tiles) and registration.

Registers the ``prox_step`` / ``prox_loop`` ops: ``pallas`` keeps the Gram
VMEM-resident across the fused update(s) (per-call ``supports`` rejects
d > VMEM_MAX_D), ``xla`` is the pure-jnp path that is bit-identical to the
solvers' historical inline update.

Both ops take the composite-prox parameterization ``(t, lam, mu, lo, hi)``
plus a static ``variant`` keyword selecting the element-wise prox (``l1`` —
the default and the historical behavior — ``elastic_net``, ``box``,
``none``; see ref.py). Solver call sites pass ``variant`` (and the inert
scalars) as KEYWORDS: the custom-VJP wiring binds kwargs statically, so each
problem's prox compiles its own branch-free kernel and the recompute backward
differentiates only the positional primals.

Both pallas impls carry a recompute-based custom VJP that differentiates the
prox subgradient of the *ref.py* path (``jax.vjp`` over the jnp oracle,
which is arithmetically the same update) — the forward stays fused in VMEM,
the backward is a couple of matvecs. Differentiated call sites must pass
``prox_loop``'s ``Q`` as a keyword: kwargs are bound statically by the
custom-VJP wiring, while a positional ``Q`` becomes a traced primal and
``fori_loop`` with a traced bound has no reverse-mode rule."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.prox_step import kernel as _k
from repro.kernels.prox_step import ref as _ref

#: largest d whose fused loop fits v5e VMEM: the (d, d) f32 Gram plus the
#: (d, 1) vectors, each padded to (d, 128) lane tiles. The chip's compiler
#: accepts ``prox_loop`` at d=1280 and runs out of VMEM at 1408.
VMEM_MAX_D = 1280


def _prep(G, R, v, t, lam, mu=0.0, lo=0.0, hi=0.0):
    G = G.astype(jnp.float32)
    R = R.reshape(-1, 1).astype(jnp.float32)
    v = v.reshape(-1, 1).astype(jnp.float32)
    scal = jnp.stack([jnp.asarray(s, jnp.float32)
                      for s in (t, lam, mu, lo, hi)]).reshape(5, 1)
    return G, R, v, scal


def _interpret_default():
    return jax.default_backend() != "tpu"


def _fits_vmem(G, *_args, **_kw) -> bool:
    return G.shape[0] <= VMEM_MAX_D


def prox_step(G, R, v, t, lam, mu=0.0, lo=0.0, hi=0.0, *, variant="l1",
              interpret: bool | None = None):
    """w+ = prox(v - t*(G v - R)); accepts (d,) vectors."""
    interpret = _interpret_default() if interpret is None else interpret
    Gp, Rp, vp, scal = _prep(G, R, v, t, lam, mu, lo, hi)
    return _k.prox_step(Gp, Rp, vp, scal, variant=variant,
                        interpret=interpret).reshape(v.shape)


def prox_loop(G, R, z0, t, lam, Q: int, mu=0.0, lo=0.0, hi=0.0, *,
              variant="l1", interpret: bool | None = None):
    """z_Q from Q fused warm-started prox-gradient iterations; accepts (d,)
    vectors."""
    interpret = _interpret_default() if interpret is None else interpret
    Gp, Rp, zp, scal = _prep(G, R, z0, t, lam, mu, lo, hi)
    return _k.prox_loop(Gp, Rp, zp, scal, Q=Q, variant=variant,
                        interpret=interpret).reshape(z0.shape)


def _recompute_vjp(fused_fn, ref_fn):
    """(fwd, bwd) pair: pallas forward, backward = jax.vjp of the ref path
    over the saved primal inputs (prox subgradient semantics)."""
    def fwd(*args, **kw):
        return fused_fn(*args, **kw), args

    def bwd(res, g, **kw):
        kw.pop("interpret", None)              # pallas-only; ref.py takes none
        out, pullback = jax.vjp(functools.partial(ref_fn, **kw), *res)
        # the fused forward always emits fp32; the ref path follows the
        # input dtype — align the cotangent before pulling it back
        return pullback(g.astype(out.dtype))
    return fwd, bwd


# ------------------------------------------------------------ registry ----

def _make_step_inputs(shape, dtype=jnp.float32):
    d, = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    G = jax.random.normal(ks[0], (d, d), dtype)
    G = (G @ G.T) / d
    R = jax.random.normal(ks[1], (d,), dtype)
    v = jax.random.normal(ks[2], (d,), dtype)
    return (G, R, v, 0.05, 0.02), {}


def _make_loop_inputs(shape, dtype=jnp.float32):
    # Q rides in kwargs: it is a static (trace-time) arg of the pallas jit,
    # so benchmark/autotune harnesses must not trace over it
    args, kw = _make_step_inputs(shape, dtype)
    return args, dict(kw, Q=3)


registry.describe("prox_step", shape_of=lambda G, *a, **kw: tuple(G.shape),
                  make_inputs=_make_step_inputs)
registry.describe("prox_loop", shape_of=lambda G, *a, **kw: tuple(G.shape),
                  make_inputs=_make_loop_inputs)
registry.register("prox_step", "pallas", supports=_fits_vmem,
                  vjp=_recompute_vjp(prox_step, _ref.prox_step))(prox_step)
registry.register("prox_step", "xla")(_ref.prox_step)
registry.register("prox_loop", "pallas", supports=_fits_vmem,
                  vjp=_recompute_vjp(prox_loop, _ref.prox_loop))(prox_loop)
registry.register("prox_loop", "xla")(_ref.prox_loop)
