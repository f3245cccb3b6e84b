"""Pallas TPU kernel: fused proximal-gradient step(s) with VMEM-resident Gram.

The paper's inner loop (Alg. IV lines 13-16) runs Q ISTA iterations against a
FIXED d x d Gram block. On TPU the win over XLA is structural: H is loaded
HBM->VMEM once and all Q (matvec + prox) iterations run out of VMEM with
zero intermediate HBM traffic — the loop becomes MXU-latency-bound rather
than HBM-bandwidth-bound. XLA's fori_loop keeps z in HBM between iterations
(2*d*4B/iter round-trips) and cannot pin H in VMEM across iterations.

Layout: vectors are (d, 1) tiles (TPU needs >=2D); the scalar parameters ride
as one (5, 1) tile ``[t; lam; mu; lo; hi]``; the element-wise prox ``variant``
is a static kernel parameter, so each variant compiles its own branch-free
body (see prox_step/ref.py for the variant table). The full H (d x d fp32)
must fit VMEM => d <= ops.VMEM_MAX_D (the registry routes larger d to the
XLA path — the paper's d is 8..54, linear probes go to ~1k). With grid=() the
default BlockSpec maps whole operands into VMEM, which is exactly the intent.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _shrink(x, thresh):
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - thresh, 0.0)


def _prox(x, scal, variant: str):
    t, lam, mu, lo, hi = (scal[i, 0] for i in range(5))
    if variant == "l1":
        return _shrink(x, lam * t)
    if variant == "elastic_net":
        return _shrink(x, lam * t) / (1.0 + mu * t)
    if variant == "box":
        return jnp.clip(x, lo, hi)
    if variant == "none":
        return x
    raise ValueError(f"unknown prox variant {variant!r}")


def _matvec(G, z):
    return jax.lax.dot_general(G, z, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _prox_loop_kernel(G_ref, R_ref, z_ref, scal_ref, o_ref, *, Q: int,
                      variant: str):
    G = G_ref[...]            # (d, d), VMEM-resident across all Q iterations
    R = R_ref[...]            # (d, 1)
    scal = scal_ref[...]      # (5, 1): [t; lam; mu; lo; hi]
    t = scal[0, 0]

    def body(q, z):
        return _prox(z - t * (_matvec(G, z) - R), scal, variant)

    o_ref[...] = jax.lax.fori_loop(0, Q, body, z_ref[...])


def _prox_step_kernel(G_ref, R_ref, v_ref, scal_ref, o_ref, *, variant: str):
    scal = scal_ref[...]
    t = scal[0, 0]
    v = v_ref[...]
    o_ref[...] = _prox(v - t * (_matvec(G_ref[...], v) - R_ref[...]),
                       scal, variant)


@functools.partial(jax.jit, static_argnames=("Q", "variant", "interpret"))
def prox_loop(G: jax.Array, R: jax.Array, z0: jax.Array, scal: jax.Array,
              *, Q: int, variant: str = "l1",
              interpret: bool = True) -> jax.Array:
    """z_Q after Q fused prox-gradient iterations. G (d,d), R/z0 (d,1),
    scal (5,1)=[t;lam;mu;lo;hi]."""
    d = G.shape[0]
    return pl.pallas_call(
        functools.partial(_prox_loop_kernel, Q=Q, variant=variant),
        out_shape=jax.ShapeDtypeStruct((d, 1), jnp.float32),
        interpret=interpret,
    )(G, R, z0, scal)


@functools.partial(jax.jit, static_argnames=("variant", "interpret"))
def prox_step(G: jax.Array, R: jax.Array, v: jax.Array, scal: jax.Array,
              *, variant: str = "l1", interpret: bool = True) -> jax.Array:
    """One fused step prox(v - t (G v - R)). Shapes as in prox_loop."""
    d = G.shape[0]
    return pl.pallas_call(
        functools.partial(_prox_step_kernel, variant=variant),
        out_shape=jax.ShapeDtypeStruct((d, 1), jnp.float32),
        interpret=interpret,
    )(G, R, v, scal)
