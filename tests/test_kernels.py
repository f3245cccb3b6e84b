"""Per-kernel shape/dtype sweeps (pallas_call interpret=True on CPU vs the
pure-jnp ref.py oracle) + the kernel-registry suite: dispatch parity across
every registered op/backend, policy precedence, autotune cache plumbing, and
the deprecated-kwarg shims."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import registry
from repro.kernels.gram import ops as gram_ops, ref as gram_ref
from repro.kernels.prox_step import ops as prox_ops, ref as prox_ref
from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.ssd import ops as ssd_ops, ref as ssd_ref

KEY = jax.random.PRNGKey(0)


# ----------------------------------------------------------------- gram ----
@pytest.mark.parametrize("d,m", [(8, 64), (54, 1000), (64, 512), (130, 777),
                                 (256, 2048), (1, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_sweep(d, m, dtype):
    Xs = jax.random.normal(KEY, (d, m), dtype)
    got = gram_ops.gram(Xs)
    want = gram_ref.gram(Xs.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=m * 2e-2 if dtype == jnp.bfloat16 else
                               m * 1e-6)


@pytest.mark.parametrize("bd,bm", [(8, 128), (16, 256), (128, 512)])
def test_gram_block_shapes(bd, bm):
    Xs = jax.random.normal(KEY, (64, 512))
    got = gram_ops.gram(Xs, bd=min(bd, 64), bm=min(bm, 512))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(gram_ref.gram(Xs)), rtol=1e-5,
                               atol=1e-3)


# ------------------------------------------------------------ prox_step ----
@pytest.mark.parametrize("d", [8, 54, 100, 512])
@pytest.mark.parametrize("Q", [1, 3, 9])
def test_prox_loop_sweep(d, Q):
    ks = jax.random.split(KEY, 3)
    G = jax.random.normal(ks[0], (d, d))
    G = G @ G.T / d
    R = jax.random.normal(ks[1], (d,))
    z = jax.random.normal(ks[2], (d,))
    got = prox_ops.prox_loop(G, R, z, 0.05, 0.02, Q)
    want = prox_ref.prox_loop(G, R, z, 0.05, 0.02, Q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_prox_step_large_d_fallback():
    """Above the VMEM limit the registry's ``supports`` predicate is the one
    gate: it routes the call to the XLA impl, even under a pallas policy."""
    d = prox_ops.VMEM_MAX_D + 64     # exceeds VMEM budget -> XLA path
    ks = jax.random.split(KEY, 3)
    G = jax.random.normal(ks[0], (d, d)) / d
    R = jax.random.normal(ks[1], (d,))
    v = jax.random.normal(ks[2], (d,))
    with registry.use("pallas"):
        assert registry.select("prox_step", G, R, v, 0.1, 0.01).backend \
            == "xla"
        got = registry.dispatch("prox_step", G, R, v, 0.1, 0.01)
    want = prox_ref.prox_step(G, R, v, 0.1, 0.01)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


#: composite-prox variants with their scalar parameters (lam, mu, lo, hi)
PROX_VARIANTS = [("l1", (0.02, 0.0, 0.0, 0.0)),
                 ("elastic_net", (0.02, 0.5, 0.0, 0.0)),
                 ("box", (0.0, 0.0, -0.1, 0.4)),
                 ("none", (0.0, 0.0, 0.0, 0.0))]


@pytest.mark.parametrize("variant,scal", PROX_VARIANTS,
                         ids=[v for v, _ in PROX_VARIANTS])
@pytest.mark.parametrize("d", [7, 54, 129])   # odd, non-tile-multiple shapes
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_prox_variant_backend_parity(variant, scal, d, dtype):
    """Every prox variant: fused pallas path vs the pure-jnp oracle, f32 and
    bf16, at shapes that don't tile evenly."""
    lam, mu, lo, hi = scal
    ks = jax.random.split(KEY, 3)
    G = jax.random.normal(ks[0], (d, d), dtype)
    G = (G @ G.T / d).astype(dtype)
    R = jax.random.normal(ks[1], (d,), dtype)
    v = jax.random.normal(ks[2], (d,), dtype)
    got = prox_ops.prox_step(G, R, v, 0.1, lam, mu, lo, hi, variant=variant)
    want = prox_ref.prox_step(G.astype(jnp.float32), R.astype(jnp.float32),
                              v.astype(jnp.float32), 0.1, lam, mu, lo, hi,
                              variant=variant)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)

    got_l = prox_ops.prox_loop(G, R, v, 0.1, lam, 3, mu, lo, hi,
                               variant=variant)
    want_l = prox_ref.prox_loop(G.astype(jnp.float32),
                                R.astype(jnp.float32),
                                v.astype(jnp.float32), 0.1, lam, 3, mu, lo,
                                hi, variant=variant)
    np.testing.assert_allclose(np.asarray(got_l), np.asarray(want_l),
                               atol=tol)


def test_prox_variant_dispatch_kwargs_are_static():
    """mu/lo/hi/variant ride as kwargs through registry.dispatch — the
    custom-VJP wrapper binds them statically, so gradients flow through the
    positional primals under both backends."""
    d = 12
    ks = jax.random.split(KEY, 3)
    G = jax.random.normal(ks[0], (d, d))
    G = G @ G.T / d
    R = jax.random.normal(ks[1], (d,))
    v = jax.random.normal(ks[2], (d,))
    for backend in ("pallas", "xla"):
        with registry.use(backend):
            def loss(v_):
                out = registry.dispatch("prox_step", G, R, v_, 0.1, 0.02,
                                        mu=0.3, variant="elastic_net")
                return jnp.sum(out * out)
            g = jax.grad(loss)(v)
        assert np.isfinite(np.asarray(g)).all()


# ------------------------------------------------------- flash attention ---
@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D", [
    (2, 4, 2, 64, 64, 32),
    (1, 8, 2, 128, 128, 64),
    (2, 4, 4, 100, 100, 80),      # unaligned seq + head dim
    (1, 4, 2, 1, 128, 64),        # decode
    (1, 2, 1, 96, 160, 48),       # cross-window
    (1, 10, 5, 64, 64, 128),      # phi3-style head ratio
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, Hq, Hkv, Sq, Skv, D, causal):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D))
    k = jax.random.normal(ks[1], (B, Hkv, Skv, D))
    v = jax.random.normal(ks[2], (B, Hkv, Skv, D))
    got = fa_ops.flash_attention(q, k, v, causal=causal, bq=32, bk=32)
    want = fa_ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_dtypes(dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 4, 64, 32), dtype)
    k = jax.random.normal(ks[1], (1, 2, 64, 32), dtype)
    v = jax.random.normal(ks[2], (1, 2, 64, 32), dtype)
    got = fa_ops.flash_attention(q, k, v, bq=32, bk=32)
    want = fa_ref.attention(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_xla_chunked_attention_matches_ref():
    """The XLA train/prefill path (models.attention) against the oracle,
    including q-chunking and GQA."""
    from repro.models.attention import chunked_attention
    ks = jax.random.split(KEY, 3)
    B, Hq, Hkv, S, D = 2, 4, 2, 256, 32
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    got = chunked_attention(q, k, v, causal=True, chunk=64, q_chunk=64)
    want = fa_ref.attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3),
                            causal=True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_chunked_attention_kv_valid_len():
    from repro.models.attention import chunked_attention
    ks = jax.random.split(KEY, 3)
    B, H, S, D = 1, 2, 64, 16
    q = jax.random.normal(ks[0], (B, 1, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    valid = 37
    got = chunked_attention(q, k, v, causal=False, chunk=16,
                            kv_valid_len=valid)
    want = fa_ref.attention(q.transpose(0, 2, 1, 3),
                            k[:, :valid].transpose(0, 2, 1, 3),
                            v[:, :valid].transpose(0, 2, 1, 3), causal=False)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want.transpose(0, 2, 1, 3)),
                               atol=2e-5)


# ------------------------------------------------------------------ ssd ----
@pytest.mark.parametrize("Bt,S,H,P,N,chunk", [
    (2, 128, 4, 16, 8, 32),
    (1, 100, 2, 8, 16, 32),       # padded seq
    (2, 64, 3, 16, 4, 64),
    (1, 256, 8, 64, 128, 64),     # mamba2-realistic head
])
def test_ssd_kernel_sweep(Bt, S, H, P, N, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (Bt, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, S, H))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    B = jax.random.normal(ks[3], (Bt, S, N))
    C = jax.random.normal(ks[4], (Bt, S, N))
    y0, h0 = ssd_ref.ssd_sequential(x, dt, A, B, C)
    with registry.use("pallas"):
        y1, h1 = ssd_ops.ssd(x, dt, A, B, C, chunk=chunk)
    with registry.use("xla"):
        y2, h2 = ssd_ops.ssd(x, dt, A, B, C, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=5e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), atol=5e-4)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y0), atol=5e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h0), atol=5e-4)


def test_ssd_decode_trajectory():
    Bt, S, H, P, N = 2, 24, 4, 16, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (Bt, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    B = jax.random.normal(ks[3], (Bt, S, N))
    C = jax.random.normal(ks[4], (Bt, S, N))
    y_seq, h_seq = ssd_ref.ssd_sequential(x, dt, A, B, C)
    h = jnp.zeros((Bt, H, P, N))
    for t in range(S):
        y_t, h = ssd_ops.ssd_decode_step(x[:, t], dt[:, t], A, B[:, t],
                                         C[:, t], h)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_seq), atol=1e-4)
    np.testing.assert_allclose(np.asarray(y_t), np.asarray(y_seq[:, -1]),
                               atol=1e-4)


# ------------------------------------------------------- kernel registry ---

EXPECTED_OPS = {"gram", "prox_step", "prox_loop", "flash_attention", "ssd"}

#: make_inputs shape descriptors per op, including odd non-tile-multiple
#: sizes (13, 33, 37, 65, 77, 130 ...) that exercise every pad/unpad path
PARITY_SHAPES = {
    "gram": [(8, 64), (13, 77), (130, 777)],
    "prox_step": [(54,), (130,)],
    "prox_loop": [(54,)],
    "flash_attention": [(2, 33, 4, 16, 33, 2),     # odd seq, GQA
                        (1, 1, 4, 40, 65, 2)],     # decode vs odd kv window
    "ssd": [(1, 37, 2, 8, 4), (2, 64, 3, 16, 4)],
}

_TOL = {  # (f32 kwargs, bf16 kwargs); bf16 inputs lose mantissa up front
    "gram": (dict(atol=1e-3, rtol=1e-5), dict(atol=16.0, rtol=2e-2)),
    "prox_step": (dict(atol=1e-5), dict(atol=0.5, rtol=5e-2)),
    "prox_loop": (dict(atol=1e-5), dict(atol=0.5, rtol=5e-2)),
    "flash_attention": (dict(atol=2e-5), dict(atol=2e-2)),
    "ssd": (dict(atol=5e-4), dict(atol=0.5, rtol=5e-2)),
}


def test_registry_table_covers_expected_ops():
    assert EXPECTED_OPS <= set(registry.ops())
    for op in EXPECTED_OPS:
        assert set(registry.backends_of(op)) == {"pallas", "xla"}
        assert registry.get_op(op).make_inputs is not None


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize(
    "op,shape", [(op, shape) for op, shapes in sorted(PARITY_SHAPES.items())
                 for shape in shapes])
def test_registry_backend_parity(op, shape, dtype):
    """Every registered backend of every op agrees with the xla reference,
    through the same dispatch call sites production code uses."""
    args, kw = registry.get_op(op).make_inputs(shape, dtype=dtype)
    with registry.use("xla"):
        want = registry.dispatch(op, *args, **kw)
    tol = _TOL[op][0 if dtype == jnp.float32 else 1]
    for backend in registry.backends_of(op):
        if backend == "xla":
            continue
        with registry.use(backend):
            got = registry.dispatch(op, *args, **kw)
        jax.tree.map(
            lambda g, w: np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(w, np.float32), **tol),
            got, want)


def test_registry_use_overrides_env_and_restores(monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "xla")
    assert registry.policy() == "xla"
    assert registry.resolved_backend() == "xla"
    with registry.use("pallas"):
        assert registry.resolved_backend() == "pallas"
        with registry.use("ref"):                 # alias for xla
            assert registry.resolved_backend() == "xla"
        assert registry.resolved_backend() == "pallas"
    assert registry.resolved_backend() == "xla"   # env restored
    monkeypatch.setenv("REPRO_BACKEND", "pallas")
    assert registry.resolved_backend() == "pallas"


def test_registry_policy_precedence(monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "pallas")
    try:
        registry.set_backend("xla")               # process beats env
        assert registry.resolved_backend() == "xla"
        with registry.use("pallas"):              # context beats process
            assert registry.resolved_backend() == "pallas"
        assert registry.resolved_backend() == "xla"
    finally:
        registry.set_backend(None)
    assert registry.resolved_backend() == "pallas"


def test_registry_use_restores_on_exception():
    before = registry.policy()
    with pytest.raises(RuntimeError):
        with registry.use("pallas"):
            raise RuntimeError("boom")
    assert registry.policy() == before


def test_registry_rejects_unknown_backend():
    with pytest.raises(ValueError):
        with registry.use("cuda"):
            pass
    with pytest.raises(ValueError):
        registry.set_backend("tensorrt")


def test_forced_pallas_falls_back_for_dynamic_mask():
    """flash_attention's pallas impl only does static masks; a dynamic
    kv_valid_len under a forced pallas policy must take the XLA path (with
    a warning) and match it bitwise."""
    from repro.models.attention import attention
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 1, 4, 16))
    k = jax.random.normal(ks[1], (2, 48, 2, 16))
    v = jax.random.normal(ks[2], (2, 48, 2, 16))
    valid = jnp.asarray([17, 33], jnp.int32)
    with registry.use("pallas"):
        got = attention(q, k, v, causal=False, kv_valid_len=valid, chunk=16)
    with registry.use("xla"):
        want = attention(q, k, v, causal=False, kv_valid_len=valid, chunk=16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_grad_safe_is_narrow_vjp_less_guard():
    """grad_safe() only reroutes impls WITHOUT a vjp; every stock pallas
    kernel now carries one, so it passes through unchanged under the guard
    while a synthetic VJP-less impl still falls back to xla."""
    name = "_test_nodiff_op"
    registry.register(name, "pallas", differentiable=False)(lambda x: x * 2)
    registry.register(name, "xla")(lambda x: x * 2)
    try:
        with registry.use("pallas"):
            assert registry.select(name, 1.0).backend == "pallas"
            with registry.grad_safe():
                assert registry.select(name, 1.0).backend == "xla"
    finally:
        registry._OPS.pop(name, None)

    # the stock ops keep their pallas impls under grad_safe
    with registry.use("pallas"), registry.grad_safe():
        for op in sorted(EXPECTED_OPS):
            args, kw = registry.get_op(op).make_inputs(PARITY_SHAPES[op][0])
            impl = registry.select(op, *args, **kw)
            assert impl.backend == "pallas" and impl.vjp is not None, op


def test_vjp_requires_differentiable():
    with pytest.raises(ValueError, match="differentiable"):
        registry.register("_test_bad_op", "pallas", differentiable=False,
                          vjp=(lambda *a: None, lambda *a: None))(lambda x: x)
    registry._OPS.pop("_test_bad_op", None)


# --------------------------------------------------- grad parity (VJPs) ----

#: per-op grad tolerances vs the XLA autodiff reference; looser than the
#: forward _TOL (cotangents compound the reassociation error)
_GRAD_TOL = {
    "gram": (dict(atol=1e-2, rtol=1e-4), dict(atol=32.0, rtol=5e-2)),
    "prox_step": (dict(atol=1e-4), dict(atol=0.5, rtol=5e-2)),
    "prox_loop": (dict(atol=1e-4), dict(atol=0.5, rtol=5e-2)),
    "flash_attention": (dict(atol=5e-4), dict(atol=0.5, rtol=5e-2)),
    # bf16 ssd: the xla ref folds x*dt at bf16 before upcasting, the kernel
    # folds in f32 — a genuine one-ulp forward divergence the grads inherit
    "ssd": (dict(atol=5e-3, rtol=1e-3), dict(atol=2.0, rtol=0.1)),
}


def _dispatch_loss(op, kw):
    def loss(*args):
        out = registry.dispatch(op, *args, **kw)
        return sum((jnp.asarray(leaf).astype(jnp.float32) ** 2).sum()
                   for leaf in jax.tree.leaves(out))
    return loss


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize(
    "op,shape", [(op, shape) for op, shapes in sorted(PARITY_SHAPES.items())
                 for shape in shapes])
def test_registry_grad_parity(op, shape, dtype):
    """jax.grad through every pallas custom VJP matches the XLA autodiff
    gradients, through the same dispatch call sites production uses —
    including odd (padded) shapes, GQA group > 1, and bf16."""
    args, kw = registry.get_op(op).make_inputs(shape, dtype=dtype)
    if op == "prox_loop":
        kw = dict(kw)                  # Q must ride as a static kwarg
    argnums = registry.grad_argnums(args)
    loss = _dispatch_loss(op, kw)
    with registry.use("pallas"):
        impl = registry.select(op, *args, **kw)
        assert impl.backend == "pallas", \
            f"{op}{shape}: silent fallback defeats the parity check"
        got = jax.grad(loss, argnums)(*args)
    with registry.use("xla"):
        want = jax.grad(loss, argnums)(*args)
    tol = _GRAD_TOL[op][0 if dtype == jnp.float32 else 1]
    for i, g, w in zip(argnums, got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), **tol,
                                   err_msg=f"{op}{shape} darg{i}")
        assert g.dtype == args[i].dtype, f"{op} darg{i} cotangent dtype"


def test_prox_grad_with_explicit_interpret_kwarg():
    """Regression: the recompute VJP forwards kwargs to ref.py, which takes
    no ``interpret`` — differentiating a dispatch that pins it used to raise
    TypeError at trace time."""
    (G, R, v, t, lam), _ = registry.get_op("prox_step").make_inputs((16,))
    with registry.use("pallas"):
        g = jax.grad(lambda v: (registry.dispatch(
            "prox_step", G, R, v, t, lam, interpret=True) ** 2).sum())(v)
    assert np.isfinite(np.asarray(g)).all()


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_grad_noncausal_and_decode_window(causal):
    """Grad parity for the masking variants the sweep above fixes to
    causal=True: non-causal, and the right-aligned decode window."""
    op = registry.get_op("flash_attention")
    args, kw = op.make_inputs((1, 40, 4, 16, 72, 2))     # Sq < Skv
    kw = dict(kw, causal=causal)
    loss = _dispatch_loss("flash_attention", kw)
    with registry.use("pallas"):
        got = jax.grad(loss, (0, 1, 2))(*args)
    with registry.use("xla"):
        want = jax.grad(loss, (0, 1, 2))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-4)


def test_pallas_backward_selected_under_grad_safe():
    """Acceptance: under REPRO_BACKEND=pallas, loss_fn-style dispatch (inside
    grad_safe) selects the pallas impls of flash_attention and ssd — no
    silent XLA detour — and differentiating them runs their custom VJPs."""
    fa_args, fa_kw = registry.get_op("flash_attention").make_inputs(
        (1, 32, 4, 16, 32, 2))
    ssd_args, ssd_kw = registry.get_op("ssd").make_inputs((1, 32, 2, 8, 4))
    with registry.use("pallas"), registry.grad_safe():
        for op, args, kw in [("flash_attention", fa_args, fa_kw),
                             ("ssd", ssd_args, ssd_kw)]:
            impl = registry.select(op, *args, **kw)
            assert impl.backend == "pallas", op
            assert impl.differentiable and impl.vjp is not None, op
        g = jax.grad(_dispatch_loss("flash_attention", fa_kw))(*fa_args)
        assert np.isfinite(np.asarray(g, np.float32)).all()


def test_loss_fn_grads_match_across_backends():
    """End to end: jax.grad(loss_fn) under forced pallas equals the xla
    gradients within tolerance for an attention arch and an SSM arch."""
    from repro.configs import ARCHS, smoke_config
    from repro.models import init_params, loss_fn
    for arch in ("internlm2-1.8b", "mamba2-780m"):
        cfg = smoke_config(ARCHS[arch])
        params = init_params(cfg, KEY)
        batch = dict(tokens=jax.random.randint(KEY, (2, 16), 0, cfg.vocab),
                     labels=jax.random.randint(KEY, (2, 16), 0, cfg.vocab))
        grads = {}
        for backend in ("pallas", "xla"):
            with registry.use(backend):
                grads[backend] = jax.grad(
                    lambda p: loss_fn(p, cfg, batch))(params)
        jax.tree.map(
            lambda g, w: np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(w, np.float32),
                atol=5e-2, rtol=5e-2, err_msg=arch),
            grads["pallas"], grads["xla"])


def test_autotune_writes_and_dispatch_consumes_cache(tmp_path, monkeypatch):
    cache = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache))
    registry.reload_tuned()
    try:
        results = registry.autotune("gram", [(16, 64)], backends=["pallas"],
                                    iters=1, warmup=1)
        assert cache.exists()
        on_disk = json.loads(cache.read_text())
        assert results and set(results) <= set(on_disk)
        (key, entry), = results.items()
        assert key.startswith("gram|pallas|16x64|")
        assert set(entry["params"]) <= {"bd", "bm"} and entry["us"] > 0
        # dispatch picks the tuned block sizes up (and stays correct)
        Xs = jax.random.normal(KEY, (16, 64))
        with registry.use("pallas"):
            got = registry.dispatch("gram", Xs)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(gram_ref.gram(Xs)), atol=1e-4)
        # explicit kwargs beat the cache
        with registry.use("pallas"):
            got2 = registry.dispatch("gram", Xs, bd=8, bm=128)
        np.testing.assert_allclose(np.asarray(got2),
                                   np.asarray(gram_ref.gram(Xs)), atol=1e-4)
    finally:
        registry.reload_tuned()


def test_autotune_save_merges_concurrent_writers(tmp_path, monkeypatch):
    """Regression: autotune(save=True) used to dump only its own in-memory
    table, clobbering entries another process wrote between our load and our
    save (the CI matrix races exactly like this). The save must re-read and
    merge the on-disk file under the atomic replace."""
    cache = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache))
    registry.reload_tuned()
    try:
        first = registry.autotune("gram", [(16, 64)], backends=["pallas"],
                                  iters=1, warmup=0)
        assert first
        (first_key,) = first
        # a concurrent process lands a new entry AND re-tunes our key on
        # disk after our load
        foreign_key = "gram|pallas|512x4096|tpu_v5e"
        on_disk = json.loads(cache.read_text())
        on_disk[foreign_key] = {"params": {"bd": 128, "bm": 512}, "us": 1.0}
        on_disk[first_key] = {"params": {"bd": 8, "bm": 128}, "us": 7.77}
        cache.write_text(json.dumps(on_disk))
        second = registry.autotune("gram", [(8, 128)], backends=["pallas"],
                                   iters=1, warmup=0)
        merged = json.loads(cache.read_text())
        assert foreign_key in merged, "concurrent writer's entry clobbered"
        assert set(first) | set(second) <= set(merged)
        # the concurrent re-tune of a key we only LOADED must not be
        # reverted by our stale in-memory copy
        assert merged[first_key]["us"] == 7.77, "lost update on shared key"
    finally:
        registry.reload_tuned()


def test_autotune_never_persists_unknown_device_kind(tmp_path, monkeypatch):
    """Regression: a pre-backend-init 'unknown' device kind used to get
    baked into persisted keys, which could never match once the real device
    resolved. Unknown-keyed entries stay process-local; the kind is resolved
    lazily at lookup."""
    cache = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache))
    monkeypatch.setattr(registry, "_device_kind",
                        lambda: registry.UNKNOWN_DEVICE)
    registry.reload_tuned()
    try:
        results = registry.autotune("gram", [(16, 64)], backends=["pallas"],
                                    iters=1, warmup=0)
        assert results and all(k.endswith("|unknown") for k in results)
        assert not cache.exists() or not any(
            k.endswith("|unknown") for k in json.loads(cache.read_text()))
        # in-memory lookups still work while the kind stays unresolved
        Xs = jax.random.normal(KEY, (16, 64))
        with registry.use("pallas"):
            got = registry.dispatch("gram", Xs)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(gram_ref.gram(Xs)), atol=1e-4)
        # legacy unknown entries already on disk are dropped on load
        cache.write_text(json.dumps(
            {"gram|pallas|9x9|unknown": {"params": {}, "us": 1.0}}))
        registry.reload_tuned()
        assert "gram|pallas|9x9|unknown" not in registry._tuned()
    finally:
        registry.reload_tuned()


def test_autotune_grad_mode_tunes_backward_blocks(tmp_path, monkeypatch):
    """autotune(grad=True) sweeps bwd_tunables, keys entries under the
    '<op>+bwd' namespace, and dispatch feeds them to the backward only."""
    cache = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache))
    registry.reload_tuned()
    try:
        results = registry.autotune("flash_attention", [(1, 32, 4, 16, 32, 2)],
                                    backends=["pallas"], iters=1, warmup=0,
                                    grad=True)
        (key, entry), = results.items()
        assert key.startswith("flash_attention+bwd|pallas|")
        assert set(entry["params"]) <= {"bq_bwd", "bk_bwd"}
        # a differentiated dispatch picks the tuned backward blocks up and
        # stays correct against the xla gradients
        op = registry.get_op("flash_attention")
        args, kw = op.make_inputs((1, 32, 4, 16, 32, 2))
        loss = _dispatch_loss("flash_attention", kw)
        with registry.use("pallas"):
            got = jax.grad(loss)(*args)
        with registry.use("xla"):
            want = jax.grad(loss)(*args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-4)
    finally:
        registry.reload_tuned()


def test_solver_trajectories_ulp_identical_under_each_backend():
    """CA-vs-classical parity is backend-independent: both solvers pin the
    same resolved policy, so the ~1-ulp identity (same tolerance as
    tests/test_core.py — vmapped Gram blocks may reassociate) holds under
    forced pallas exactly as under xla."""
    from repro.core import (LassoProblem, SolverConfig, sfista, ca_sfista,
                            spnm, ca_spnm)
    ks = jax.random.split(KEY, 2)
    X = jax.random.normal(ks[0], (8, 96))
    w_true = jnp.zeros((8,)).at[:3].set(1.0)
    y = X.T @ w_true
    problem = LassoProblem(X=X, y=y, lam=0.05)
    cfg = SolverConfig(T=16, k=4, b=0.25, Q=3)
    for backend in ("xla", "pallas"):
        with registry.use(backend):
            np.testing.assert_allclose(
                np.asarray(sfista(problem, cfg, KEY)),
                np.asarray(ca_sfista(problem, cfg, KEY)), atol=5e-6, rtol=0,
                err_msg=f"sfista vs ca_sfista diverged under {backend}")
            np.testing.assert_allclose(
                np.asarray(spnm(problem, cfg, KEY)),
                np.asarray(ca_spnm(problem, cfg, KEY)), atol=5e-6, rtol=0,
                err_msg=f"spnm vs ca_spnm diverged under {backend}")


def test_ca_solver_validates_T_divisible_by_k():
    from repro.core import LassoProblem, SolverConfig, ca_sfista, ca_spnm
    with pytest.raises(ValueError, match="multiple of k"):
        SolverConfig(T=100, k=8)                  # caught at construction
    # a cfg mutated past __post_init__ still gets a clear solver-side error
    cfg = SolverConfig(T=96, k=8)
    object.__setattr__(cfg, "k", 7)
    X = jax.random.normal(KEY, (4, 32))
    problem = LassoProblem(X=X, y=X.T @ jnp.ones((4,)), lam=0.1)
    for solver in (ca_sfista, ca_spnm):
        with pytest.raises(ValueError, match="divisible by cfg.k"):
            solver(problem, cfg, KEY)


def test_shared_pad_helpers():
    from repro.kernels import pad
    assert pad.round_up(1, 8) == 8 and pad.round_up(16, 8) == 16
    x = jnp.ones((3, 5))
    p = pad.pad_dims(x, {0: 8, 1: 5})
    assert p.shape == (8, 5) and float(p[3:].sum()) == 0.0
    assert pad.pad_dims(x, {0: 3}) is x            # no-op fast path
    assert pad.pad_to_multiple(x, 1, 4).shape == (3, 8)
    np.testing.assert_array_equal(
        np.asarray(pad.unpad_dims(p, {0: 3})), np.asarray(x))
    with pytest.raises(ValueError):
        pad.pad_dims(x, {0: 2})
