"""Property-based tests (hypothesis) for the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st
import hypothesis.extra.numpy as hnp

from repro.core.soft_threshold import soft_threshold, prox_grad_step, \
    fista_momentum, prox_elem, moreau_dual_prox
from repro.core.cost_model import CostModel, MachineParams
from repro.optim.compression import (topk_compress, topk_decompress,
                                     int8_compress, int8_decompress)
from repro.dist.sharding import fit_spec
from jax.sharding import PartitionSpec as P

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")

floats = hnp.arrays(np.float32, st.integers(1, 64),
                    elements=st.floats(-100, 100, width=32))


# ---------------------------------------------------------- prox operator --
@given(floats, st.floats(0, 10))
def test_soft_threshold_shrinks(w, lam):
    out = np.asarray(soft_threshold(jnp.asarray(w), lam))
    assert (np.abs(out) <= np.abs(w) + 1e-6).all()          # non-expansive
    assert (np.sign(out) * np.sign(w) >= 0).all()           # sign-preserving
    assert (out[np.abs(w) <= lam] == 0).all()               # kill small coords


@given(floats, floats, st.floats(1e-3, 1.0))
def test_soft_threshold_is_prox(w, v, lam):
    """S_lam(v) minimizes (1/2)||x-v||^2 + lam||x||_1 — compare against any
    other candidate point (here: w)."""
    v_, w_ = jnp.asarray(v), jnp.asarray(np.resize(w, v.shape))
    s = soft_threshold(v_, lam)
    def obj(x):
        return 0.5 * jnp.sum((x - v_) ** 2) + lam * jnp.sum(jnp.abs(x))
    assert float(obj(s)) <= float(obj(w_)) + 1e-4


@given(st.integers(1, 10_000))
def test_fista_momentum_bounds(j):
    m = float(fista_momentum(jnp.asarray(j)))
    assert 0.0 <= m < 1.0
    if j >= 3:
        assert abs(m - (j - 2) / j) < 1e-6   # fp32 evaluation


def test_prox_fixed_point_is_lasso_optimum():
    """w* = S_{lam t}(w* - t grad(w*)) iff w* solves LASSO (optimality of the
    proximal operator); verified on a solved instance."""
    from repro.core import solve_reference
    from repro.core.problem import lipschitz_step
    from repro.data import make_lasso_data
    prob, _ = make_lasso_data(jax.random.PRNGKey(1), d=16, n=512)
    w = solve_reference(prob, iters=6000)
    t = lipschitz_step(prob.X)
    grad = prob.X @ (prob.X.T @ w - prob.y) / prob.n
    w2 = soft_threshold(w - t * grad, prob.lam * t)
    np.testing.assert_allclose(np.asarray(w2), np.asarray(w), atol=2e-5)


# --------------------------------------------------- composite prox family --
_VARIANTS = st.sampled_from(["l1", "elastic_net", "box", "none"])


def _prox_kwargs(variant, lam, mu, lo, hi):
    lo, hi = min(lo, hi), max(lo, hi)
    return dict(variant=variant, lam=lam, mu=mu, lo=lo, hi=hi)


@given(floats, floats, st.floats(1e-3, 1.0), st.floats(0, 5), st.floats(0, 5),
       st.floats(-3, 3, width=32), st.floats(-3, 3, width=32), _VARIANTS)
def test_prox_elem_nonexpansive(x, y, t, lam, mu, lo, hi, variant):
    """Every variant is the prox of a convex g, hence 1-Lipschitz
    (elementwise, since all variants are separable)."""
    kw = _prox_kwargs(variant, lam, mu, lo, hi)
    xj, yj = jnp.asarray(x), jnp.asarray(np.resize(y, x.shape))
    px = np.asarray(prox_elem(xj, t, **kw))
    py = np.asarray(prox_elem(yj, t, **kw))
    assert (np.abs(px - py) <= np.abs(x - np.resize(y, x.shape)) + 1e-5).all()


@given(floats, floats, st.floats(1e-3, 1.0), st.floats(0, 5), st.floats(0, 5),
       st.floats(-3, 3, width=32), st.floats(-3, 3, width=32), _VARIANTS)
def test_prox_elem_is_subproblem_minimizer(v, w, t, lam, mu, lo, hi, variant):
    """prox_{t g}(v) minimizes (1/2)||x-v||^2 + t g(x) — compare against any
    other candidate point (here: w, projected into the domain for box)."""
    kw = _prox_kwargs(variant, lam, mu, lo, hi)
    v_ = jnp.asarray(v)
    w_ = jnp.asarray(np.resize(w, v.shape))
    if variant == "box":
        w_ = jnp.clip(w_, kw["lo"], kw["hi"])   # candidate must be feasible

    def g(x):
        if variant == "l1":
            return kw["lam"] * jnp.sum(jnp.abs(x))
        if variant == "elastic_net":
            return (kw["lam"] * jnp.sum(jnp.abs(x))
                    + 0.5 * kw["mu"] * jnp.sum(x * x))
        return 0.0   # box handled via feasibility; none has g = 0

    def obj(x):
        return 0.5 * jnp.sum((x - v_) ** 2) + t * g(x)

    p = prox_elem(v_, t, **kw)
    if variant == "box":
        assert float(p.min()) >= kw["lo"] - 1e-6
        assert float(p.max()) <= kw["hi"] + 1e-6
    assert float(obj(p)) <= float(obj(w_)) + 1e-3


@given(floats, st.floats(1e-2, 10.0), st.floats(0, 5))
def test_moreau_dual_prox_l1_is_ball_projection(x, sigma, lam):
    """For g = lam||.||_1 the conjugate prox is projection onto the
    l-inf ball of radius lam, for ANY sigma (Moreau decomposition)."""
    xj = jnp.asarray(x)
    got = np.asarray(moreau_dual_prox(xj, sigma, variant="l1", lam=lam))
    np.testing.assert_allclose(got, np.clip(x, -lam, lam), atol=1e-4)


@given(floats, st.floats(1e-2, 10.0), st.floats(0.1, 5), st.floats(0.1, 5),
       _VARIANTS)
def test_moreau_identity(x, sigma, lam, mu, variant):
    """prox_{sigma g*}(x) + sigma * prox_{g/sigma}(x/sigma) = x — the Moreau
    decomposition every PDHG dual step relies on."""
    kw = dict(variant=variant, lam=lam, mu=mu, lo=-lam, hi=lam)
    xj = jnp.asarray(x)
    dual = np.asarray(moreau_dual_prox(xj, sigma, **kw))
    primal = np.asarray(prox_elem(xj / sigma, 1.0 / sigma, **kw))
    np.testing.assert_allclose(dual + sigma * primal, x, atol=2e-4 * max(
        1.0, float(np.abs(x).max())))


# ------------------------------------------------------------- cost model --
@given(st.integers(1, 1024), st.integers(1, 128))
def test_cost_model_table1_invariants(P_, k):
    """Table I: latency / k; flops, bandwidth unchanged; memory grows kd^2."""
    cm1 = CostModel(d=54, n=100_000, b=0.1, T=128, k=1)
    cmk = CostModel(d=54, n=100_000, b=0.1, T=128, k=k)
    assert cmk.flops(P_) == cm1.flops(P_)
    assert cmk.words(P_) == cm1.words(P_)
    np.testing.assert_allclose(cmk.messages(P_, ca=True) * k,
                               cm1.messages(P_, ca=True) * 1, rtol=1e-9)
    np.testing.assert_allclose(
        cmk.memory(P_, ca=True),
        cm1.memory(P_, ca=True) + (k - 1) * 54 ** 2, rtol=1e-9)


@given(st.integers(2, 1024), st.integers(1, 64))
def test_cost_model_bcd_tradeoff(P_, k):
    """CA-BCD: latency still drops k-fold, but the cross-Gram word volume
    inflates (bounded by k) — the 1612.04003 tradeoff, distinct from the
    gram-schedule rows asserted above."""
    cm1 = CostModel(d=54, n=100_000, b=0.1, T=128, k=1)
    cmk = CostModel(d=54, n=100_000, b=0.1, T=128, k=k)
    np.testing.assert_allclose(cmk.messages(P_, ca=True, solver="bcd") * k,
                               cm1.messages(P_, ca=True, solver="bcd"),
                               rtol=1e-9)
    w1 = cm1.words(P_, solver="bcd")
    wk = cmk.words(P_, solver="bcd", ca=True)
    assert w1 <= wk <= k * w1 + 1e-9
    assert cmk.flops(P_, solver="bcd") == cm1.flops(P_, solver="bcd")


@given(st.integers(2, 1024))
def test_ca_speedup_positive_in_latency_regime(P_):
    """On a latency-dominated machine, CA speedup > 1 and grows with k."""
    machine = MachineParams("lat", gamma=1e-13, alpha=1e-4, beta=1e-11)
    cm = CostModel(d=54, n=100_000, b=0.01, T=128, k=32)
    s = cm.speedup(P_, machine)
    assert s > 1.0


# ------------------------------------------------------------ compression --
@given(hnp.arrays(np.float32, st.integers(4, 256),
                  elements=st.floats(-10, 10, width=32)))
def test_topk_lossless_reconstruction(g):
    gj = jnp.asarray(g)
    c, resid = topk_compress(gj, frac=0.25)
    np.testing.assert_allclose(
        np.asarray(topk_decompress(c, gj.shape) + resid), g, atol=1e-6)


@given(hnp.arrays(np.float32, st.integers(4, 256),
                  elements=st.floats(-10, 10, width=32)))
def test_int8_error_bound(g):
    gj = jnp.asarray(g)
    c, resid = int8_compress(gj)
    err = np.abs(np.asarray(int8_decompress(c, gj.shape)) - g)
    bound = float(np.abs(g).max()) / 127.0 * 0.5 + 1e-6
    assert err.max() <= bound + 1e-5


# ---------------------------------------------------------------- sharding --
@given(st.integers(1, 4096), st.integers(1, 4096))
def test_fit_spec_always_divides(a, b):
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    # trivial mesh always divides
    spec = fit_spec(P("data", "model"), (a, b), mesh)
    for dim, entry in zip((a, b), spec):
        if entry is not None:
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = 1
            for ax in axes:
                size *= mesh.shape[ax]
            assert dim % size == 0


def test_fit_spec_drops_and_degrades():
    from repro.launch.mesh import spoof_mesh
    mesh = spoof_mesh((2, 16, 16), ("pod", "data", "model"))
    # 50280 % 16 != 0 -> model axis dropped on dim 0
    spec = fit_spec(P("model", "data"), (50280, 1536), mesh)
    assert spec[0] is None and spec[1] == "data"
    # batch 2 over ("pod","data")=32 -> degrades to ("pod",)=2
    spec = fit_spec(P(("pod", "data"), None), (2, 7), mesh)
    assert spec[0] == "pod"
    # batch 1 -> fully dropped
    spec = fit_spec(P(("pod", "data"), None), (1, 7), mesh)
    assert spec[0] is None
