"""The paper's central claims, asserted directly:

1. CA-SFISTA / CA-SPNM are ARITHMETICALLY IDENTICAL to SFISTA / SPNM given
   the same sample draws (§IV: "maintaining the exact arithmetic of the
   classical algorithms") — asserted to ~1 ulp: the only difference is float
   reassociation inside XLA's batched (vmap'd) Gram matmul vs the per-step
   one; the operation sequence is identical.
2. Both converge to the LASSO optimum (relative solution error, §V-A).
3. Changing k does not change the trajectory (paper Fig. 3).
4. The fused Pallas kernels do not change solver results.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (LassoProblem, SolverConfig, sfista, spnm, ca_sfista,
                        ca_spnm, solve_reference, relative_solution_error,
                        lasso_objective, soft_threshold)
from repro.core.problem import lipschitz_step
from repro.data import make_lasso_data


@pytest.fixture(scope="module")
def problem():
    prob, w_star = make_lasso_data(jax.random.PRNGKey(0), d=32, n=2048)
    return prob


KEY = jax.random.PRNGKey(42)


def test_ca_sfista_bitwise_equals_sfista(problem):
    cfg = SolverConfig(T=64, k=8, b=0.1)
    w_classical = sfista(problem, cfg, KEY)
    w_ca = ca_sfista(problem, cfg, KEY)
    np.testing.assert_allclose(np.asarray(w_classical), np.asarray(w_ca),
                               atol=5e-6, rtol=0)


def test_ca_spnm_bitwise_equals_spnm(problem):
    cfg = SolverConfig(T=64, k=8, b=0.1, Q=5)
    np.testing.assert_allclose(np.asarray(spnm(problem, cfg, KEY)),
                               np.asarray(ca_spnm(problem, cfg, KEY)),
                               atol=5e-6, rtol=0)


@pytest.mark.parametrize("k", [1, 2, 4, 16, 32])
def test_k_does_not_change_trajectory(problem, k):
    """Paper Fig. 3: k only reschedules communication."""
    base = SolverConfig(T=64, k=1, b=0.1)
    w_ref, hist_ref = ca_sfista(problem, base, KEY, collect_history=True)
    cfg = SolverConfig(T=64, k=k, b=0.1)
    w, hist = ca_sfista(problem, cfg, KEY, collect_history=True)
    np.testing.assert_allclose(np.asarray(hist_ref), np.asarray(hist),
                               atol=5e-6, rtol=0)


def test_convergence_to_optimum(problem):
    w_opt = solve_reference(problem)
    cfg = SolverConfig(T=512, k=8, b=0.2)
    for solver in (ca_sfista, ca_spnm):
        w = solver(problem, cfg, KEY)
        err = float(relative_solution_error(w, w_opt))
        assert err < 0.15, f"{solver.__name__}: rel err {err}"
        # objective near-optimal as well
        gap = float(lasso_objective(problem, w) -
                    lasso_objective(problem, w_opt))
        assert gap < 5e-3


def test_spnm_converges_faster_per_iteration(problem):
    """Paper Fig. 2: 'CA-SPNM converges faster than CA-SFISTA'."""
    w_opt = solve_reference(problem)
    cfg = SolverConfig(T=96, k=8, b=0.3, Q=8)
    e_f = float(relative_solution_error(ca_sfista(problem, cfg, KEY), w_opt))
    e_n = float(relative_solution_error(ca_spnm(problem, cfg, KEY), w_opt))
    assert e_n <= e_f * 1.5


def test_b_controls_stochastic_error(problem):
    """Paper Fig. 2 + §V-B1: very small b degrades accuracy near the optimum
    or destabilizes the iteration outright ("very small sample sizes can
    influence stability and convergence") — with m = b*n = 10 samples the
    sampled Gram's spectrum routinely exceeds the full-Gram Lipschitz bound
    used for the step size, so divergence (NaN) is the expected failure mode.
    """
    w_opt = solve_reference(problem)
    errs = {}
    for b in (0.005, 0.5):
        cfg = SolverConfig(T=256, k=8, b=b)
        errs[b] = float(relative_solution_error(
            ca_sfista(problem, cfg, KEY), w_opt))
    assert np.isfinite(errs[0.5]) and errs[0.5] < 0.1
    assert (not np.isfinite(errs[0.005])) or errs[0.5] < errs[0.005]


def test_kernel_paths_match_jnp(problem):
    """Forcing the pallas backend (registry policy) does not change solver
    results. (The PR-3 ``use_kernel``/``backend`` kwarg shims are gone:
    the registry policy is the only backend selector.)"""
    from repro.kernels import registry
    cfg = SolverConfig(T=32, k=8, b=0.2, Q=4)
    for solver in (ca_sfista, ca_spnm):
        w_jnp = solver(problem, cfg, KEY)
        with registry.use("pallas"):
            w_ker = solver(problem, cfg, KEY)
        # the two backends sum the Gram and the prox matvecs in different
        # orders (the pallas Gram accumulates 512-column blocks), so after T
        # iterations the f32 iterates agree to a few ulps, not bitwise:
        # rtol=1e-6 is 8 ulps (2^-23 each); under jax 0.9 the worst entry
        # differs by 7.7e-7 relative at |w| = 1.56
        np.testing.assert_allclose(np.asarray(w_jnp), np.asarray(w_ker),
                                   atol=1e-6, rtol=1e-6)
        with pytest.raises(TypeError):
            solver(problem, cfg, KEY, use_kernel=True)   # shim removed


def test_warm_start_and_history(problem):
    cfg = SolverConfig(T=32, k=8, b=0.2)
    w, hist = ca_sfista(problem, cfg, KEY, collect_history=True)
    assert hist.shape == (32, problem.d)
    np.testing.assert_array_equal(np.asarray(hist[-1]), np.asarray(w))


def test_step_size_power_iteration(problem):
    t = float(lipschitz_step(problem.X))
    G = np.asarray(problem.X @ problem.X.T / problem.n)
    L = np.linalg.eigvalsh(G).max()
    # must satisfy FISTA's t <= 1/L (safety direction), and be close to it
    assert 1.0 / t >= L * 0.995
    assert 1.0 / t <= L * 1.15
