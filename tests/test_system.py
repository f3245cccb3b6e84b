"""End-to-end behaviour tests for the paper's system.

One full arc per test: the LASSO solver stack end-to-end (paper-faithful),
and the LM training/serving stack end-to-end (paper's CA schedule inside the
trainer) — including failure injection + checkpoint recovery, i.e. the whole
production story in miniature.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, smoke_config
from repro.core import (SolverConfig, ca_sfista, ca_spnm, solve_reference,
                        relative_solution_error)
from repro.data import make_dataset_like
from repro.launch.steps import (make_train_step, make_serve_step,
                                init_train_state)
from repro.models import init_cache, init_params
from repro.dist.fault_tolerance import TrainingRunner, FailureSource


def test_lasso_end_to_end():
    """Generate data -> solve with both CA solvers -> verify vs oracle."""
    problem, _ = make_dataset_like("abalone")
    w_opt = solve_reference(problem)
    cfg = SolverConfig(T=256, k=32, b=0.25)
    for solver in (ca_sfista, ca_spnm):
        w = solver(problem, cfg, jax.random.PRNGKey(0))
        assert float(relative_solution_error(w, w_opt)) < 0.2


def test_lm_train_checkpoint_recover_serve(tmp_path):
    """Full production arc: train with the CA schedule, crash twice, recover
    from checkpoints, finish, then serve greedily from the trained params."""
    cfg = smoke_config(ARCHS["internlm2-1.8b"])

    def step_builder(mesh):
        step = make_train_step(cfg, None, ca_k=2, peak_lr=5e-3, warmup=2,
                               total_steps=30, remat=False)
        return jax.jit(step), None

    def data_factory(start):
        def gen():
            s = start
            while True:
                key = jax.random.PRNGKey(s)
                toks = jax.random.randint(key, (4, 17), 0, cfg.vocab)
                yield dict(tokens=toks[:, :-1], labels=toks[:, 1:])
                s += 1
        return iter(gen())

    runner = TrainingRunner(
        step_builder, None, data_factory,
        lambda: init_train_state(cfg, jax.random.PRNGKey(0)),
        str(tmp_path), ckpt_every=8,
        failure_source=FailureSource(fail_at=[5, 19]))
    state = runner.run(30)
    assert runner.restarts == 2
    losses = [m["loss"] for m in runner.metrics_log]
    assert np.isfinite(losses).all()

    serve = jax.jit(make_serve_step(cfg, None))
    cache = init_cache(cfg, 2, 16)
    tok = jnp.zeros((2, 1), jnp.int32)
    for _ in range(5):
        tok, logits, cache = serve(state.params, cache, tok)
    assert int(cache["pos"]) == 5
    assert np.isfinite(np.asarray(logits, np.float32)).all()


def test_chip_smoke_refuses_a_host_without_tpu():
    """With JAX held to the CPU the chip check exits nonzero before any
    phase and prints no result line."""
    root = Path(__file__).resolve().parents[1]
    out = subprocess.run([sys.executable, str(root / "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "platform=cpu" in out.stdout
    assert "===" not in out.stdout and '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_compile_cache_rule(monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR wins and nothing else is set; otherwise
    the cache is the fixed in-checkout directory."""
    from repro.launch import compile_cache
    root = Path(__file__).resolve().parents[1]
    assert compile_cache.DEFAULT_DIR == root / ".jax_cache"
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.use_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert compile_cache.use_compile_cache() == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
