"""Distributed CA solvers exactly as the paper runs them (Algorithm V): X
column-partitioned over processors, per-processor sampling, one Gram
all-reduce every k iterations — plus the PDHG and BCD pairs through the
same shard_map path. Runs on every device JAX finds: the chips of a TPU
host, or 8 simulated CPU devices elsewhere.

  PYTHONPATH=src python examples/distributed_lasso.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import sys, pathlib
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import SolverConfig, solve_reference, relative_solution_error
from repro.core.distributed import make_distributed_solver, shard_problem
from repro.core.problem import lipschitz_step
from repro.data import make_dataset_like
from repro.roofline.hlo_cost import analyze_hlo


def main():
    problem, _ = make_dataset_like("covtype", scale=0.05)
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    print(f"mesh: {mesh.shape}  problem: d={problem.d} n={problem.n}")

    Xs, ys = shard_problem(mesh, problem.X, problem.y)
    t = lipschitz_step(problem.X)
    w_opt = solve_reference(problem)
    cfg = SolverConfig(T=128, k=16, b=0.05)

    for alg in ("sfista", "ca_sfista", "spnm", "ca_spnm",
                "pdhg", "ca_pdhg", "bcd", "ca_bcd"):
        solve = make_distributed_solver(alg, mesh, cfg, problem.lam)
        w = solve(Xs, ys, jnp.zeros(problem.d), t, jax.random.PRNGKey(0))
        err = float(relative_solution_error(w, w_opt))
        # count collective rounds in the compiled program
        lowered = solve.lower(
            jax.ShapeDtypeStruct(Xs.shape, Xs.dtype),
            jax.ShapeDtypeStruct(ys.shape, ys.dtype),
            jax.ShapeDtypeStruct((problem.d,), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        cost = analyze_hlo(lowered.compile().as_text())
        rounds = int(cost.collectives.get("all-reduce", {"count": 0})["count"])
        print(f"{alg:10s} rel_err={err:.4f}  all-reduce rounds/run={rounds:4d}"
              f"  ({rounds / cfg.T:.2f} per iteration)")


if __name__ == "__main__":
    main()
