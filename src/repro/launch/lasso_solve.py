"""LASSO solver driver — the paper's workload as a production CLI.

  PYTHONPATH=src python -m repro.launch.lasso_solve --dataset covtype \
      --algorithm ca_sfista --k 32 --b 0.1 --T 256
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

import numpy as np

from repro.core import (SolverConfig, sfista, ca_sfista, spnm, ca_spnm,
                        pdhg, ca_pdhg, bcd, ca_bcd, lasso_reference_f64,
                        lasso_objective)
from repro.core.cost_model import CostModel, MachineParams
from repro.data import make_dataset_like
from repro.launch.compile_cache import use_compile_cache

SOLVERS = dict(sfista=sfista, ca_sfista=ca_sfista, spnm=spnm, ca_spnm=ca_spnm,
               pdhg=pdhg, ca_pdhg=ca_pdhg, bcd=bcd, ca_bcd=ca_bcd)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="covtype",
                    choices=["abalone", "covtype", "susy"])
    ap.add_argument("--algorithm", default="ca_sfista",
                    choices=sorted(SOLVERS))
    ap.add_argument("--T", type=int, default=256)
    ap.add_argument("--k", type=int, default=32)
    ap.add_argument("--b", type=float, default=0.1)
    ap.add_argument("--Q", type=int, default=5)
    ap.add_argument("--scale", type=float, default=0.01,
                    help="fraction of the dataset's published sample count")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tol", type=float, default=None,
                    help="stop at relative solution error <= tol (paper's "
                         "second stopping criterion); runs in k-sized rounds")
    args = ap.parse_args(argv)
    use_compile_cache()

    problem, _ = make_dataset_like(args.dataset, scale=args.scale)
    cfg = SolverConfig(T=args.T, k=args.k, b=args.b, Q=args.Q)
    solver = SOLVERS[args.algorithm]
    key = jax.random.PRNGKey(args.seed)

    w_opt = lasso_reference_f64(problem)

    def rel_err(w):
        w = np.asarray(w, np.float64)
        return float(np.linalg.norm(w - w_opt) / np.linalg.norm(w_opt))

    t0 = time.time()
    if args.tol is not None:
        # paper §V-A stopping criterion (ii): run until rel err <= tol,
        # checking once per k-step round (checking costs one extra collective)
        w = jnp.zeros(problem.d)
        total = 0
        cfg_round = SolverConfig(T=args.k, k=args.k, b=args.b, Q=args.Q)
        while total < args.T:
            key, sub = jax.random.split(key)
            w = solver(problem, cfg_round, sub, w0=w)
            total += args.k
            if rel_err(w) <= args.tol:
                break
        iters = total
    else:
        w = solver(problem, cfg, key)
        iters = cfg.T
    dt = time.time() - t0

    err = rel_err(w)
    print(f"dataset={args.dataset} d={problem.d} n={problem.n} "
          f"lambda={problem.lam:.5f}")
    print(f"{args.algorithm}: iters={iters} rel_err={err:.5f} "
          f"objective={float(lasso_objective(problem, w)):.6f} "
          f"wall={dt:.2f}s (rel_err against float64 NumPy FISTA, 4000 "
          f"full-batch iterations)")
    nnz = int((jnp.abs(w) > 1e-6).sum())
    print(f"solution support: {nnz}/{problem.d}")
    cm = CostModel(d=problem.d, n=problem.n, b=args.b, T=iters, k=args.k)
    cm_solver = "bcd" if args.algorithm.endswith("bcd") else "fista"
    for P in (64, 1024):
        print(f"  predicted CA speedup at P={P}: "
              f"{cm.speedup(P, MachineParams.comet_like(), solver=cm_solver):.2f}x")
    return dict(w=w, rel_err=err, iters=iters)


if __name__ == "__main__":
    main()
