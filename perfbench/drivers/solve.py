"""Solve driver: back-to-back LASSO solves, a fresh key per solve.

Traffic keys: ``algorithm`` (a solver of ``repro.core``), ``T``, ``k``,
``b``, ``warm_solves`` (set-up solves, which compile), ``check_solves``
(solves of the window compared with the reference, drawn from the seed),
``trace_seconds`` (the profiled part of the window with ``--trace 1``).

Solve i of the window uses ``fold_in(key(seed), i)``, as bootstrap or
stability selection would. Each solve ends in ``block_until_ready`` before
the next is dispatched. ``solve_ms`` is the window's length over the solves
completed in it. On more than one chip the columns of X are sharded over a
``data`` mesh and ``repro.core.make_distributed_solver`` runs, with the step
size computed once in set-up, as that API takes it as an input.
"""
from __future__ import annotations

import contextlib
import sys
import time

import numpy as np

from perfbench.references import lasso as ref


def _key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _solver(b, X, y, lam, scfg):
    """solve(key) -> w, through the program's own entry points."""
    import jax
    import repro.core as core
    from repro.core.problem import LassoProblem, lipschitz_step
    alg = b.traffic["algorithm"]
    if b.chips == 1:
        fn = getattr(core, alg)
        problem = LassoProblem(X=X, y=y, lam=lam)
        return lambda key: fn(problem, scfg, key), (X, y)
    from jax.sharding import Mesh
    from repro.core.distributed import make_distributed_solver, shard_problem
    mesh = Mesh(np.array(jax.devices()[:b.chips]), ("data",))
    Xs, ys = shard_problem(mesh, X, y)
    t = lipschitz_step(Xs)
    solve = make_distributed_solver(alg, mesh, scfg, lam)
    w0 = jax.numpy.zeros((X.shape[0],), X.dtype)
    return lambda key: solve(Xs, ys, w0, t, key), (Xs, ys)


def run(b) -> None:
    import jax
    from repro.core import SolverConfig
    cfg, tr = b.config, b.traffic
    d, n = int(cfg["d"]), int(cfg["n"])
    X, y = ref.make_data(b.seed, d, n, cfg)
    lam = float(cfg["lam"])
    scfg = SolverConfig(T=int(tr["T"]), k=int(tr["k"]), b=float(tr["b"]))
    solve, (Xs, ys) = _solver(b, X, y, lam, scfg)
    if b.chips > 1:
        del X, y
    base = _key(b.seed)
    fold = jax.jit(jax.random.fold_in)
    for i in range(int(tr["warm_solves"])):
        jax.block_until_ready(solve(fold(base, -1 - i)))
    b.setup_done()

    ws = []
    t_trace = float(tr["trace_seconds"])
    t0 = time.perf_counter()
    t_end = t0 + b.seconds
    i = traced_solves = 0
    while True:
        tracing = b.tracing and i == 0
        with b.traced() if tracing else contextlib.nullcontext():
            while True:
                with b.annotate("bench.key_prep"):
                    key = fold(base, i)
                with b.annotate("bench.dispatch"):
                    w = solve(key)
                with b.annotate("bench.wait"):
                    w = jax.block_until_ready(w)
                ws.append(w)
                i += 1
                traced_solves += tracing
                now = time.perf_counter()
                if now >= t_end or (tracing and now >= t0 + t_trace):
                    break
        if now >= t_end:
            break
    t1 = time.perf_counter()
    b.window_done(t0, t1)
    b.attempted = i
    b.e2e["solve_ms"] = (t1 - t0) / i * 1e3
    b.counts.update(solves=i, traced_solves=traced_solves, d=d, n=n,
                    T=scfg.T, k=scfg.k, b=scfg.b)
    b.read_memory()

    # ---- the check, once the window has closed: every finite answer, and a
    # sample drawn from the seed replayed by the reference
    w_host = np.stack([np.asarray(w) for w in ws])
    b.failed = int((~np.isfinite(w_host).all(axis=1)).sum())
    del solve, ws
    if b.chips > 1:
        X, y = ref.make_data(b.seed, d, n, cfg)
        del Xs, ys
    rng = np.random.RandomState(b.seed % 2 ** 32)
    pick = rng.choice(i, size=min(int(tr["check_solves"]), i), replace=False)
    ops = {"float32": None, "bfloat16": "bf16"}[cfg["matmul_operands"]]
    gaps, ctrl = [], []
    for j in sorted(pick):
        kw = dict(T=scfg.T, b=scfg.b, shards=b.chips)
        key = fold(base, int(j))
        w_ref = ref.replay(X, y, lam, key, quant=ops, **kw)
        gaps.append(ref.rel_gap(w_host[j], w_ref))
        print(f"solve {j}: gap to the reference {gaps[-1]:.3e}",
              file=sys.stderr)
        if b.control:
            ctrl.append([ref.rel_gap(ref.replay(X, y, lam, key, quant=q,
                                                **kw), w_ref)
                         for q in ("int8", None)])
    if b.control:
        # the control in the program's place, judged as the program is
        b.counts.update(program_w_gap=max(gaps),
                        control_f32_w_gap=max(c[1] for c in ctrl))
        gaps = [c[0] for c in ctrl]
    b.check("w_gap", max(gaps), b.limit("w_gap"))
