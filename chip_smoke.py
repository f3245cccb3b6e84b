"""Bring-up check on a TPU: drive the solver, serving and training paths once
through their normal entry points, in this one process, and check what comes
out. It claims no speed.

  python chip_smoke.py              # one chip: solvers, serving, training
  python chip_smoke.py --chips 4    # four chips: the distributed solvers only

Phases (one chip):

- solvers: ``ca_sfista``/``sfista`` and ``ca_spnm``/``spnm`` through
  ``repro.launch.lasso_solve`` on a seeded problem at covtype's published
  shape (d=54, n=581,012), each scored against a float64 NumPy reference;
- serving: ``repro.launch.serve`` with internlm2-1.8b at its published
  widths on 16-token pages, bf16 and int8 pages, plus a logit check of
  cached paged decode against a full forward (``check_paged_logits``);
- training: a few steps of ``repro.launch.train --preset 100m``;
- the kernel dispatch table: every op on these paths must have run its
  Pallas kernel, never the XLA fallback.

With ``--chips 4`` it runs only the shard_map ``ca_sfista`` and classical
``sfista`` on a 4-device ``data`` mesh, on the same problem, and counts the
all-reduces of each compiled program.

It exits nonzero, before any phase, when JAX finds no TPU. A failed phase is
never caught: the last line, ``{"ok": true, "device": {...}}``, is printed
only when every phase passed.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import tempfile
import time
from pathlib import Path

#: rel_err bound for the stochastic solvers after T=256 iterations. In f32 on
#: a CPU all four reach 2.7e-3 on this problem (CA and classical agree to
#: 5 digits); on the TPU the Gram and prox matvecs multiply f32 in bf16
#: passes (relative error ~2^-9 per product), which moves the iterates by a
#: similar relative amount. 2e-2 leaves room for that and still rejects a
#: solver that did not converge (rel_err starts at 1).
SOLVER_TOL = 2e-2
SOLVER_ARGS = ["--dataset", "covtype", "--scale", "1.0", "--T", "256",
               "--b", "0.1", "--seed", "0"]
SERVE_ARGS = ["--arch", "internlm2-1.8b", "--preset", "full",
              "--page-size", "16", "--batch", "4", "--requests", "8",
              "--new-tokens", "32", "--k", "8"]
TRAIN_STEPS = 4


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    info = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{info['platform']!r}); no phase was run")
    if info["count"] < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                 f"found {info['count']}")
    return info


def phase(name):
    def deco(fn):
        def run(*a, **kw):
            print(f"\n=== {name} ===", flush=True)
            t0 = time.perf_counter()
            out = fn(*a, **kw)
            print(f"=== {name}: passed in {time.perf_counter() - t0:.1f} s "
                  f"(compilation included)", flush=True)
            return out
        return run
    return deco


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


@phase("solvers")
def solvers():
    from repro.launch import lasso_solve
    errs = {}
    for alg, k in (("ca_sfista", 32), ("sfista", 1), ("ca_spnm", 32),
                   ("spnm", 1)):
        res = lasso_solve.main(SOLVER_ARGS + ["--algorithm", alg,
                                              "--k", str(k)])
        errs[alg] = res["rel_err"]
    for ca, classical in (("ca_sfista", "sfista"), ("ca_spnm", "spnm")):
        print(f"{ca} rel_err={errs[ca]:.5f}  {classical} "
              f"rel_err={errs[classical]:.5f}  (tolerance {SOLVER_TOL:.0e})")
    for alg, err in errs.items():
        check(err <= SOLVER_TOL, f"{alg}: rel_err {err} > {SOLVER_TOL}")


@phase("serving")
def serving():
    from repro.launch import serve
    for kv_dtype in ("f32", "int8"):
        engine = serve.main(SERVE_ARGS + ["--kv-dtype", kv_dtype])
        s, k = engine.stats, engine.k
        print(f"check: retired={s.retired} requests=8 steps={s.steps} "
              f"syncs*k={s.syncs * k}", flush=True)
        check(s.retired == 8, f"retired {s.retired} != 8 requests")
        check(s.steps == s.syncs * k, f"steps {s.steps} != syncs*k")
        if kv_dtype == "f32":
            err = serve.check_paged_logits(engine.params, engine.cfg,
                                           page_size=16)
            print(f"logit check: paged decode vs full forward, max rel L2 "
                  f"err {err:.3e} (tolerance {serve.LOGIT_RTOL:.0e})")
            check(err <= serve.LOGIT_RTOL, f"logit error {err}")
        del engine, s                  # free the 7.6 GB of weights
        gc.collect()


@phase("training")
def training():
    from repro.launch import train
    with tempfile.TemporaryDirectory() as ckpt:
        runner = train.main(["--arch", "internlm2-1.8b", "--preset", "100m",
                             "--steps", str(TRAIN_STEPS), "--ckpt-dir", ckpt,
                             "--ckpt-every", "1000", "--log-every", "1"])
        runner.ckpt.wait()
    losses = [m["loss"] for m in runner.metrics_log]
    print(f"losses: {losses}")
    check(len(losses) == TRAIN_STEPS, f"{len(losses)} losses logged")
    check(all(math.isfinite(x) for x in losses), "non-finite loss")


@phase("distributed solvers (4 chips)")
def distributed_solvers():
    import jax
    import jax.numpy as jnp
    from repro.core import SolverConfig, lasso_reference_f64
    from repro.core.distributed import make_distributed_solver, shard_problem
    from repro.core.problem import lipschitz_step
    from repro.data import make_dataset_like
    from repro.roofline.hlo_cost import analyze_hlo

    problem, _ = make_dataset_like("covtype", scale=1.0)
    mesh = jax.make_mesh((4,), ("data",))
    Xs, ys = shard_problem(mesh, problem.X, problem.y)
    t = lipschitz_step(problem.X)
    w_opt = lasso_reference_f64(problem)
    cfg = SolverConfig(T=256, k=32, b=0.1)
    print(f"mesh={dict(mesh.shape)} d={problem.d} n={problem.n} "
          f"T={cfg.T} k={cfg.k}")
    rounds = {}
    for alg in ("ca_sfista", "sfista"):
        solve = make_distributed_solver(alg, mesh, cfg, problem.lam)
        args = (Xs, ys, jnp.zeros(problem.d), t, jax.random.PRNGKey(0))
        w = jax.device_get(solve(*args))
        err = float(((w - w_opt) ** 2).sum() ** 0.5
                    / (w_opt ** 2).sum() ** 0.5)
        cost = analyze_hlo(solve.lower(*args).compile().as_text())
        ar = cost.collectives.get("all-reduce", dict(count=0, bytes=0))
        rounds[alg] = int(ar["count"])
        print(f"{alg}: rel_err={err:.5f} (tolerance {SOLVER_TOL:.0e}) "
              f"all-reduces in the compiled HLO={rounds[alg]} "
              f"({ar['bytes']:.0f} bytes)", flush=True)
        check(err <= SOLVER_TOL, f"{alg}: rel_err {err} > {SOLVER_TOL}")
    check(rounds["sfista"] == cfg.k * rounds["ca_sfista"] > 0,
          f"all-reduces {rounds}: expected classical = k x CA")
    print(f"all-reduces: ca_sfista {rounds['ca_sfista']} = T/k vs sfista "
          f"{rounds['sfista']} = T")


def dispatch_table():
    """Print kernel dispatches by op and backend; fail on any XLA one."""
    from repro import obs
    snap = obs.metrics_snapshot()
    rows = {k: v for k, v in snap.items()
            if k.startswith(("repro_kernel_dispatch_total",
                             "repro_kernel_fallback_total"))}
    print("\n=== kernel dispatch table (trace-time dispatches) ===")
    for key in sorted(rows):
        print(f"  {key} {rows[key]:.0f}")
    bad = [k for k in rows if 'backend="xla"' in k
           or k.startswith("repro_kernel_fallback_total")]
    check(any('backend="pallas"' in k for k in rows), "no kernel dispatched")
    check(not bad, f"ops ran the XLA fallback: {bad}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed solvers on a "
                         "4-device mesh")
    args = ap.parse_args(argv)
    device = device_info(args.chips)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro import obs
    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}")
    obs.enable()
    if args.chips == 4:
        distributed_solvers()
    else:
        solvers()
        serving()
        training()
    dispatch_table()
    print(json.dumps(dict(ok=True, device=device)))


if __name__ == "__main__":
    main()
