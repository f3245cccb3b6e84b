"""Paper Table I, verified two ways:

1. Analytically, via the CostModel invariants (latency / k, flops and
   bandwidth unchanged, memory +k d^2).
2. Structurally, from compiled HLO of the distributed solvers on an 8-way
   host mesh (subprocess): loop-weighted all-reduce ROUNDS drop k-fold while
   all-reduced BYTES stay constant.
"""
from __future__ import annotations

import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

from repro.core.cost_model import CostModel
from benchmarks.common import emit

SRC = str(Path(__file__).resolve().parents[1] / "src")

_SUB = """
import jax, jax.numpy as jnp
from repro.core import SolverConfig
from repro.core.distributed import make_distributed_solver
from repro.data import make_lasso_data
from repro.roofline.hlo_cost import analyze_hlo
prob, _ = make_lasso_data(jax.random.PRNGKey(0), d=16, n=1024)
mesh = jax.make_mesh((8,), ("data",))
cfg = SolverConfig(T=32, k=8, b=0.1)
for alg in ["sfista", "ca_sfista", "spnm", "ca_spnm",
            "pdhg", "ca_pdhg", "bcd", "ca_bcd"]:
    solve = make_distributed_solver(alg, mesh, cfg, prob.lam)
    lowered = solve.lower(
        jax.ShapeDtypeStruct((16, 1024), jnp.float32),
        jax.ShapeDtypeStruct((1024,), jnp.float32),
        jax.ShapeDtypeStruct((16,), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    cost = analyze_hlo(lowered.compile().as_text())
    ar = cost.collectives.get("all-reduce", dict(count=0, bytes=0))
    print(f"{alg} ROUNDS {int(ar['count'])} BYTES {int(ar['bytes'])}")
"""


def run():
    # --- analytic Table I --------------------------------------------------
    for (d, n) in ((54, 581_012), (18, 5_000_000)):
        for P in (64, 1024):
            c1 = CostModel(d=d, n=n, b=0.01, T=128, k=1)
            ck = CostModel(d=d, n=n, b=0.01, T=128, k=32)
            emit(f"table1/d={d}/P={P}", 0.0,
                 f"latency_ratio={c1.messages(P)/ck.messages(P, ca=True):.1f}"
                 f";flops_ratio={c1.flops(P)/ck.flops(P):.3f}"
                 f";bw_ratio={c1.words(P)/ck.words(P):.3f}"
                 f";mem_overhead_words={ck.memory(P, ca=True)-c1.memory(P):.0f}")
            # CA-BCD's tradeoff row (1612.04003 Table 1): same k-fold latency
            # win, but the cross-Gram word volume inflates ~k-fold
            emit(f"table1/bcd/d={d}/P={P}", 0.0,
                 f"latency_ratio={c1.messages(P, solver='bcd')/ck.messages(P, ca=True, solver='bcd'):.1f}"
                 f";word_inflation={ck.words(P, solver='bcd', ca=True)/c1.words(P, solver='bcd'):.2f}"
                 f";flops_ratio={c1.flops(P, solver='bcd')/ck.flops(P, solver='bcd'):.3f}")

    # --- structural HLO verification ---------------------------------------
    # the child runs on 8 host CPUs: the parent may hold the accelerator
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(_SUB)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    if out.returncode != 0:
        emit("table1/hlo", 0.0, f"SUBPROCESS_FAILED:{out.stderr[-200:]}")
        return
    stats = {}
    for m in re.finditer(r"(\w+) ROUNDS (\d+) BYTES (\d+)", out.stdout):
        stats[m.group(1)] = (int(m.group(2)), int(m.group(3)))
    for base in ("sfista", "spnm", "pdhg", "bcd"):
        cr, cb = stats[base]
        ar, ab = stats["ca_" + base]
        # gram solvers: bytes_ratio ~1 (volume unchanged); bcd: ~1/k (the
        # CA cross-Gram inflates words k-fold, see CostModel.words)
        emit(f"table1/hlo/{base}", 0.0,
             f"classical_rounds={cr};ca_rounds={ar};"
             f"round_ratio={cr/max(ar,1):.1f};"
             f"bytes_ratio={cb/max(ab,1):.2f}")
    return stats


if __name__ == "__main__":
    run()
