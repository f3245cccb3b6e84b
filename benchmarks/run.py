"""Benchmark harness: one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV rows; ``--json PATH`` additionally writes
the same records as ``[{suite, name, us_per_call, derived}, ...]`` — the
machine-readable perf trajectory CI archives per commit.

  PYTHONPATH=src python -m benchmarks.run [--only fig2,...] [--json OUT.json]
  PYTHONPATH=src python -m benchmarks.run --only serve --json BENCH_serve.json

``--bench-dir DIR`` writes the per-suite artifact files (``BENCH_<suite>.json``
for the suites in :data:`BENCH_FILES`) as each suite finishes — *including*
on failure, in which case the file carries whatever rows the suite emitted
before dying plus one ``us_per_call=-1`` error-sentinel row naming the
exception. A regression in one suite therefore never erases another suite's
artifact, and downstream diffing (``benchmarks.compare``) can distinguish "a
row got slower" from "a row stopped being produced".
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

from benchmarks import common
from repro.launch.compile_cache import use_compile_cache

SUITES = {
    "fig2_convergence_b": "benchmarks.convergence_b",
    "fig3_convergence_k": "benchmarks.convergence_k",
    "fig4_6_speedup": "benchmarks.speedup_model",
    "fig7_strong_scaling": "benchmarks.strong_scaling",
    "table1_costs": "benchmarks.cost_table",
    "kernels": "benchmarks.kernel_bench",
    "wallclock": "benchmarks.solver_wallclock",
    "serve": "benchmarks.serve_bench",
}

#: suites with a per-suite CI artifact file (written under --bench-dir)
BENCH_FILES = {
    "kernels": "BENCH_kernels.json",
    "serve": "BENCH_serve.json",
    "fig3_convergence_k": "BENCH_convergence.json",
}

#: sentinel us_per_call marking "suite died before producing this row"
ERROR_SENTINEL = -1.0


def _suite_records(name: str) -> list:
    return [r for r in common.RECORDS if r.get("suite") == name]


def _write_suite_file(bench_dir: str, name: str,
                      error: Exception = None) -> None:
    records = _suite_records(name)
    if error is not None:
        records = records + [dict(
            suite=name, name=f"{name}/ERROR",
            us_per_call=ERROR_SENTINEL,
            derived=f"error={type(error).__name__}: {error}")]
    path = os.path.join(bench_dir, BENCH_FILES[name])
    with open(path, "w") as f:
        json.dump(records, f, indent=1)
    print(f"# wrote {len(records)} records to {path}", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write captured records as JSON to PATH")
    ap.add_argument("--bench-dir", default=None, metavar="DIR",
                    help="write per-suite BENCH_<suite>.json artifacts here "
                         "(always, with an error-sentinel row on failure)")
    args = ap.parse_args(argv)
    use_compile_cache()
    del common.RECORDS[:]        # main() is reentrant: one run, one trajectory
    picked = set(args.only.split(",")) if args.only else set(SUITES)
    unknown = picked - set(SUITES)
    if unknown:
        raise SystemExit(f"unknown suites {sorted(unknown)}; "
                         f"available: {sorted(SUITES)}")
    if args.bench_dir:
        os.makedirs(args.bench_dir, exist_ok=True)

    import importlib
    failures = []
    for name, mod_name in SUITES.items():
        if name not in picked:
            continue
        print(f"# --- {name} ---", flush=True)
        common.set_suite(name)
        err = None
        try:
            mod = importlib.import_module(mod_name)
            mod.run()
        except Exception as e:
            traceback.print_exc()
            failures.append(name)
            err = e
        if args.bench_dir and name in BENCH_FILES:
            _write_suite_file(args.bench_dir, name, error=err)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(common.RECORDS, f, indent=1)
        print(f"# wrote {len(common.RECORDS)} records to {args.json}")
    if failures:
        print(f"# FAILED suites: {failures}", file=sys.stderr)
        raise SystemExit(1)
    print("# all benchmark suites completed")


if __name__ == "__main__":
    main()
