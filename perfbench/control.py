"""Readings for the limits of a cell's correctness check.

  python3 perfbench/control.py --workload covtype.ca32 --seeds 1,2,3 \
      --seconds 10 --control --out chiprun_out/ctrl.json

Runs the cell once per seed in this one process, as ``run.py`` does, and
reads the program's compared numbers; with ``--control`` the control (the
reference in the next lower precision, on the same answers) takes the
program's place in the check, so those runs come out not correct, and the
program's numbers are kept beside it under ``program_``; with
``--fault NAME`` the program runs with that fault of ``faults.py`` planted.
The benchmark's own runs never do either. Writes one JSON object per seed,
then the largest and smallest reading of each number.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[0] = str(Path(__file__).resolve().parents[1])

from perfbench import faults, harness  # noqa: E402

#: counters a run leaves for the program's and the control's extra readings
#: (with ``--control`` the checks hold the control's, in the program's place)
READINGS = ("program_", "control_")


def main(argv=None, *, require_chip=True, loaded=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    harness.prepare_env()
    loaded = loaded or harness.load_cell(args.workload)
    undo = None
    if args.fault:
        import jax
        jax.clear_caches()
        undo = faults.FAULTS[loaded["traffic"]["driver"]][args.fault]()

    def patch(b):
        b.control = args.control

    rows = []
    try:
        for seed in [int(s) for s in args.seeds.split(",")]:
            t0 = time.perf_counter()
            res = harness.main(
                ["--workload", args.workload, "--seed", str(seed),
                 "--seconds", str(args.seconds)],
                t_start=t0, require_chip=require_chip, loaded=loaded,
                patch=lambda b: (patch(b), rows.append(dict(seed=seed,
                                                             bench=b))))
            b = rows[-1].pop("bench")
            rows[-1].update(
                correct=res["correct"], checks=res["checks"],
                readings={k: v for k, v in b.counts.items()
                          if k.startswith(READINGS)},
                metrics=res["metrics"])
            print("READING " + json.dumps(rows[-1]), flush=True)
    finally:
        if undo:
            undo()
    summary = {}
    for r in rows:
        vals = {**{k: v["value"] for k, v in r["checks"].items()},
                **r["readings"]}
        for k, v in vals.items():
            lo, hi = summary.get(k, (v, v))
            summary[k] = (min(lo, v), max(hi, v))
    out = dict(workload=args.workload, fault=args.fault, rows=rows,
               summary={k: dict(min=lo, max=hi)
                        for k, (lo, hi) in summary.items()})
    print("SUMMARY " + json.dumps(out["summary"]), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    return out


if __name__ == "__main__":
    main()
