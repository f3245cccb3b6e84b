"""Render the dry-run status, per-mesh roofline and collective-schedule
markdown tables from the dry-run JSON.

  PYTHONPATH=src python -m benchmarks.roofline_report results/dryrun_final.json
"""
from __future__ import annotations

import json
import sys


def fmt_table(results: dict, mesh: str) -> str:
    lines = [
        "| arch | shape | bottleneck | compute s | memory s | collective s |"
        " useful FLOP ratio | fits 16G HBM (args+temp) | compile s |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for key in sorted(results):
        v = results[key]
        arch, shape, m = key.split("|")
        if m != mesh:
            continue
        if v["status"] == "skipped":
            lines.append(f"| {arch} | {shape} | — skipped: "
                         f"{v['reason'][:60]}… | | | | | | |")
            continue
        if v["status"] != "ok":
            lines.append(f"| {arch} | {shape} | **ERROR** | | | | | | |")
            continue
        ro = v["roofline"]
        mem = ro["per_device_memory"]
        tot = (mem.get("argument_bytes", 0) + mem.get("temp_bytes", 0) +
               mem.get("output_bytes", 0) - mem.get("alias_bytes", 0))
        fits = "yes" if tot < 16e9 else f"NO ({tot/1e9:.0f} GB)"
        lines.append(
            f"| {arch} | {shape} | {ro['bottleneck']} "
            f"| {ro['t_compute']:.3f} | {ro['t_memory']:.3f} "
            f"| {ro['t_collective']:.3f} | {v['useful_flop_ratio']:.3f} "
            f"| {fits} | {v['t_compile_s']:.0f} |")
    return "\n".join(lines)


def collective_summary(results: dict) -> str:
    lines = ["| arch | shape | mesh | all-reduce rounds | AR GB | all-gather"
             " rounds | AG GB | all-to-all GB |",
             "|---|---|---|---|---|---|---|---|"]
    for key in sorted(results):
        v = results[key]
        if v["status"] != "ok":
            continue
        arch, shape, mesh = key.split("|")
        cd = v["roofline"]["collective_detail"]
        ar = cd.get("all-reduce", dict(count=0, bytes=0))
        ag = cd.get("all-gather", dict(count=0, bytes=0))
        aa = cd.get("all-to-all", dict(count=0, bytes=0))
        lines.append(f"| {arch} | {shape} | {mesh} | {int(ar['count'])} "
                     f"| {ar['bytes']/1e9:.1f} | {int(ag['count'])} "
                     f"| {ag['bytes']/1e9:.1f} | {aa['bytes']/1e9:.2f} |")
    return "\n".join(lines)


def run(path="results/dryrun_final.json"):
    results = json.loads(open(path).read())
    n_ok = sum(1 for v in results.values() if v["status"] == "ok")
    n_skip = sum(1 for v in results.values() if v["status"] == "skipped")
    print(f"## Dry-run status: {n_ok} compiled, {n_skip} documented skips, "
          f"{len(results) - n_ok - n_skip} errors\n")
    print("### Single-pod mesh (data=16, model=16) — 256 chips\n")
    print(fmt_table(results, "16x16"))
    print("\n### Multi-pod mesh (pod=2, data=16, model=16) — 512 chips\n")
    print(fmt_table(results, "2x16x16"))
    print("\n### Collective schedules\n")
    print(collective_summary(results))


if __name__ == "__main__":
    run(sys.argv[1] if len(sys.argv) > 1 else "results/dryrun_final.json")
