"""The data-driven harness: one cell per process.

``run.py`` calls :func:`main`. The cell named by ``--workload`` is read from
``BENCHMARK.json``; its configuration is ``configs/<config>.json``, its
traffic mix ``traffic/<traffic>.json`` (which names the driver in
``drivers/``), the limits of its correctness check ``limits/<cell>.json``,
and every per-layer metric ``metrics/<metric>.py``. A later cell, mix,
configuration or metric is new files, and never an edit here.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
#: JAX's persistent compilation cache when the environment names none: a
#: fixed path inside the checkout (the path is part of the cache key)
CACHE_DIR = REPO / ".jax_cache"
#: where a traced run writes its profile (emptied before and after)
TRACE_DIR = REPO / ".bench_trace"


def prepare_env() -> None:
    """Before JAX is imported: the compile cache, and every program cached
    however quickly it compiled, so that a second run compiles nothing."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CACHE_DIR))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    src = str(REPO / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, benchmark: Optional[dict] = None) -> dict:
    """The cell, its configuration, traffic, limits and metric entries."""
    spec = benchmark if benchmark is not None else \
        read_json(REPO / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]

    def applies(m):
        return name in m.get("workloads", [name])

    return dict(
        cell=cell,
        config=read_json(REPO / conf["file"]),
        traffic=read_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        limits=read_json(BENCH / "limits" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)])


def load_module(path: Path):
    """Import a file whose name need not be a Python identifier."""
    mod_name = "perfbench_" + "".join(
        c if c.isalnum() else "_" for c in str(path.relative_to(BENCH)))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile over every value (no interpolation)."""
    if not values:
        raise ValueError("percentile of no values")
    v = sorted(values)
    rank = max(1, math.ceil(q * len(v) - 1e-9))
    return v[min(rank, len(v)) - 1]


def device_info(chips: int, require_chip: bool = True) -> dict:
    import jax
    devs = jax.devices()
    info = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", file=sys.stderr, flush=True)
    if require_chip and info["platform"] != "tpu":
        raise SystemExit(f"JAX found no TPU (platform {info['platform']!r}); "
                         "nothing was measured")
    if info["count"] < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found "
                         f"{info['count']}")
    info["count"] = chips
    return info


class CompileWatch:
    """Counts JAX tracing and compilation events, by time."""

    def __init__(self):
        self.events: List[tuple] = []

    def install(self):
        from jax import monitoring

        def on_duration(name, secs, **kw):
            if name.startswith("/jax/core/compile/"):
                self.events.append((time.perf_counter(), name))
        monitoring.register_event_duration_secs_listener(on_duration)

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.events if t0 <= t <= t1)


class Bench:
    """What a driver needs from the harness, and what it hands back."""

    def __init__(self, *, name: str, loaded: dict, seed: int, seconds: float,
                 trace: bool, t_start: float, device: dict):
        self.name = name
        self.cell = loaded["cell"]
        self.config = loaded["config"]
        self.traffic = loaded["traffic"]
        self.limits = loaded["limits"]
        self.chips = int(self.cell["chips"])
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.tracing = bool(trace)
        self.device = device
        self.t_start = t_start
        self.watch = CompileWatch()
        self.watch.install()
        self.setup_s: Optional[float] = None
        self.window: Optional[tuple] = None
        self.e2e: Dict[str, float] = {}
        self.counts: Dict[str, Any] = {}
        self.checks: List[tuple] = []     # (name, value, limit)
        self.attempted = 0
        self.failed = 0
        self.memory_peak = 0
        self.trace = None
        self.control = False         # also read the control (control.py)
        self.in_trace = False        # inside the traced part of the window
        self._trace_written = False

    # ---------------------------------------------------------- annotations
    def annotate(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    # -------------------------------------------------------------- phases
    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start
        print(f"setup: {self.setup_s:.3f} s", file=sys.stderr, flush=True)

    def window_done(self, t0: float, t1: float) -> None:
        self.window = (t0, t1)
        n = self.watch.between(t0, t1)
        self.counts["compiles_in_window"] = n
        print(f"compiles inside the window: {n}", file=sys.stderr, flush=True)

    @contextlib.contextmanager
    def traced(self):
        """Profile the enclosed part of the window when ``--trace 1``."""
        if not self.tracing:
            yield
            return
        from perfbench import devtrace as tr
        with tr.capture(str(TRACE_DIR)):
            with self.annotate(tr.WINDOW):
                self.in_trace = True
                try:
                    yield
                finally:
                    self.in_trace = False
        self._trace_written = True

    def load_trace(self) -> None:
        """Reduce the profile, after the window (reading it takes time)."""
        if not self._trace_written:
            return
        from perfbench import devtrace as tr
        self.trace = tr.load(str(TRACE_DIR), self.chips)
        tr.reset_dir(str(TRACE_DIR))

    def read_memory(self) -> None:
        import jax
        stats = [d.memory_stats() or {} for d in jax.devices()[:self.chips]]
        self.memory_peak = max(int(s.get("peak_bytes_in_use", 0))
                               for s in stats)

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append((name, float(value), float(limit)))

    def limit(self, name: str) -> float:
        return float(self.limits[name])


def _per_layer(b: Bench, entries: List[dict]) -> Dict[str, dict]:
    from perfbench import peaks
    chip = peaks.chip_for(b.device["kind"]) if b.device["platform"] == "tpu" \
        else None
    out = {}
    for m in entries:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        v = reader.read(b, chip)
        if v is not None:
            out[m["name"]] = dict(value=float(v), unit=m["unit"])
    return out


def result_line(b: Bench, per_layer: List[dict], end_to_end: List[dict]):
    correct = bool(b.checks) and all(v <= lim for _, v, lim in b.checks)
    device = dict(platform=b.device["platform"], kind=b.device["kind"],
                  count=b.device["count"], memory_peak_bytes=b.memory_peak)
    res: Dict[str, Any] = dict(correct=correct, attempted=b.attempted,
                               failed=b.failed)
    if b.tracing:
        res["metrics"] = _per_layer(b, per_layer)
        if b.trace is not None:
            device["busy_s"] = b.trace.busy_s()
            device["window_s"] = b.trace.window_s
            res["device"] = device
            res["breakdown"] = b.trace.breakdown()
    else:
        metrics = {"setup_s": dict(value=b.setup_s, unit="s")}
        for m in end_to_end:
            if m["name"] in b.e2e:
                metrics[m["name"]] = dict(value=b.e2e[m["name"]],
                                          unit=m["unit"])
        res["metrics"] = metrics
    res.setdefault("device", device)
    res["checks"] = {n: dict(value=v, limit=lim) for n, v, lim in b.checks}
    return res


def main(argv=None, *, t_start: Optional[float] = None,
         require_chip: bool = True, loaded: Optional[dict] = None,
         patch: Optional[Callable] = None) -> dict:
    """Run one cell and print its result line. ``require_chip=False``,
    ``loaded`` and ``patch`` are for the benchmark's own tests: they skip
    the look for a chip, give the cell in memory and break the program."""
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    prepare_env()
    loaded = loaded if loaded is not None else load_cell(args.workload)
    device = device_info(int(loaded["cell"]["chips"]), require_chip)
    b = Bench(name=args.workload, loaded=loaded, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace), t_start=t_start,
              device=device)
    driver = importlib.import_module(
        f"perfbench.drivers.{b.traffic['driver']}")
    if patch is not None:
        patch(b)
    driver.run(b)
    b.load_trace()
    res = result_line(b, loaded["per_layer"], loaded["end_to_end"])
    for n, v, lim in b.checks:
        print(f"check {n}: {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return res
