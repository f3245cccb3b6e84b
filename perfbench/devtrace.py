"""Capture a window with the JAX profiler and reduce its trace.

Busy time is the union of the intervals in which an operation ran on a
device; idle gaps are what is left of the traced window, each labelled by
the benchmark's own host annotation (``bench.*``) that covers most of it.
Nothing here knows a kernel by name: readers in ``metrics/`` match the op
names they need.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, List, Optional, Tuple

#: host annotation that brackets the traced part of the window
WINDOW = "bench.traced"
#: ops that only contain other ops' events
_CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")


@dataclasses.dataclass
class Op:
    name: str        # HLO op name, e.g. "fusion.12" or "gram.6"
    label: str       # the op's HLO text and name stack, for matching
    start: int       # ns, trace clock
    dur: int         # ns
    chip: int


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    host: List[Tuple[str, int, int]]     # (name, start ns, dur ns)
    t0: int
    t1: int
    n_chips: int

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_intervals(self, chip: int) -> List[Tuple[int, int]]:
        spans = sorted((max(o.start, self.t0), min(o.start + o.dur, self.t1))
                       for o in self.ops if o.chip == chip)
        out: List[Tuple[int, int]] = []
        for s, e in spans:
            if e <= s:
                continue
            if out and s <= out[-1][1]:
                if e > out[-1][1]:
                    out[-1] = (out[-1][0], e)
            else:
                out.append((s, e))
        return out

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the chips."""
        tot = sum(e - s for c in range(self.n_chips)
                  for s, e in self.busy_intervals(c))
        return tot * 1e-9 / self.n_chips

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of ops whose name matches ``pattern`` (regex),
        clipped to the window and averaged over the chips."""
        rx = re.compile(pattern)
        tot = sum(max(0, min(o.start + o.dur, self.t1) - max(o.start, self.t0))
                  for o in self.ops if rx.search(o.name))
        return tot * 1e-9 / self.n_chips

    def gaps(self, chip: int = 0) -> List[Tuple[int, int]]:
        gaps, t = [], self.t0
        for s, e in self.busy_intervals(chip):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.t1:
            gaps.append((t, self.t1))
        return gaps

    def label_gap(self, s: int, e: int) -> str:
        """The host annotation that covers most of [s, e)."""
        best, best_ov = "host: none", 0
        for name, hs, hd in self.host:
            if name == WINDOW:
                continue
            ov = min(e, hs + hd) - max(s, hs)
            if ov > best_ov:
                best, best_ov = name, ov
        return best

    def host_seconds_outside_busy(self, name: str, chip: int = 0) -> float:
        """Seconds of host annotation ``name`` in which the device was idle."""
        gaps = self.gaps(chip)
        tot = 0
        for hname, hs, hd in self.host:
            if hname != name:
                continue
            he = hs + hd
            for gs, ge in gaps:
                tot += max(0, min(he, ge) - max(hs, gs))
        return tot * 1e-9

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """Top ops by device time (control-flow ops, which only contain
        others, left out) and the longest idle gaps, labelled."""
        per: Dict[str, int] = {}
        for o in self.ops:
            if _CONTAINER.match(o.name):
                continue
            d = max(0, min(o.start + o.dur, self.t1) - max(o.start, self.t0))
            per[o.name] = per.get(o.name, 0) + d
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps(0), key=lambda g: g[0] - g[1])[:top]
        return dict(
            device_ops=[[n, d * 1e-9 / self.n_chips] for n, d in ops],
            idle_gaps=[[self.label_gap(s, e), (e - s) * 1e-9]
                       for s, e in gaps])


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the enclosed block into ``log_dir`` (emptied first), with the
    Python tracer off: only device ops and ``TraceAnnotation`` spans."""
    import jax
    shutil.rmtree(log_dir, ignore_errors=True)
    os.makedirs(log_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


_NAME_STATS = ("tf_op", "name", "long_name")


def _short(text: str) -> str:
    """``%fusion.20 = s32[...] fusion(...)`` -> ``fusion.20``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def _label(ev) -> str:
    parts = [ev.name]
    for k, v in ev.stats:
        if k in _NAME_STATS and isinstance(v, str):
            parts.append(v[:400])
    return " | ".join(parts)


def load(log_dir: str, n_chips: int) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise RuntimeError(f"no trace written under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    ops: List[Op] = []
    host: List[Tuple[str, int, int]] = []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            chip = int(m.group(1))
            if chip >= n_chips:
                continue
            lines = list(plane.lines)
            names = [ln.name for ln in lines]
            keep = [ln for ln in lines if ln.name == "XLA Ops"] if \
                "XLA Ops" in names else []
            for ln in keep:
                for ev in ln.events:
                    ops.append(Op(_short(ev.name), _label(ev),
                                  int(ev.start_ns),
                                  int(ev.duration_ns), chip))
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith("bench."):
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)))
    win = [h for h in host if h[0] == WINDOW]
    if not win:
        raise RuntimeError(f"the trace has no {WINDOW!r} annotation")
    _, ws, wd = win[-1]
    return Trace(ops=ops, host=host, t0=ws, t1=ws + wd, n_chips=n_chips)


def reset_dir(path: Optional[str]) -> None:
    if path:
        shutil.rmtree(path, ignore_errors=True)
