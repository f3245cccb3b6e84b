"""Roofline terms from compiled dry-run artifacts.

compute term    = HLO_FLOPs / peak_FLOP/s          (per chip)
memory term     = HLO_bytes / HBM_bw               (per chip)
collective term = collective_bytes / (link_bw * links)

FLOPs / HBM bytes / collective bytes come from repro.roofline.hlo_cost — an
HLO-text analysis that (unlike XLA's built-in HloCostAnalysis) multiplies
while-loop (lax.scan) bodies by their trip counts, which matters by orders of
magnitude for scan-over-layers models. XLA's cost_analysis() numbers are kept
in the record as `xla_*` for reference. All values are per-device (GSPMD
emits the partitioned module).
"""
from __future__ import annotations

import dataclasses
from typing import Dict

from repro.roofline.hw import chip_for
from repro.roofline.hlo_cost import analyze_hlo


@dataclasses.dataclass
class Roofline:
    flops: float
    bytes_hbm: float
    bytes_collective: float
    collective_detail: dict
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    per_device_memory: dict
    xla_flops: float = 0.0
    xla_bytes: float = 0.0
    hbm_top: list = dataclasses.field(default_factory=list)

    def as_dict(self):
        return dataclasses.asdict(self)


def analyze_compiled(compiled, device_kind: str) -> Roofline:
    """Derive the three roofline terms from one compiled SPMD executable,
    against the peaks of ``device_kind`` (the chip the program is meant
    for; an unknown kind raises, see :func:`repro.roofline.hw.chip_for`)."""
    chip = chip_for(device_kind)
    cost = analyze_hlo(compiled.as_text())

    xla_flops = xla_bytes = 0.0
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        xla_flops = float(ca.get("flops", 0.0))
        xla_bytes = float(ca.get("bytes accessed", 0.0))
    except Exception:
        pass

    t_c = cost.flops / chip.peak_flops_bf16
    t_m = cost.hbm_bytes / chip.hbm_bw
    t_x = cost.collective_bytes / (chip.ici_bw_per_link * chip.ici_links)
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    bottleneck = max(terms, key=terms.get)

    mem = {}
    try:
        ma = compiled.memory_analysis()
        mem = dict(
            argument_bytes=getattr(ma, "argument_size_in_bytes", 0),
            output_bytes=getattr(ma, "output_size_in_bytes", 0),
            temp_bytes=getattr(ma, "temp_size_in_bytes", 0),
            alias_bytes=getattr(ma, "alias_size_in_bytes", 0),
        )
    except Exception:
        pass

    return Roofline(flops=cost.flops, bytes_hbm=cost.hbm_bytes,
                    bytes_collective=cost.collective_bytes,
                    collective_detail=cost.collectives,
                    t_compute=t_c, t_memory=t_m, t_collective=t_x,
                    bottleneck=bottleneck, per_device_memory=mem,
                    xla_flops=xla_flops, xla_bytes=xla_bytes,
                    hbm_top=cost.hbm_top)


def roofline_terms(compiled, device_kind: str) -> dict:
    return analyze_compiled(compiled, device_kind).as_dict()


def model_flops(cfg, shape, n_params_active: int, kind: str) -> float:
    """MODEL_FLOPS = 6*N*D (train) or 2*N*D (fwd); D = processed tokens."""
    if kind == "train":
        toks = shape.global_batch * shape.seq_len
        return 6.0 * n_params_active * toks
    if kind == "prefill":
        toks = shape.global_batch * shape.seq_len
        return 2.0 * n_params_active * toks
    toks = shape.global_batch * 1
    return 2.0 * n_params_active * toks
