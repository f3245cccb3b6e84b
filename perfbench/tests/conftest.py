"""The benchmark's own tests, on the CPU at small sizes:

  python -m pytest perfbench/tests -q

They sit outside the repository's ``testpaths``.
"""
import os
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(autouse=True)
def _own_compile_cache(tmp_path_factory, monkeypatch):
    # programs compiled here stay out of the checkout's cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path_factory.getbasetemp() / "jax_cache"))


CELLS = {"covtype.ca32": ("covtype", "ca32_bootstrap"),
         "internlm2.decode_sat": ("internlm2-1.8b", "decode_sat")}


def tiny(cell: str, **traffic):
    """A cell's configuration and mix, cut to a size the CPU runs in
    seconds."""
    from perfbench import harness
    config, mix = CELLS[cell]
    ld = dict(cell=dict(name=cell, config=config, traffic=mix, chips=1),
              config=harness.read_json(harness.BENCH / "configs"
                                       / f"{config}.json"),
              traffic=harness.read_json(harness.BENCH / "traffic"
                                        / f"{mix}.json"),
              end_to_end=[], per_layer=[])
    if ld["traffic"]["driver"] == "solve":
        ld["config"] = dict(ld["config"], n=20000)
        ld["traffic"] = dict(ld["traffic"], T=32, k=8, check_solves=2,
                             trace_seconds=0.5)
        ld["config"] = dict(ld["config"], matmul_operands="float32")
        ld["limits"] = dict(w_gap=5e-3)
    else:
        ld["config"] = dict(
            ld["config"], hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, vocab_size=256,
            serve=dict(num_slots=4, max_len=128, page_size=16),
            reference_bucket=64)
        ld["traffic"] = dict(
            ld["traffic"], prompt=dict(dist="uniform", lo=4, hi=12),
            output=dict(dist="uniform", lo=8, hi=24), warm_seconds=0.5,
            trace_seconds=0.5, check_requests=3)
        ld["limits"] = dict(logit_gap_mean=5e-3)
    ld["traffic"].update(traffic)
    return ld


def run_cell(ld, seed=12345678901, seconds=1.0, trace=0, patch=None):
    from perfbench import harness
    return harness.main(["--workload", ld["cell"]["name"], "--seed",
                         str(seed), "--seconds", str(seconds), "--trace",
                         str(trace)], require_chip=False, loaded=ld,
                        patch=patch)
