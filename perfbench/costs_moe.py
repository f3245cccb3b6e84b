"""Operations and bytes a DeepSeekMoE decoder needs at one chip's share of
the routed experts, from its shapes alone (``references/moe_lm.dims``).

As in ``costs.py``, these count the work the mathematics asks for: every
weight once per processed token (2 FLOPs each) outside the routed experts,
each held expert's three matrices once per (token, held expert) pair, and
the weight bytes of a decode step read once in the compute precision,
never the padded rows a kernel happens to compute.
"""
from __future__ import annotations

from perfbench.costs import BF16


def moe_lm_params(z: dict) -> dict:
    """Parameter counts: the leading dense layers, the MoE layers' attention
    and norms, router, shared experts and held routed experts, one routed
    expert, the head, the embedding and the final norm."""
    d, Dh = z["d"], z["head_dim"]
    attn = d * (z["n_heads"] + 2 * z["n_kv_heads"]) * Dh \
        + z["n_heads"] * Dh * d
    L = z["n_layers"] - z["n_dense"]
    expert = 3 * d * z["moe_ff"]
    return dict(dense=z["n_dense"] * (attn + 3 * d * z["d_ff"] + 2 * d),
                attn=L * (attn + 2 * d), router=L * d * z["n_experts"],
                shared=L * z["n_shared"] * expert,
                experts=L * z["n_held"] * expert, expert=expert,
                head=d * z["vocab"], embed=z["vocab"] * d, final_norm=d)


def token_flops(p: dict) -> float:
    """Forward FLOPs of one token outside attention and the routed
    experts: every such weight used once, 2 FLOPs a weight."""
    return 2.0 * (p["dense"] + p["attn"] + p["router"] + p["shared"]
                  + p["head"])


def assignment_flops(p: dict) -> float:
    """FLOPs of one (token, held expert) pair: the expert's gate, up and
    down matrices once each."""
    return 2.0 * p["expert"]


def step_weight_bytes(p: dict, itemsize: int = BF16) -> float:
    """Weight bytes of one decode step outside the routed experts, each
    matrix read once (the embedding is a gather of a few rows: not
    counted)."""
    return itemsize * (p["dense"] + p["attn"] + p["router"] + p["shared"]
                       + p["head"] + p["final_norm"])


def expert_bytes(p: dict, itemsize: int = BF16) -> float:
    """Weight bytes of one routed expert."""
    return itemsize * p["expert"]
