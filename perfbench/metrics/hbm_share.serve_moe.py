"""Bytes the traced engine steps needed, over the window, over 819 GB/s:
each step reads the weights outside the routed experts once in bf16
(``costs_moe.step_weight_bytes``), the held experts that got a token
(``moe_experts_hit``), and the live K/V of its tokens."""
from perfbench import costs, costs_moe
from perfbench.references.moe_lm import dims


def read(b, chip):
    tr, c = b.trace, b.counts
    if tr is None or chip is None or not c.get("syncs") \
            or "moe_experts_hit" not in c:
        return None
    z = dims(b.config)
    p = costs_moe.moe_lm_params(z)
    steps = c["syncs"] * c["k"]
    need = steps * costs_moe.step_weight_bytes(p) \
        + c["moe_experts_hit"] * costs_moe.expert_bytes(p) \
        + c["kv_token_reads"] * costs.kv_bytes_per_token(
            z["n_layers"], z["n_kv_heads"], z["head_dim"])
    return 100.0 * need / tr.window_s / chip.hbm_bw
