"""Model FLOPs of the traced window, per second, over the bf16 peak: every
token the engine processed (prompt and output tokens) through the weights
outside the routed experts and attending over its live context, plus each
(token, held expert) pair the engine computed (``moe_assignments``)
through that expert's three matrices."""
from perfbench import costs, costs_moe
from perfbench.references.moe_lm import dims


def read(b, chip):
    tr, c = b.trace, b.counts
    if tr is None or chip is None or not c.get("tokens_processed") \
            or "moe_assignments" not in c:
        return None
    z = dims(b.config)
    p = costs_moe.moe_lm_params(z)
    flops = c["tokens_processed"] * costs_moe.token_flops(p) \
        + c["moe_assignments"] * costs_moe.assignment_flops(p) \
        + costs.attention_flops(c["kv_token_reads"], z["n_layers"],
                                z["n_heads"], z["head_dim"])
    return 100.0 * flops / (tr.window_s * chip.flops_bf16)
