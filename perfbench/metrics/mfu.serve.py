"""Model FLOPs of every token the engine processed in the traced window
(prompt and output tokens: prefill runs through the decode block), each
attending over its live context, per second, over the bf16 peak."""
from perfbench import costs
from perfbench.references.dense_lm import dims


def read(b, chip):
    tr, c = b.trace, b.counts
    if tr is None or chip is None or not c.get("tokens_processed"):
        return None
    z = dims(b.config)
    p = costs.dense_lm_params(z["d"], z["n_layers"], z["n_heads"],
                              z["n_kv_heads"], z["head_dim"], z["d_ff"],
                              z["vocab"])
    flops = c["tokens_processed"] * costs.dense_lm_token_flops(p) + \
        costs.attention_flops(c["kv_token_reads"], z["n_layers"],
                              z["n_heads"], z["head_dim"])
    return 100.0 * flops / (tr.window_s * chip.flops_bf16)
