"""Bytes the traced engine steps needed, over the window, over 819 GB/s:
each step reads every weight matrix once in the compute precision (bf16,
``costs.dense_lm_step_weight_bytes``) plus the live K/V of its tokens."""
from perfbench import costs
from perfbench.references.dense_lm import dims


def read(b, chip):
    tr, c = b.trace, b.counts
    if tr is None or chip is None or not c.get("syncs"):
        return None
    z = dims(b.config)
    p = costs.dense_lm_params(z["d"], z["n_layers"], z["n_heads"],
                              z["n_kv_heads"], z["head_dim"], z["d_ff"],
                              z["vocab"])
    steps = c["syncs"] * c["k"]
    need = steps * costs.dense_lm_step_weight_bytes(p) + c["kv_token_reads"] \
        * costs.kv_bytes_per_token(z["n_layers"], z["n_kv_heads"],
                                   z["head_dim"])
    return 100.0 * need / tr.window_s / chip.hbm_bw
