"""Share of the paged decode-attention kernel's roofline over the traced
engine steps.

Kernel time: device time of the ops named for ``paged_flash_decode``. Work:
the K/V bytes of the live context of every step, from the driver's
bookkeeping (``kv_token_reads``: for each processed token, the positions it
attends over), times the bf16 K/V bytes of a token over all layers. The
kernel needs about 2 FLOPs a byte: HBM bandwidth bounds it.
"""
from perfbench import costs
from perfbench.references.dense_lm import dims

PATTERN = r"^paged_flash_decode(\.|$)"


def read(b, chip):
    tr, c = b.trace, b.counts
    if tr is None or chip is None or not c.get("kv_token_reads"):
        return None
    secs = tr.op_seconds(PATTERN)
    if secs <= 0:
        return None
    z = dims(b.config)
    need = c["kv_token_reads"] * costs.kv_bytes_per_token(
        z["n_layers"], z["n_kv_heads"], z["head_dim"])
    return 100.0 * need / chip.hbm_bw / secs
