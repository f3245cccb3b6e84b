"""Share of the sampled-Gram kernel's roofline over the traced solves.

Kernel time: device time of the ops whose name or name stack names the gram
kernel. Work: the algorithm's T Gram matrices of (d, m) per solve and chip,
m = floor(b n / chips), unpadded (``costs.gram_flops``/``gram_bytes``).
At d = 54 the kernel needs 27 FLOPs a byte, under the v5e's 240: it is bound
by HBM bandwidth, and the least time is its bytes over 819 GB/s.
"""
from perfbench import costs

PATTERN = r"^gram(\.|$)"


def read(b, chip):
    tr, c = b.trace, b.counts
    if tr is None or chip is None or not c.get("traced_solves"):
        return None
    secs = tr.op_seconds(PATTERN)
    if secs <= 0:
        return None
    m = costs.lasso_sample_size(c["n"] // b.chips, c["b"])
    least = max(costs.gram_flops(c["d"], m) / chip.flops_bf16,
                costs.gram_bytes(c["d"], m) / chip.hbm_bw)
    return 100.0 * least * c["T"] * c["traced_solves"] / secs
