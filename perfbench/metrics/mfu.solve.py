"""The solve's FLOPs (``costs.lasso_solve_flops``: the full Gram and power
iteration of the step size, T sampled Grams, X y products and updates)
times the traced window's solves per second, over chips x the bf16 peak."""
from perfbench import costs


def read(b, chip):
    tr, c = b.trace, b.counts
    if tr is None or chip is None or not c.get("traced_solves"):
        return None
    flops = costs.lasso_solve_flops(c["d"], c["n"], c["T"], c["b"])
    return 100.0 * flops * c["traced_solves"] / (
        tr.window_s * b.chips * chip.flops_bf16)
