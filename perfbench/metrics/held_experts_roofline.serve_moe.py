"""Share of the held-experts kernel's roofline over the traced engine
steps.

Kernel time: device time of the ops named for ``held_experts_ffn``. Work:
the weights of every (step, layer, held expert) with a token, read once in
bf16 (the engine's ``moe_experts_hit``), plus each (token, held expert)
pair's token row in bf16 (``moe_assignments``). At 1.5 tokens an expert
the kernel needs under 2 FLOPs a byte: HBM bandwidth bounds it.
"""
from perfbench import costs_moe
from perfbench.references.moe_lm import dims

PATTERN = r"^held_experts_ffn(\.|$)"


def read(b, chip):
    tr, c = b.trace, b.counts
    if tr is None or chip is None or not c.get("moe_experts_hit"):
        return None
    secs = tr.op_seconds(PATTERN)
    if secs <= 0:
        return None
    z = dims(b.config)
    p = costs_moe.moe_lm_params(z)
    need = c["moe_experts_hit"] * costs_moe.expert_bytes(p) \
        + c["moe_assignments"] * z["d"] * 2
    return 100.0 * need / chip.hbm_bw / secs
