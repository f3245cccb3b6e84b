"""Operations and bytes that the algorithms need, from their shapes alone.

These are the yardstick of every roofline and utilization metric: they count
the work the mathematics asks for at the configuration's sizes, never the
padded tiles or fused extras a kernel happens to compute.
"""
from __future__ import annotations

F32 = 4
BF16 = 2


# --------------------------------------------------------------- LASSO solve

def lasso_sample_size(n: int, b: float) -> int:
    """Columns drawn per iteration: m = floor(b * n), at least 1."""
    return max(int(b * n), 1)


def gram_flops(d: int, m: int) -> float:
    """G = Xs Xs^T for Xs (d, m): a d x d x m product."""
    return 2.0 * d * d * m


def gram_bytes(d: int, m: int) -> float:
    """Least HBM traffic of one sampled Gram: read Xs once, write G (f32)."""
    return F32 * (d * m + d * d)


def lasso_solve_flops(d: int, n: int, T: int, b: float,
                      power_iters: int = 50) -> float:
    """FLOPs of one stochastic FISTA solve with a power-iteration step size.

    Full Gram X X^T / n for the step size, the power iteration on it, and per
    iteration the sampled Gram, the sampled X y, and the update G v - R.
    """
    m = lasso_sample_size(n, b)
    step = gram_flops(d, n) + power_iters * 2.0 * d * d
    per_iter = gram_flops(d, m) + 2.0 * d * m + 2.0 * d * d
    return step + T * per_iter


# ------------------------------------------------------ decoder-only model

def kv_bytes_per_token(n_layers: int, n_kv_heads: int, head_dim: int,
                       itemsize: int = BF16) -> float:
    """K and V of one token over all layers."""
    return 2.0 * n_layers * n_kv_heads * head_dim * itemsize


def dense_lm_params(d: int, n_layers: int, n_heads: int, n_kv_heads: int,
                    head_dim: int, d_ff: int, vocab: int) -> dict:
    """Parameter counts of a GQA SwiGLU decoder with an untied head."""
    attn = d * (n_heads + 2 * n_kv_heads) * head_dim + n_heads * head_dim * d
    mlp = 3 * d * d_ff
    return dict(layers=n_layers * (attn + mlp + 2 * d), head=d * vocab,
                embed=vocab * d, final_norm=d)


def dense_lm_token_flops(p: dict) -> float:
    """Forward FLOPs of one token outside attention: every weight used once,
    2 FLOPs a weight."""
    return 2.0 * (p["layers"] + p["head"])


def attention_flops(kv_token_reads: float, n_layers: int, n_heads: int,
                    head_dim: int) -> float:
    """QK^T and PV over all layers, for ``kv_token_reads`` (query, cached
    position) pairs."""
    return 4.0 * n_layers * n_heads * head_dim * kv_token_reads


def dense_lm_step_weight_bytes(p: dict, itemsize: int = BF16) -> float:
    """Least weight traffic of one decode step: every matrix read once in the
    compute precision. The embedding is a gather of a few rows: not
    counted."""
    return itemsize * (p["layers"] + p["head"] + p["final_norm"])
