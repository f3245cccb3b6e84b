"""deepseek-moe-16b [moe]: fine-grained, 2 shared + 64 routed top-6 with
softmax gates that are not renormalised; first layer dense.
[arXiv:2401.06066; hf:deepseek-ai/deepseek-moe-16b-base]"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-moe-16b", family="moe", n_layers=28, d_model=2048,
    n_heads=16, n_kv_heads=16, d_ff=10944, vocab=102400, norm_eps=1e-6,
    n_experts=64, top_k=6, n_shared_experts=2, moe_d_ff=1408,
    norm_topk_prob=False, first_layer_dense=True, dense_d_ff=10944,
)
