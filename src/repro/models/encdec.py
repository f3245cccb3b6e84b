"""Whisper-style encoder-decoder backbone. The audio conv frontend is a STUB
per the assignment: callers provide precomputed frame embeddings (B,S,d)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import rms_norm, dense_init
from repro.models.blocks import init_attn, attn_forward
from repro.models.mlp import init_gelu_mlp, gelu_mlp


def init_enc_block(key, cfg, dtype=jnp.float32):
    ka, km = jax.random.split(key)
    return dict(
        ln1=jnp.ones((cfg.d_model,), dtype),
        attn=init_attn(ka, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                       cfg.head_dim, dtype),
        ln2=jnp.ones((cfg.d_model,), dtype),
        mlp=init_gelu_mlp(km, cfg.d_model, cfg.d_ff, dtype),
    )


def enc_block(params, x, cfg, constrain):
    B, S, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    h, _ = attn_forward(params["attn"], rms_norm(x, params["ln1"], cfg.norm_eps),
                        n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                        head_dim=cfg.head_dim, positions=pos, causal=False,
                        rope_theta=cfg.rope_theta, constrain=constrain)
    x = x + h
    return x + gelu_mlp(params["mlp"],
                        rms_norm(x, params["ln2"], cfg.norm_eps), constrain)


def init_dec_block(key, cfg, dtype=jnp.float32):
    ka, kc, km = jax.random.split(key, 3)
    return dict(
        ln1=jnp.ones((cfg.d_model,), dtype),
        self_attn=init_attn(ka, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                            cfg.head_dim, dtype),
        ln2=jnp.ones((cfg.d_model,), dtype),
        cross_attn=init_attn(kc, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                             cfg.head_dim, dtype),
        ln3=jnp.ones((cfg.d_model,), dtype),
        mlp=init_gelu_mlp(km, cfg.d_model, cfg.d_ff, dtype),
    )


def cross_kv(params, enc_out, cfg, constrain):
    """Precompute cross-attention K/V from encoder output (cached at decode)."""
    B, S, _ = enc_out.shape
    k = jnp.einsum("bsd,dh->bsh", enc_out,
                   params["cross_attn"]["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,dh->bsh", enc_out,
                   params["cross_attn"]["wv"].astype(enc_out.dtype))
    k = constrain(k, ("batch", None, "tp"))
    v = constrain(v, ("batch", None, "tp"))
    return (k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim),
            v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim))


def dec_block(params, x, cfg, *, kv_cross, positions, cache=None,
              cache_pos=None, constrain=lambda x, s: x, page_table=None,
              layer=None):
    # cache/page_table/layer address the decoder self-attn K/V only (see
    # ``attn_forward``); the cross K/V is enc_len-shaped request state and
    # stays in slot layout
    h, new_cache = attn_forward(
        params["self_attn"], rms_norm(x, params["ln1"], cfg.norm_eps),
        n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
        positions=positions, rope_theta=cfg.rope_theta, cache=cache,
        cache_pos=cache_pos, constrain=constrain, page_table=page_table,
        layer=layer)
    x = x + h
    h, _ = attn_forward(
        params["cross_attn"], rms_norm(x, params["ln2"], cfg.norm_eps),
        n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
        causal=False, kv_override=kv_cross, constrain=constrain)
    x = x + h
    return x + gelu_mlp(params["mlp"],
                        rms_norm(x, params["ln3"], cfg.norm_eps), constrain), \
        new_cache
