"""Transformer blocks: GQA attention block (with KV cache), dense/MoE blocks,
and the Zamba2-style hybrid superblock built from Mamba2 + shared attention.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import (rms_norm, apply_rope, apply_mrope, dense_init)
from repro.models.attention import attention, paged_attention, quantize_kv
from repro.models.mlp import init_swiglu, swiglu
from repro.models.moe import (init_moe, moe_ffn, route, held_experts,
                              expert_counts)


# ---------------------------------------------------------------------------
# Attention block
# ---------------------------------------------------------------------------

_trace = threading.local()


@contextlib.contextmanager
def decoding():
    """Mark the trace of one decode step (``models.decode_step`` opens it
    once), so that ``decode_scope`` names the parts of that step."""
    prev = getattr(_trace, "decoding", False)
    _trace.decoding = True
    try:
        yield
    finally:
        _trace.decoding = prev


def decode_scope(part: str):
    """``jax.named_scope("decode.<part>")`` inside ``decoding()``, so that a
    profile's readers can tell which part of the decode step an op belongs
    to; nothing elsewhere (training, prefill). Scopes are compile-time
    metadata (``op_name``): no run-time cost, and no HLO instruction is
    renamed."""
    if getattr(_trace, "decoding", False):
        return jax.named_scope("decode." + part)
    return contextlib.nullcontext()


def _tp_size(constrain) -> Optional[int]:
    """Model-axis size behind a Rules.constrain bound method (None off-mesh)."""
    rules = getattr(constrain, "__self__", None)
    if rules is None or getattr(rules, "tp", None) is None:
        return None
    return rules.mesh.shape[rules.tp]


def _kv_factorizes(n_kv: int, group: int, tp: int) -> bool:
    """True if GSPMD can tile (n_kv x group) q-heads exactly onto tp shards
    without padding — in which case the flat projection constraint suffices
    and forcing a padded kv-head tiling only hurts (llama kv=8 on tp=16:
    collective term 3.2 s -> 20 s). When no factorization exists (phi3 10x4,
    qwen2-vl 2x6 on tp=16) GSPMD collapses to a 2-way attention split unless
    we pad the kv-head axis explicitly (phi3 prefill: 3.6x memory-term
    win)."""
    for a in range(1, n_kv + 1):
        if n_kv % a == 0 and tp % a == 0:
            rest = tp // a
            if rest <= group and group % rest == 0:
                return True
    # padding n_kv up to tp costs tp/n_kv x KV memory/compute — worth it for
    # phi3 (10 -> 16, 1.6x) but not for tiny-kv archs (qwen2-vl 2 -> 16, 8x:
    # measured 10x collective regression). Cap the acceptable padding at 2x.
    if tp / n_kv > 2:
        return True
    return False


def init_attn(key, d: int, n_heads: int, n_kv: int, head_dim: int,
              dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    return dict(
        wq=dense_init(ks[0], (d, n_heads * head_dim), dtype=dtype),
        wk=dense_init(ks[1], (d, n_kv * head_dim), dtype=dtype),
        wv=dense_init(ks[2], (d, n_kv * head_dim), dtype=dtype),
        wo=dense_init(ks[3], (n_heads * head_dim, d), dtype=dtype),
    )


def attn_forward(params, x, *, n_heads: int, n_kv: int, head_dim: int,
                 positions=None, mrope_pos=None, rope_theta: float = 1e4,
                 causal: bool = True, cache: Optional[dict] = None,
                 cache_pos=None, kv_override=None, constrain=lambda x, s: x,
                 attn_chunk: Optional[int] = None, page_table=None,
                 layer=None):
    """GQA attention. x (B,S,d).

    cache: the K/V of every layer that uses it, stacked over those layers;
    this call writes its rows into layer ``layer`` of the stack and reads
    that layer where it lies. Returns the stack updated.
    - scalar ``cache_pos`` (the model's plain whole-batch decode): slot rows
      dict(k=(L,B,Smax,Hkv,Dh), v=...), the whole batch written at
      ``cache_pos``.
    - (B,) int32 ``cache_pos`` (the serving engine: each row at its own
      depth) needs ``page_table``, (B, pages_per_slot) int32: the leaves
      are a paged pool (L, num_pages, page_size, Hkv, Dh), plus int8 scale
      siblings (L, num_pages, page_size, Hkv), and position p of batch row
      b lives at pool page ``page_table[b, p // page_size]``, row
      ``p % page_size`` (decode-only: S == 1).
    kv_override: (k, v) tuple for cross-attention (whisper decoder).
    Returns (out, new_cache).
    """
    B, S, d = x.shape
    group = n_heads // max(n_kv, 1)
    tp = _tp_size(constrain)
    pad_kv = tp is not None and tp > 1 and not _kv_factorizes(n_kv, group, tp)

    q = jnp.einsum("bsd,dh->bsh", x, params["wq"].astype(x.dtype))
    # 4D head-axis constraint (see _kv_factorizes): for tp-indivisible head
    # layouts GSPMD otherwise collapses attention to a 2-way split.
    q = q.reshape(B, S, n_heads, head_dim)
    q = constrain(q, ("batch", None, "tp", None))

    if kv_override is None:
        k = jnp.einsum("bsd,dh->bsh", x, params["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dh->bsh", x, params["wv"].astype(x.dtype))
        if pad_kv:
            k = constrain(k.reshape(B, S, n_kv, head_dim),
                          ("batch", None, "tp", None))
            v = constrain(v.reshape(B, S, n_kv, head_dim),
                          ("batch", None, "tp", None))
        else:
            k = constrain(k, ("batch", None, "tp")).reshape(
                B, S, n_kv, head_dim)
            v = constrain(v, ("batch", None, "tp")).reshape(
                B, S, n_kv, head_dim)
        if mrope_pos is not None:
            q = apply_mrope(q, mrope_pos, theta=rope_theta)
            k = apply_mrope(k, mrope_pos, theta=rope_theta)
        elif positions is not None:
            q = apply_rope(q, positions, theta=rope_theta)
            k = apply_rope(k, positions, theta=rope_theta)
    else:
        k, v = kv_override

    new_cache = cache
    kv_valid = None
    per_row = cache is not None and getattr(cache_pos, "ndim", 0) > 0
    if per_row != (page_table is not None):
        raise ValueError("per-row cache_pos and a page_table go together: "
                         "the engine's decode reads a paged pool")
    if page_table is not None:
        if S != 1:
            raise ValueError("paged KV cache is decode-only: S == 1")
        P_pg = cache["k"].shape[2]
        pidx = jnp.take_along_axis(page_table, cache_pos[:, None] // P_pg,
                                   axis=1)[:, 0]
        off = cache_pos % P_pg
        # B rows a step, scattered into the pool where it lies
        with decode_scope("kv_write"):
            if "k_scale" in cache:
                # int8 pool: quantize on scatter — codes and their
                # per-(row, head) scales land in the same page/row, so a
                # page is self-describing and CoW/defrag/trie sharing move
                # both together
                (kq, ks), (vq, vs) = quantize_kv(k[:, 0]), quantize_kv(v[:, 0])
                rows = dict(k=kq, v=vq, k_scale=ks, v_scale=vs)
            else:
                rows = dict(k=k[:, 0], v=v[:, 0])
            new_cache = {n: cache[n].at[layer, pidx, off].set(
                r.astype(cache[n].dtype)) for n, r in rows.items()}
        o = paged_attention(q, new_cache["k"], new_cache["v"], page_table,
                            cache_pos + 1, layer,
                            k_scale=new_cache.get("k_scale"),
                            v_scale=new_cache.get("v_scale"),
                            chunk=attn_chunk)
        o = o.reshape(B, S, n_heads * head_dim)
        out = jnp.einsum("bsh,hd->bsd", o, params["wo"].astype(x.dtype))
        return constrain(out, ("batch", None, None)), new_cache
    if cache is not None:
        with decode_scope("kv_write"):
            new_cache = {n: jax.lax.dynamic_update_slice(
                cache[n], u[None].astype(cache[n].dtype),
                (layer, 0, cache_pos, 0, 0)) for n, u in (("k", k), ("v", v))}
        k, v = (jax.lax.dynamic_index_in_dim(new_cache[n], layer,
                                             keepdims=False) for n in "kv")
        kv_valid = cache_pos + S
        causal = False if S == 1 else causal    # single query: mask via kv_valid

    o = attention(q, k, v, causal=causal, chunk=attn_chunk,
                  kv_valid_len=kv_valid)
    o = o.reshape(B, S, n_heads * head_dim)
    out = jnp.einsum("bsh,hd->bsd", o, params["wo"].astype(x.dtype))
    return constrain(out, ("batch", None, None)), new_cache


def init_attn_cache(batch: int, max_len: int, n_kv: int, head_dim: int,
                    dtype=jnp.bfloat16):
    z = jnp.zeros((batch, max_len, n_kv, head_dim), dtype)
    return dict(k=z, v=z)


# ---------------------------------------------------------------------------
# Decoder blocks (pre-norm residual)
# ---------------------------------------------------------------------------

def init_dense_block(key, cfg, dtype=jnp.float32):
    ka, km, kn = jax.random.split(key, 3)
    return dict(
        ln1=jnp.ones((cfg.d_model,), dtype),
        attn=init_attn(ka, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                       cfg.head_dim, dtype),
        ln2=jnp.ones((cfg.d_model,), dtype),
        mlp=init_swiglu(km, cfg.d_model, cfg.d_ff, dtype),
    )


def dense_block(params, x, cfg, *, pos_info, cache=None, cache_pos=None,
                constrain=lambda x, s: x, page_table=None, layer=None):
    """``cache``/``layer``: see ``attn_forward``."""
    with decode_scope("attn"):
        h, new_cache = attn_forward(
            params["attn"], rms_norm(x, params["ln1"], cfg.norm_eps),
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            positions=pos_info.get("positions"),
            mrope_pos=pos_info.get("mrope"), rope_theta=cfg.rope_theta,
            cache=cache, cache_pos=cache_pos, constrain=constrain,
            page_table=page_table, layer=layer)
        x = x + h
    with decode_scope("mlp"):
        x = x + swiglu(params["mlp"],
                       rms_norm(x, params["ln2"], cfg.norm_eps), constrain)
    return x, new_cache


def init_moe_block(key, cfg, dtype=jnp.float32):
    ka, km, kn = jax.random.split(key, 3)
    shared_ff = cfg.moe_d_ff * cfg.n_shared_experts
    return dict(
        ln1=jnp.ones((cfg.d_model,), dtype),
        attn=init_attn(ka, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                       cfg.head_dim, dtype),
        ln2=jnp.ones((cfg.d_model,), dtype),
        moe=init_moe(km, cfg.d_model, cfg.moe_d_ff, cfg.n_experts,
                     cfg.n_shared_experts, shared_ff, dtype,
                     n_held=cfg.experts_held),
    )


def moe_block(params, x, cfg, *, pos_info, cache=None, cache_pos=None,
              constrain=lambda x, s: x, page_table=None, layer=None):
    """Returns (x, new_cache, aux, counts). Without a cache, over every
    expert (training, prefill), the capacity formulation gives the
    load-balance ``aux`` and no counts; decoding, or holding a share of the
    experts, the dropless held-experts layer gives ``counts``
    (``moe.expert_counts``) and a zero ``aux``. ``layer``: see
    ``moe.held_experts`` and ``attn_forward``."""
    with decode_scope("attn"):
        h, new_cache = attn_forward(
            params["attn"], rms_norm(x, params["ln1"], cfg.norm_eps),
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            positions=pos_info.get("positions"),
            mrope_pos=pos_info.get("mrope"), rope_theta=cfg.rope_theta,
            cache=cache, cache_pos=cache_pos, constrain=constrain,
            page_table=page_table, layer=layer)
        x = x + h
    with decode_scope("mlp"):
        mp = params["moe"]
        h = rms_norm(x, params["ln2"], cfg.norm_eps)
        if cache is None and cfg.experts_held == cfg.n_experts:
            m, aux = moe_ffn(mp, h, top_k=cfg.top_k,
                             capacity_factor=cfg.capacity_factor,
                             norm_topk_prob=cfg.norm_topk_prob,
                             constrain=constrain)
            return x + m, new_cache, aux, None
        with decode_scope("router"):
            gates, sel, _ = route(mp["router"], h, top_k=cfg.top_k,
                                  norm_topk_prob=cfg.norm_topk_prob)
            counts = expert_counts(sel, cfg.first_expert, cfg.experts_held)
        with decode_scope("experts"):
            m = held_experts(mp, h, gates, sel,
                             first_expert=cfg.first_expert, layer=layer)
        if "shared" in mp:
            m = m + swiglu(mp["shared"], h, constrain)
        x = x + m.astype(x.dtype)
    return x, new_cache, jnp.zeros((), jnp.float32), counts
