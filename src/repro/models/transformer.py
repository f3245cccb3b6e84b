"""Model orchestration: init / forward / loss / cache / decode for all six
architecture families, with scan-over-layers + optional remat and GSPMD
sharding constraints threaded via ``constrain(x, logical_spec)``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import rms_norm, embed_init, dense_init
from repro.models.blocks import (
    init_dense_block, dense_block, init_moe_block, moe_block,
    init_attn, attn_forward, init_attn_cache, decoding)
from repro.models.moe import EXPERT_WEIGHTS
from repro.models.ssm import (
    init_mamba2, mamba2_forward, init_mamba2_state, mamba2_decode_step)
from repro.models import encdec
from repro.models.frontend import mrope_positions
from repro.kernels import registry


def _no_constrain(x, spec):
    return x


def _stack_init(init_fn, key, n: int):
    return jax.vmap(init_fn)(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg, key, dtype=jnp.float32):
    ks = jax.random.split(key, 8)
    params = dict(embed=embed_init(ks[0], cfg.vocab, cfg.d_model, dtype),
                  ln_f=jnp.ones((cfg.d_model,), dtype))
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab),
                                       dtype=dtype)

    fam = cfg.family
    if fam in ("dense", "vlm"):
        params["layers"] = _stack_init(
            lambda k: init_dense_block(k, cfg, dtype), ks[2], cfg.n_layers)
    elif fam == "moe":
        n_moe = cfg.n_layers - int(cfg.first_layer_dense)
        if cfg.first_layer_dense:
            dense_cfg = cfg.scaled(d_ff=cfg.dense_d_ff)
            params["dense0"] = init_dense_block(ks[3], dense_cfg, dtype)
        params["layers"] = _stack_init(
            lambda k: init_moe_block(k, cfg, dtype), ks[2], n_moe)
    elif fam == "ssm":
        params["layers"] = _stack_init(
            lambda k: dict(ln=jnp.ones((cfg.d_model,), dtype),
                           mamba=init_mamba2(k, cfg.d_model, cfg, dtype)),
            ks[2], cfg.n_layers)
    elif fam == "hybrid":
        n_super = cfg.n_layers // cfg.shared_attn_period
        flat = _stack_init(
            lambda k: dict(ln=jnp.ones((cfg.d_model,), dtype),
                           mamba=init_mamba2(k, cfg.d_model, cfg, dtype)),
            ks[2], cfg.n_layers)
        params["layers"] = jax.tree.map(
            lambda x: x.reshape(n_super, cfg.shared_attn_period, *x.shape[1:]),
            flat)
        params["shared"] = dict(
            ln=jnp.ones((cfg.d_model,), dtype),
            attn=init_attn(ks[4], cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                           cfg.head_dim, dtype))
    elif fam == "audio":
        params["encoder"] = _stack_init(
            lambda k: encdec.init_enc_block(k, cfg, dtype), ks[5],
            cfg.n_enc_layers)
        params["enc_ln"] = jnp.ones((cfg.d_model,), dtype)
        params["layers"] = _stack_init(
            lambda k: encdec.init_dec_block(k, cfg, dtype), ks[2],
            cfg.n_layers)
    else:
        raise ValueError(f"unknown family {fam}")
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _maybe_remat(fn, remat: bool):
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable) \
        if remat else fn


def _embed_inputs(params, cfg, batch, constrain):
    """Token (+modality prefix) embedding and position streams."""
    fam = cfg.family
    emb = params["embed"]
    pos_info = {}
    if fam == "vlm":
        tok = jnp.take(emb, batch["tokens"], axis=0).astype(jnp.bfloat16)
        x = jnp.concatenate(
            [batch["vision_embeds"].astype(jnp.bfloat16), tok], axis=1)
        B, S = x.shape[0], x.shape[1]
        pos_info["mrope"] = mrope_positions(
            cfg.vision_patches, batch["tokens"].shape[1], B)
    elif fam == "audio":
        x = jnp.take(emb, batch["tokens"], axis=0).astype(jnp.bfloat16)
        B, S = x.shape[0], x.shape[1]
        pos_info["positions"] = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32), (B, S))
    else:
        x = jnp.take(emb, batch["tokens"], axis=0).astype(jnp.bfloat16)
        B, S = x.shape[0], x.shape[1]
        pos_info["positions"] = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32), (B, S))
    return constrain(x, ("batch", None, None)), pos_info


def _logits(params, cfg, x, constrain):
    x = rms_norm(x, params["ln_f"].astype(jnp.float32), cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(x.dtype)
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    return constrain(logits, ("batch", None, "tp"))


def _forward(params, cfg, batch, *, constrain=_no_constrain,
             remat: bool = False, last_only: bool = False):
    fam = cfg.family
    x, pos_info = _embed_inputs(params, cfg, batch, constrain)
    aux = jnp.zeros((), jnp.float32)

    if fam in ("dense", "vlm"):
        def body(x, lp):
            y, _ = dense_block(lp, x, cfg, pos_info=pos_info,
                               constrain=constrain)
            return y, None
        x, _ = jax.lax.scan(_maybe_remat(body, remat), x, params["layers"])

    elif fam == "moe":
        if cfg.first_layer_dense:
            dense_cfg = cfg.scaled(d_ff=cfg.dense_d_ff)
            x, _ = dense_block(params["dense0"], x, dense_cfg,
                               pos_info=pos_info, constrain=constrain)

        def body(carry, lp):
            x, aux = carry
            y, _, a, _ = moe_block(lp, x, cfg, pos_info=pos_info,
                                   constrain=constrain)
            return (y, aux + a), None
        (x, aux), _ = jax.lax.scan(_maybe_remat(body, remat), (x, aux),
                                   params["layers"])

    elif fam == "ssm":
        def body(x, lp):
            h = mamba2_forward(lp["mamba"],
                               rms_norm(x, lp["ln"], cfg.norm_eps), cfg,
                               constrain)
            return x + h, None
        x, _ = jax.lax.scan(_maybe_remat(body, remat), x, params["layers"])

    elif fam == "hybrid":
        shared = params["shared"]

        def super_body(x, sb):
            def inner(x, lp):
                h = mamba2_forward(lp["mamba"],
                                   rms_norm(x, lp["ln"], cfg.norm_eps), cfg,
                                   constrain)
                return x + h, None
            x, _ = jax.lax.scan(inner, x, sb)
            h, _ = attn_forward(
                shared["attn"], rms_norm(x, shared["ln"], cfg.norm_eps),
                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                head_dim=cfg.head_dim, positions=pos_info["positions"],
                rope_theta=cfg.rope_theta, constrain=constrain)
            return x + h, None
        x, _ = jax.lax.scan(_maybe_remat(super_body, remat), x,
                            params["layers"])

    elif fam == "audio":
        enc = constrain(batch["enc_embeds"].astype(jnp.bfloat16),
                        ("batch", None, None))

        def enc_body(h, lp):
            return encdec.enc_block(lp, h, cfg, constrain), None
        enc, _ = jax.lax.scan(_maybe_remat(enc_body, remat), enc,
                              params["encoder"])
        enc = rms_norm(enc, params["enc_ln"].astype(jnp.float32), cfg.norm_eps)

        def dec_body(x, lp):
            kv = encdec.cross_kv(lp, enc, cfg, constrain)
            y, _ = encdec.dec_block(lp, x, cfg, kv_cross=kv,
                                    positions=pos_info["positions"],
                                    constrain=constrain)
            return y, None
        x, _ = jax.lax.scan(_maybe_remat(dec_body, remat), x, params["layers"])

    else:
        raise ValueError(fam)

    if last_only:
        x = x[:, -1:]
    return _logits(params, cfg, x, constrain), aux


def forward(params, cfg, batch, *, constrain=_no_constrain,
            remat: bool = False, last_only: bool = False):
    """Teacher-forced forward. Returns (logits, aux_loss).

    last_only: project logits for the final position only (prefill path —
    avoids materializing the (B, S, V) tensor at 32k sequence lengths).

    Kernels dispatch through ``repro.kernels.registry``."""
    return _forward(params, cfg, batch, constrain=constrain, remat=remat,
                    last_only=last_only)


def loss_fn(params, cfg, batch, *, constrain=_no_constrain,
            remat: bool = False,
            aux_weight: float = 0.01, vocab_chunks: int = 1):
    """Next-token cross entropy (+ MoE load-balance aux).

    Runs the forward under ``registry.grad_safe()``, now a narrow per-impl
    guard: the stock Pallas kernels register custom VJPs, so under
    ``REPRO_BACKEND=pallas`` differentiation traces their backward kernels
    (FA-2-style flash attention, reverse chunk-scan SSD); only an impl
    without a VJP is routed to its XLA fallback."""
    with registry.grad_safe():
        logits, aux = _forward(params, cfg, batch, constrain=constrain,
                               remat=remat)
    labels = batch["labels"]
    if cfg.family == "vlm":
        # loss over the text tail only
        logits = logits[:, cfg.vision_patches:]
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = (logz - gold).mean()
    return ce + aux_weight * aux


# ---------------------------------------------------------------------------
# decode (serve path)
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16,
               enc_len: Optional[int] = None):
    """Cache pytree for one-token-at-a-time decode against max_len context."""
    fam = cfg.family
    cache = dict(pos=jnp.zeros((), jnp.int32))
    kv = lambda: init_attn_cache(batch, max_len, cfg.n_kv_heads,
                                 cfg.head_dim, dtype)
    if fam in ("dense", "vlm", "moe"):
        n = cfg.n_layers - int(cfg.family == "moe" and cfg.first_layer_dense)
        cache["layers"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n, *x.shape)).copy(), kv())
        if cfg.family == "moe" and cfg.first_layer_dense:
            cache["dense0"] = jax.tree.map(lambda x: x[None], kv())
        if cfg.family == "moe":
            # the held experts' work of the steps since the counts were last
            # zeroed (the serving engine's decode block zeroes them)
            cache["moe"] = dict(assignments=jnp.zeros((), jnp.int32),
                                experts_hit=jnp.zeros((), jnp.int32))
    elif fam == "ssm":
        st = init_mamba2_state(batch, cfg.d_model, cfg)
        cache["layers"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers, *x.shape)).copy(), st)
    elif fam == "hybrid":
        n_super = cfg.n_layers // cfg.shared_attn_period
        st = init_mamba2_state(batch, cfg.d_model, cfg)
        cache["layers"] = jax.tree.map(
            lambda x: jnp.broadcast_to(
                x, (n_super, cfg.shared_attn_period, *x.shape)).copy(), st)
        cache["shared"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_super, *x.shape)).copy(), kv())
    elif fam == "audio":
        cache["layers"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers, *x.shape)).copy(),
            init_attn_cache(batch, max_len, cfg.n_kv_heads, cfg.head_dim,
                            dtype))
        el = enc_len or max_len
        z = jnp.zeros((cfg.n_layers, batch, el, cfg.n_kv_heads, cfg.head_dim),
                      dtype)
        cache["cross"] = dict(k=z, v=z)
    return cache


def _attn_scan(body, x, xs, kv):
    """Scan ``body(x, xs_i, kv, i) -> (x, kv, y)`` over the layers. ``kv``,
    the attention K/V stacked over those layers, rides the carry whole:
    each layer writes its rows into it and reads its own layer where it
    lies, where a scanned slice and a stacked result would copy it every
    step (nothing reads the carried stack after the write, so XLA updates
    it in place). Returns (x, kv, ys)."""
    def step(carry, inp):
        x, kv = carry
        x, kv, y = body(x, inp[0], kv, inp[1])
        return (x, kv), y
    n = jax.tree.leaves(kv)[0].shape[0]
    (x, kv), ys = jax.lax.scan(step, (x, kv), (xs, jnp.arange(n)))
    return x, kv, ys


def _decode_step(params, cfg, cache, tokens, *, positions=None,
                 constrain=_no_constrain, page_table=None):
    """One decode step: tokens (B, 1) -> (logits (B, 1, V), new cache).

    positions: optional (B,) int32 per-slot decode depths (continuous-batching
    serve path). When given, each batch row RoPEs at its own position and
    writes its KV at its own cache index; ``cache["pos"]`` is ignored for
    addressing (the caller owns per-slot lengths) but still advanced so the
    pytree keeps its classic-path meaning. Default: the scalar ``cache["pos"]``
    shared by the whole batch.

    page_table: (B, pages_per_slot) int32, required with per-row
    ``positions`` wherever the model has attention: the attention K/V
    leaves are then a paged pool (see ``repro.serve.paging``) and every
    attention read/write goes through the table. Either layout is stacked
    over the layers that use it and rides their scan (``_attn_scan``).
    Recurrent leaves (mamba conv/ssm, whisper cross-K/V) are pageless and
    are scanned per layer."""
    fam = cfg.family
    B = tokens.shape[0]
    if positions is None:
        pos = cache["pos"]
        positions = jnp.broadcast_to(pos, (B, 1)).astype(jnp.int32)
    else:
        pos = jnp.asarray(positions, jnp.int32)            # (B,) per-slot
        positions = pos[:, None]
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.bfloat16)
    x = constrain(x, ("batch", None, None))
    pos_info = dict(positions=positions)
    if cfg.family == "vlm":
        # after the vision prefix all three M-RoPE streams advance together
        pos_info = dict(mrope=jnp.broadcast_to(positions, (3, B, 1)))
    attn = dict(cache_pos=pos, constrain=constrain, page_table=page_table)

    if fam in ("dense", "vlm", "moe"):
        if fam == "moe" and cfg.first_layer_dense:
            dense_cfg = cfg.scaled(d_ff=cfg.dense_d_ff)
            x, c0 = dense_block(params["dense0"], x, dense_cfg,
                                pos_info=pos_info, cache=cache["dense0"],
                                layer=0, **attn)
            cache = dict(cache, dense0=c0)

        layers = params["layers"]
        if fam == "moe":
            # the held experts' weights stay stacked over the layers, out of
            # the scan: the expert kernel reads each layer's blocks in place,
            # where a scanned slice would copy them every step
            experts = {k: layers["moe"][k] for k in EXPERT_WEIGHTS}
            layers = dict(layers, moe={k: v for k, v in layers["moe"].items()
                                       if k not in experts})

        def body(x, lp, kv, i):
            if fam == "moe":
                lp = dict(lp, moe=dict(lp["moe"], **experts))
                y, kv, _, counts = moe_block(lp, x, cfg, pos_info=pos_info,
                                             cache=kv, layer=i, **attn)
                return y, kv, counts
            y, kv = dense_block(lp, x, cfg, pos_info=pos_info, cache=kv,
                                layer=i, **attn)
            return y, kv, None
        x, new_caches, counts = _attn_scan(body, x, layers, cache["layers"])
        if fam == "moe":
            cache = dict(cache, moe=jax.tree.map(
                lambda c, n: c + n.sum(), cache["moe"], counts))
        cache = dict(cache, layers=new_caches)

    elif fam == "ssm":
        def body(x, inp):
            lp, st = inp
            h, new_st = mamba2_decode_step(
                lp["mamba"], rms_norm(x, lp["ln"], cfg.norm_eps), st, cfg,
                constrain)
            return x + h, new_st
        x, new_states = jax.lax.scan(body, x, (params["layers"],
                                               cache["layers"]))
        cache = dict(cache, layers=new_states)

    elif fam == "hybrid":
        shared = params["shared"]

        def super_body(x, inp, skv, i):
            def inner(x, inp2):
                lp, s = inp2
                h, ns = mamba2_decode_step(
                    lp["mamba"], rms_norm(x, lp["ln"], cfg.norm_eps), s, cfg,
                    constrain)
                return x + h, ns
            x, new_st = jax.lax.scan(inner, x, inp)
            h, skv = attn_forward(
                shared["attn"], rms_norm(x, shared["ln"], cfg.norm_eps),
                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                head_dim=cfg.head_dim, positions=positions,
                rope_theta=cfg.rope_theta, cache=skv, layer=i, **attn)
            return x + h, skv, new_st
        x, new_skv, new_st = _attn_scan(
            super_body, x, (params["layers"], cache["layers"]),
            cache["shared"])
        cache = dict(cache, layers=new_st, shared=new_skv)

    elif fam == "audio":
        def body(x, inp, kv, i):
            lp, cross = inp
            y, kv = encdec.dec_block(lp, x, cfg, kv_cross=(cross["k"],
                                                           cross["v"]),
                                     positions=positions, cache=kv,
                                     layer=i, **attn)
            return y, kv, None
        x, new_caches, _ = _attn_scan(body, x,
                                      (params["layers"], cache["cross"]),
                                      cache["layers"])
        cache = dict(cache, layers=new_caches)

    else:
        raise ValueError(fam)

    with jax.named_scope("decode.head"):
        logits = _logits(params, cfg, x, constrain)
    cache = dict(cache, pos=cache["pos"] + 1)   # stays scalar in both modes
    return logits, cache


def decode_step(params, cfg, cache, tokens, *, positions=None,
                constrain=_no_constrain, page_table=None):
    """One decode step (see ``_decode_step`` for shapes/positions semantics).

    Kernels dispatch through ``repro.kernels.registry``; the step's ops are
    named ``decode.attn`` / ``decode.kv_write`` / ``decode.mlp`` (MoE:
    ``decode.router`` and ``decode.experts`` inside it) / ``decode.head``
    (``jax.named_scope``). MoE steps add their expert counts to
    ``cache["moe"]``."""
    with decoding():
        return _decode_step(params, cfg, cache, tokens, positions=positions,
                            constrain=constrain, page_table=page_table)


def prefill_audio_cache(params, cfg, cache, enc_embeds, *,
                        constrain=_no_constrain):
    """Run the whisper encoder and fill per-layer cross-attention K/V."""
    return _prefill_audio_cache(params, cfg, cache, enc_embeds,
                                constrain=constrain)


def _prefill_audio_cache(params, cfg, cache, enc_embeds, *,
                         constrain=_no_constrain):
    enc = constrain(enc_embeds.astype(jnp.bfloat16), ("batch", None, None))

    def enc_body(h, lp):
        return encdec.enc_block(lp, h, cfg, constrain), None
    enc, _ = jax.lax.scan(enc_body, enc, params["encoder"])
    enc = rms_norm(enc, params["enc_ln"].astype(jnp.float32), cfg.norm_eps)

    def kv_body(_, lp):
        k, v = encdec.cross_kv(lp, enc, cfg, constrain)
        return None, dict(k=k.astype(cache["cross"]["k"].dtype),
                          v=v.astype(cache["cross"]["v"].dtype))
    _, cross = jax.lax.scan(kv_body, None, params["layers"])
    return dict(cache, cross=cross)
