"""Plain float32 reference for a DeepSeekMoE decoder-only LM, at one chip's
share of the routed experts.

Written from arXiv:2401.06066 and the published ``config.json``
(deepseek-ai/deepseek-moe-16b-base): pre-norm RMSNorm, multi-head attention
with rotary positions, ``first_k_dense_replace`` leading dense SwiGLU
layers, then layers whose feed-forward is ``n_shared_experts`` shared
experts (one SwiGLU of ``n_shared_experts * moe_intermediate_size``) plus
routed experts: a softmax over every routed expert, the top
``num_experts_per_tok``, gates renormalised only if ``norm_topk_prob``;
untied output head. Straightforward ``jax.numpy`` at the highest matmul
precision, with no kernel, cache or batching.

Departures, noted:
- The expert share: the chip holds routed experts ``first_routed_expert ..
  + n_routed_experts - 1`` of ``n_routed_experts_published``. The router
  keeps its published width and top-k; the part that experts held on other
  chips would add is left out, exactly as in the program.
- Each held expert runs over every token and its output is weighted by the
  token's gate for it, zero where the token did not choose it: the same sum
  as running it over the routed tokens only.
- Rotary positions rotate interleaved (even, odd) lane pairs, the layout
  the program stores its q/k projections in; the published rotate-half
  layout is the same map under a fixed permutation of each head's q/k
  columns.

:func:`make_params` makes the weights from the seed, on the device, in
bfloat16 (the published dtype) and in the tree the serving engine takes.
The reference reads the same arrays, widened to float32, one jitted call
per layer, so no float32 copy of more than one layer is held at once.

``quant="int8"`` is the control, as in ``dense_lm.py``: every matmul (the
router's too) with its weights rounded to int8 per output channel and its
activations to int8 per token (W8A8).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from perfbench.references.dense_lm import _mm, _reduce, _rms, _rope

#: router weights are drawn at this multiple of the fan-in scale: router
#: logits of standard deviation 2, so the top-6 of 64 gates carry about
#: half of the softmax mass and the routed experts a large share of each
#: layer's output (at the fan-in scale, routing is nearly uniform)
ROUTER_SCALE = 2.0


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, n_layers=cfg["num_hidden_layers"], n_heads=h,
                n_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg.get("head_dim", d // h),
                d_ff=cfg["intermediate_size"],
                moe_ff=cfg["moe_intermediate_size"],
                n_experts=cfg["n_routed_experts_published"],
                n_held=cfg["n_routed_experts"],
                first_expert=cfg["first_routed_expert"],
                n_shared=cfg["n_shared_experts"],
                top_k=cfg["num_experts_per_tok"],
                norm_topk_prob=bool(cfg["norm_topk_prob"]),
                n_dense=cfg["first_k_dense_replace"],
                vocab=cfg["vocab_size"],
                rope_theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]))


def shape_key(cfg: dict) -> tuple:
    return tuple(sorted(dims(cfg).items()))


@partial(jax.jit, static_argnames=("shape",))
def _params(key, shape):
    z = dict(shape)
    d, H, Hkv, Dh, V = (z["d"], z["n_heads"], z["n_kv_heads"],
                        z["head_dim"], z["vocab"])
    L = z["n_layers"] - z["n_dense"]
    E, ff, sff = z["n_held"], z["moe_ff"], z["moe_ff"] * z["n_shared"]
    ks = iter(jax.random.split(key, 32))

    def normal(shape, fan_in, scale=1.0):
        w = jax.random.normal(next(ks), shape, jnp.float32) \
            * (scale * fan_in ** -.5)
        return w.astype(jnp.bfloat16)

    def gamma(shape):
        g = 1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)
        return g.astype(jnp.bfloat16)

    def attn(*lead):
        return dict(wq=normal((*lead, d, H * Dh), d),
                    wk=normal((*lead, d, Hkv * Dh), d),
                    wv=normal((*lead, d, Hkv * Dh), d),
                    wo=normal((*lead, H * Dh, d), H * Dh))

    def swiglu(f, *lead):
        return dict(w_gate=normal((*lead, d, f), d),
                    w_up=normal((*lead, d, f), d),
                    w_down=normal((*lead, f, d), f))

    p = dict(embed=normal((V, d), d), ln_f=gamma((d,)),
             lm_head=normal((d, V), d),
             layers=dict(
                 ln1=gamma((L, d)), ln2=gamma((L, d)), attn=attn(L),
                 moe=dict(router=normal((L, d, z["n_experts"]), d,
                                        ROUTER_SCALE),
                          **swiglu(ff, L, E))))
    if z["n_shared"]:
        p["layers"]["moe"]["shared"] = swiglu(sff, L)
    if z["n_dense"]:
        p["dense0"] = dict(ln1=gamma((d,)), ln2=gamma((d,)), attn=attn(),
                           mlp=swiglu(z["d_ff"]))
    return p


def make_params(seed: int, cfg: dict):
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) + 2)
    z = dims(cfg)
    if z["n_dense"] > 1:
        raise ValueError("one leading dense layer, as published")
    return _params(key, tuple(sorted(
        (k, v) for k, v in z.items() if k not in ("rope_theta", "eps"))))


def _attention(x, lp, z, quant):
    S = x.shape[0]
    H, Hkv, Dh = z["n_heads"], z["n_kv_heads"], z["head_dim"]
    a = lp["attn"]
    h = _rms(x, lp["ln1"], z["eps"])
    q = _rope(_mm(h, a["wq"], quant).reshape(S, H, Dh), z["rope_theta"])
    k = _rope(_mm(h, a["wk"], quant).reshape(S, Hkv, Dh), z["rope_theta"])
    v = _mm(h, a["wv"], quant).reshape(S, Hkv, Dh)
    k = jnp.repeat(k, H // Hkv, axis=1)              # q head i -> kv i // g
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = jnp.einsum("shd,thd->hst", q, k, precision="highest") * Dh ** -.5
    mask = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hst,thd->shd", p, v, precision="highest")
    return x + _mm(o.reshape(S, H * Dh), a["wo"], quant)


def _swiglu(h, m, quant):
    u = jax.nn.silu(_mm(h, m["w_gate"], quant)) * _mm(h, m["w_up"], quant)
    return _mm(u, m["w_down"], quant)


def _experts(h, m, z, quant):
    """The shared experts plus the held routed experts' gate-weighted
    outputs, for every token of ``h`` (S, d)."""
    probs = jax.nn.softmax(_mm(h, m["router"], quant), axis=-1)    # (S, E)
    gates, sel = jax.lax.top_k(probs, z["top_k"])
    if z["norm_topk_prob"]:
        gates = gates / gates.sum(-1, keepdims=True)
    held = z["first_expert"] + jnp.arange(z["n_held"])
    comb = jnp.einsum("sk,ske->se", gates,
                      (sel[..., None] == held).astype(jnp.float32))
    out = _swiglu(h, m["shared"], quant) if "shared" in m else 0.0
    for e in range(z["n_held"]):
        w = jax.tree.map(lambda a: a[e], {k: m[k] for k in
                                          ("w_gate", "w_up", "w_down")})
        out = out + comb[:, e:e + 1] * _swiglu(h, w, quant)
    return out


@partial(jax.jit, static_argnames=("shape", "quant"))
def _moe_layer(x, layers, i, *, shape, quant):
    """MoE layer ``i`` of the stacked weights over one sequence."""
    z = dict(shape)
    lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                      layers)
    x = _attention(x, lp, z, quant)
    return x + _experts(_rms(x, lp["ln2"], z["eps"]), lp["moe"], z, quant)


@partial(jax.jit, static_argnames=("shape", "quant"))
def _dense_layer(x, lp, *, shape, quant):
    z = dict(shape)
    x = _attention(x, lp, z, quant)
    return x + _swiglu(_rms(x, lp["ln2"], z["eps"]), lp["mlp"], quant)


@partial(jax.jit, static_argnames=("shape", "quant"))
def _head(x, ln_f, lm_head, *, shape, quant):
    return _mm(_rms(x, ln_f, dict(shape)["eps"]), lm_head, quant)


def logits(params, tokens, shape: tuple, quant=None):
    """(S,) tokens -> (S, V) float32 logits of one sequence."""
    z = dict(shape)
    x = params["embed"][tokens].astype(jnp.float32)
    if z["n_dense"]:
        x = _dense_layer(x, params["dense0"], shape=shape, quant=quant)
    for i in range(z["n_layers"] - z["n_dense"]):
        x = _moe_layer(x, params["layers"], jnp.int32(i), shape=shape,
                       quant=quant)
    return _head(x, params["ln_f"], params["lm_head"], shape=shape,
                 quant=quant)


def readings(params, tokens, *, shape, control=False):
    """Per position p of a padded sequence: the reference's best logit, its
    logit of token p+1, and (with ``control``) its logit of the token the
    int8 control puts first."""
    ref = logits(params, tokens, shape)
    top = logits(params, tokens, shape, "int8").argmax(-1) if control \
        else jnp.concatenate([tokens[1:], tokens[:1]])
    return _reduce(ref, tokens, top)
