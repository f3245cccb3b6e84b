"""Plain float32 reference for a dense decoder-only LM (internlm2 family).

Written from the published description (arXiv:2403.17297: pre-norm RMSNorm,
grouped-query attention with rotary positions, SwiGLU feed-forward, untied
output head), in straightforward ``jax.numpy`` at the highest matmul
precision, with no kernel, cache or batching. One departure, noted: rotary
positions rotate interleaved (even, odd) lane pairs, the layout the program
stores its q/k projections in; the published model's rotate-half layout is
the same map under a fixed permutation of each head's q/k columns.

:func:`make_params` makes the weights from the seed, on the device, in one
jitted call, in bfloat16 (the published checkpoint's dtype) and in the tree
the serving engine takes. The reference reads the same arrays, widened to
float32: the benchmark makes them, not the program. It runs layer by layer,
one jitted call per layer, so that no float32 copy of every weight is ever
held at once.

``quant="int8"`` is the control: every matmul with its weights rounded to
int8 per output channel and its activations to int8 per token (W8A8), the
step below the program's bfloat16 compute that would tempt a later change.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, n_layers=cfg["num_hidden_layers"], n_heads=h,
                n_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg.get("head_dim", d // h),
                d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                rope_theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]))


@partial(jax.jit, static_argnames=("shape",))
def _params(key, shape):
    z = dict(shape)
    d, L, H, Hkv, Dh, ff, V = (z["d"], z["n_layers"], z["n_heads"],
                               z["n_kv_heads"], z["head_dim"], z["d_ff"],
                               z["vocab"])
    ks = iter(jax.random.split(key, 16))

    def normal(shape, fan_in):
        w = jax.random.normal(next(ks), shape, jnp.float32) * fan_in ** -.5
        return w.astype(jnp.bfloat16)

    def gamma(shape):
        g = 1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)
        return g.astype(jnp.bfloat16)

    return dict(
        embed=normal((V, d), d), ln_f=gamma((d,)), lm_head=normal((d, V), d),
        layers=dict(
            ln1=gamma((L, d)), ln2=gamma((L, d)),
            attn=dict(wq=normal((L, d, H * Dh), d),
                      wk=normal((L, d, Hkv * Dh), d),
                      wv=normal((L, d, Hkv * Dh), d),
                      wo=normal((L, H * Dh, d), H * Dh)),
            mlp=dict(w_gate=normal((L, d, ff), d), w_up=normal((L, d, ff), d),
                     w_down=normal((L, ff, d), ff))))


def make_params(seed: int, cfg: dict):
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) + 2)
    z = dims(cfg)
    shape = tuple(sorted((k, v) for k, v in z.items()
                         if k not in ("rope_theta", "eps")))
    return _params(key, shape)


def _q8(x, axis):
    """Round to int8 with a symmetric absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, quant):
    w = w.astype(jnp.float32)
    if quant == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision="highest")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, theta):
    S, _, dh = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


@partial(jax.jit, static_argnames=("shape", "quant"))
def _layer(x, layers, i, *, shape, quant):
    """Decoder layer ``i`` of the stacked weights over one sequence."""
    z = dict(shape)
    S = x.shape[0]
    H, Hkv, Dh = z["n_heads"], z["n_kv_heads"], z["head_dim"]
    lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                      layers)
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
    x = x + _mm(o.reshape(S, H * Dh), a["wo"], quant)
    m = lp["mlp"]
    h = _rms(x, lp["ln2"], z["eps"])
    u = jax.nn.silu(_mm(h, m["w_gate"], quant)) * _mm(h, m["w_up"], quant)
    return x + _mm(u, m["w_down"], quant)


@partial(jax.jit, static_argnames=("shape", "quant"))
def _head(x, ln_f, lm_head, *, shape, quant):
    return _mm(_rms(x, ln_f, dict(shape)["eps"]), lm_head, quant)


def logits(params, tokens, shape: tuple, quant=None):
    """(S,) tokens -> (S, V) float32 logits of one sequence."""
    x = params["embed"][tokens].astype(jnp.float32)
    for i in range(dict(shape)["n_layers"]):
        x = _layer(x, params["layers"], jnp.int32(i), shape=shape,
                   quant=quant)
    return _head(x, params["ln_f"], params["lm_head"], shape=shape,
                 quant=quant)


@jax.jit
def _reduce(ref, tokens, top):
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    pick = lambda t: jnp.take_along_axis(ref, t[:, None], -1)[:, 0]
    return ref.max(-1), pick(nxt), pick(top)


def readings(params, tokens, *, shape, control=False):
    """Per position p of a padded sequence: the reference's best logit, its
    logit of token p+1, and (with ``control``) its logit of the token the
    int8 control puts first."""
    ref = logits(params, tokens, shape)
    top = logits(params, tokens, shape, "int8").argmax(-1) if control \
        else jnp.concatenate([tokens[1:], tokens[:1]])
    return _reduce(ref, tokens, top)


def shape_key(cfg: dict) -> tuple:
    return tuple(sorted(dims(cfg).items()))
