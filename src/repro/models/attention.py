"""GQA attention: XLA-native chunked (flash-style) path for train/prefill,
exact cached path for decode, Pallas kernel path for static-shape attention.

The chunked path is an online-softmax lax.scan over KV blocks — the same
algorithm as kernels/flash_attention but expressed in XLA ops so it compiles
on any backend (the multi-pod dry-run lowers this path; the Pallas kernel is
the TPU execution target, validated against the same oracle).

For long sequences the query axis is additionally blocked by a static python
loop (``q_chunk``): peak score memory drops from O(S*Skv) to
O(q_chunk*kv_chunk), and for causal self-attention each q block only scans
the KV prefix it can see — matching FlashAttention's block-skipping FLOPs.

This module registers the ``flash_attention`` registry op in the model's
(B, S, H, D) layout: ``xla`` = :func:`chunked_attention`, ``pallas`` = the
kernel in ``repro.kernels.flash_attention`` (static masks only — its
per-call predicate rejects dynamic ``kv_valid_len``, so cached decode always
takes the XLA path). The pallas impl registers the FA-2-style custom VJP
(``kernels.flash_attention.backward``), so ``loss_fn`` gradients trace the
pallas backward kernels rather than detouring to XLA. Call sites use
:func:`attention`, which defers to the process backend policy (see
``repro.kernels.registry``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.flash_attention import ops as _fa_ops

NEG_INF = -1e30

#: q blocks engage above this length (keeps small/smoke cases single-block)
Q_CHUNK_DEFAULT = 2048
KV_CHUNK_DEFAULT = 1024


def _attn_inner(q, k, v, *, causal: bool, chunk: int, scale: float,
                kv_valid_len, qpos_offset: int):
    """Online-softmax over kv chunks. q (B,Sq,Hq,D); k,v (B,Skv,Hkv,D).
    Global query position of row i is qpos_offset + i (for causal masking)."""
    B, S, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    chunk = min(chunk, Skv)
    nkc = (Skv + chunk - 1) // chunk
    pad = nkc * chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # kv_valid_len may be scalar (shared cache fill) or (B,) (per-slot fill —
    # the continuous-batching serve path, where every batch row sits at its
    # own decode depth)
    valid = jnp.asarray(Skv if kv_valid_len is None else kv_valid_len,
                        jnp.int32).reshape(-1, 1, 1)

    # Operands stay bf16 (MXU-native); accumulation is fp32 via
    # preferred_element_type. Upcasting q itself costs a full fp32
    # activation tensor per layer AND turns every backward cotangent fp32
    # (llama3 train_4k: -30% memory term; EXPERIMENTS.md §Perf llama it.3).
    qg = q.reshape(B, S, Hkv, group, D)
    qpos = jnp.arange(S, dtype=jnp.int32) + qpos_offset

    kc = jnp.moveaxis(k.reshape(B, nkc, chunk, Hkv, D), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nkc, chunk, Hkv, D), 1, 0)

    def body(carry, inp):
        m, l, acc = carry
        ic, kb, vb = inp                                   # (B,chunk,Hkv,D)
        s = jnp.einsum("bskgd,btkd->bkgst", qg, kb,
                       preferred_element_type=jnp.float32) * scale
        kpos = ic * chunk + jnp.arange(chunk, dtype=jnp.int32)
        mask = kpos[None, None, :] < valid                 # (1|B, 1, chunk)
        if causal:
            mask = mask & (qpos[:, None] >= kpos[None, :])[None]
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bkgst,btkd->bkgsd", p.astype(v.dtype), vb,
                        preferred_element_type=jnp.float32)
        acc = acc * alpha[..., None] + pv
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, group, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, group, S), jnp.float32)
    a0 = jnp.zeros((B, Hkv, group, S, D), jnp.float32)
    if nkc == 1:
        (m, l, acc), _ = body((m0, l0, a0),
                              (jnp.int32(0), kc[0], vc[0]))
    else:
        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0), (jnp.arange(nkc, dtype=jnp.int32), kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out.reshape(B, Hkv * group, S, D), 1, 2)
    return out.astype(q.dtype)


def chunked_attention(q, k, v, *, causal: bool = True,
                      chunk: int = KV_CHUNK_DEFAULT,
                      q_chunk: Optional[int] = Q_CHUNK_DEFAULT,
                      scale: float | None = None, kv_valid_len=None):
    """Flash-style attention; see module docstring. Shapes (B,S,H,D)."""
    B, S, Hq, D = q.shape
    Skv = k.shape[1]
    scale = D ** -0.5 if scale is None else scale
    off = Skv - S                                     # right-aligned queries

    if q_chunk is None or S <= q_chunk:
        return _attn_inner(q, k, v, causal=causal, chunk=chunk, scale=scale,
                           kv_valid_len=kv_valid_len, qpos_offset=off)

    assert S % q_chunk == 0, "callers pad seq to the q-chunk multiple"
    outs = []
    for i in range(0, S, q_chunk):
        qb = q[:, i:i + q_chunk]
        if causal and kv_valid_len is None:
            # static prefix: this q block sees keys [0, off + i + q_chunk)
            kv_end = min(-(-(off + i + q_chunk) // chunk) * chunk, Skv)
        else:
            kv_end = Skv
        outs.append(_attn_inner(
            qb, k[:, :kv_end], v[:, :kv_end], causal=causal, chunk=chunk,
            scale=scale, kv_valid_len=kv_valid_len, qpos_offset=off + i))
    return jnp.concatenate(outs, axis=1)


def _attention_xla(q, k, v, *, causal: bool = True, scale=None,
                   kv_valid_len=None, chunk: Optional[int] = None,
                   q_chunk: Optional[int] = Q_CHUNK_DEFAULT,
                   bq=None, bk=None, bq_bwd=None, bk_bwd=None):
    del bq, bk, bq_bwd, bk_bwd                     # pallas-only tunables
    return chunked_attention(q, k, v, causal=causal,
                             chunk=chunk or KV_CHUNK_DEFAULT,
                             q_chunk=q_chunk, scale=scale,
                             kv_valid_len=kv_valid_len)


def _bhsd(x):
    return x.transpose(0, 2, 1, 3)                 # (B,S,H,D) <-> (B,H,S,D)


def _attention_pallas(q, k, v, *, causal: bool = True, scale=None,
                      kv_valid_len=None, chunk: Optional[int] = None,
                      q_chunk: Optional[int] = None, bq=None, bk=None,
                      bq_bwd=None, bk_bwd=None):
    del kv_valid_len, chunk, q_chunk               # xla-only knobs
    del bq_bwd, bk_bwd                             # backward-only tunables
    o = _fa_ops.flash_attention(
        _bhsd(q), _bhsd(k), _bhsd(v), causal=causal, scale=scale, bq=bq,
        bk=bk)
    return _bhsd(o)


def _attention_pallas_fwd(q, k, v, *, causal: bool = True, scale=None,
                          kv_valid_len=None, chunk=None, q_chunk=None,
                          bq=None, bk=None, bq_bwd=None, bk_bwd=None):
    del kv_valid_len, chunk, q_chunk, bq_bwd, bk_bwd
    o, res = _fa_ops.flash_attention_fwd(
        _bhsd(q), _bhsd(k), _bhsd(v), causal=causal, scale=scale, bq=bq,
        bk=bk)
    return _bhsd(o), res                           # residuals in kernel layout


def _attention_pallas_bwd(res, do, *, causal: bool = True, scale=None,
                          kv_valid_len=None, chunk=None, q_chunk=None,
                          bq=None, bk=None, bq_bwd=None, bk_bwd=None):
    del kv_valid_len, chunk, q_chunk
    dq, dk, dv = _fa_ops.flash_attention_bwd(
        res, _bhsd(do), causal=causal, scale=scale, bq=bq, bk=bk,
        bq_bwd=bq_bwd, bk_bwd=bk_bwd)
    return _bhsd(dq), _bhsd(dk), _bhsd(dv)


def attention(q, k, v, *, causal: bool = True, scale=None, kv_valid_len=None,
              chunk: Optional[int] = None,
              q_chunk: Optional[int] = Q_CHUNK_DEFAULT, bq=None, bk=None,
              bq_bwd=None, bk_bwd=None):
    """Backend-dispatched GQA attention, (B,S,H,D) layout. Differentiable
    under every backend (the pallas impl carries an FA-2-style custom VJP).

    The implementation is chosen by the registry policy; block sizes left as
    ``None`` are filled from the autotune cache (then per-impl defaults) —
    ``bq``/``bk`` for the forward, ``bq_bwd``/``bk_bwd`` for the backward
    kernels."""
    return registry.dispatch(
        "flash_attention", q, k, v, causal=causal, scale=scale,
        kv_valid_len=kv_valid_len, chunk=chunk, q_chunk=q_chunk, bq=bq, bk=bk,
        bq_bwd=bq_bwd, bk_bwd=bk_bwd)


def _fa_make_inputs(shape, dtype=jnp.float32):
    B, Sq, Hq, D, Skv, Hkv = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, D), dtype)
    return (q, k, v), dict(causal=True)


def _fa_candidates(backend, shape):
    if backend == "pallas":
        return [dict(bq=bq, bk=bk) for bq in (32, 128, 512)
                for bk in (32, 128, 512)]
    return [dict(chunk=c) for c in (128, 256, 1024)]


def _fa_bwd_candidates(backend, shape):
    if backend != "pallas":
        return []
    return [dict(bq_bwd=bq, bk_bwd=bk) for bq in (32, 128, 512)
            for bk in (32, 128, 512)]


def quantize_kv(x):
    """Symmetric per-(row, head) int8 quantization over head_dim.

    x (..., D) -> ``(q, scale)``: int8 codes plus the f32 absmax/127 scale
    with the trailing axis reduced — the layout of the paged pool's
    ``k_scale``/``v_scale`` leaves. All-zero rows get scale 1.0 so
    dequantization of never-written pool rows stays exactly 0.
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127.0, 127.0).astype(jnp.int8)
    return q, scale


def paged_attention(q, k_pool, v_pool, page_table, kv_valid_len, layer, *,
                    k_scale=None, v_scale=None, scale=None,
                    chunk: Optional[int] = None, interpret=None):
    """Backend-dispatched decode attention over one layer of a paged KV
    pool.

    q (B,1,Hq,D); pools (L, num_pages, page_size, Hkv, D), stacked over
    layers (a single layer's pool passes ``pool[None]``); page_table
    (B, pages_per_slot) int32 mapping each batch row's logical pages to pool
    pages; kv_valid_len (B,) int32 valid KV length per row; layer scalar
    int32, the layer of the stack read. Rows past ``kv_valid_len`` —
    including everything reached through table entry 0, the serve layer's
    scratch page — are masked out exactly (finite values, zero weight), so
    pool garbage never perturbs the output.

    Quantized pools pass int8 K/V plus ``k_scale``/``v_scale``
    (L, num_pages, page_size, Hkv) f32; both impls dequantize on read
    (``x = int8 * scale``), so the score/output math runs in the same
    precision as the f32 path and the only error is the per-row rounding
    bounded by ``scale/2 = absmax/254`` per element.

    The xla impl gathers the layer's table rows into dense rows and reuses
    :func:`chunked_attention` — bitwise the slot-engine decode path. The
    pallas impl (decode-only, S == 1) copies each row's live blocks of
    whole pages straight from the stack through a scalar-prefetched table
    and layer, never materialising the gather.
    """
    return registry.dispatch(
        "paged_attention", q, k_pool, v_pool, page_table, kv_valid_len,
        layer, k_scale=k_scale, v_scale=v_scale, scale=scale, chunk=chunk,
        interpret=interpret)


def paged_pool_in_place(kv_leaf, quantized: bool) -> bool:
    """Whether ``paged_attention``, under the active backend policy, reads
    a stacked pool of ``kv_leaf``'s ``(..., Hkv, D)`` pages where it lies:
    the xla impl gathers only the table's rows; the pallas kernel does so
    where its shapes need no padding (``ops.reads_pool_in_place``)."""
    if registry.resolved_backend() != "pallas":
        return True
    return _fa_ops.reads_pool_in_place(kv_leaf.shape[-2], kv_leaf.shape[-1],
                                       kv_leaf.dtype, quantized)


def _paged_attention_xla(q, k_pool, v_pool, page_table, kv_valid_len, layer,
                         *, k_scale=None, v_scale=None, scale=None,
                         chunk: Optional[int] = None, interpret=None):
    del interpret                                  # pallas-only knob
    B = q.shape[0]
    Hkv, D = k_pool.shape[3], k_pool.shape[4]
    k = k_pool[layer, page_table].reshape(B, -1, Hkv, D)
    v = v_pool[layer, page_table].reshape(B, -1, Hkv, D)
    if k_scale is not None:
        ks = k_scale[layer, page_table].reshape(B, -1, Hkv)
        vs = v_scale[layer, page_table].reshape(B, -1, Hkv)
        k = k.astype(jnp.float32) * ks[..., None]
        v = v.astype(jnp.float32) * vs[..., None]
    # decode reads are right-aligned single queries: causal=False + the
    # per-row kv_valid mask is the exact slot-engine semantics
    return chunked_attention(q, k, v, causal=False,
                             chunk=chunk or KV_CHUNK_DEFAULT, scale=scale,
                             kv_valid_len=kv_valid_len)


def _paged_attention_pallas(q, k_pool, v_pool, page_table, kv_valid_len,
                            layer, *, k_scale=None, v_scale=None, scale=None,
                            chunk: Optional[int] = None, interpret=None):
    del chunk                                      # xla-only knob
    return _fa_ops.paged_flash_decode(q, k_pool, v_pool, page_table,
                                      kv_valid_len, layer, k_scale=k_scale,
                                      v_scale=v_scale, scale=scale,
                                      interpret=interpret)


def _paged_make_inputs(shape, dtype=jnp.float32):
    B, Hq, D, Hkv, npg, P = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, D), dtype)
    k = jax.random.normal(ks[1], (1, 1 + B * npg, P, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (1, 1 + B * npg, P, Hkv, D), dtype)
    table = jnp.arange(1, 1 + B * npg, dtype=jnp.int32).reshape(B, npg)
    valid = jnp.full((B,), npg * P, jnp.int32)
    return (q, k, v, table, valid, jnp.zeros((), jnp.int32)), {}


registry.describe(
    "paged_attention",
    shape_of=lambda q, k, v, t, n, l, **kw: (q.shape[0], q.shape[2],
                                             q.shape[3], k.shape[3],
                                             t.shape[1], k.shape[2]),
    make_inputs=_paged_make_inputs)
registry.register("paged_attention", "xla",
                  tunables=("chunk",))(_paged_attention_xla)
registry.register(
    "paged_attention", "pallas", differentiable=False,
    supports=lambda q, *a, **kw: q.shape[1] == 1,
)(_paged_attention_pallas)


registry.describe(
    "flash_attention",
    shape_of=lambda q, k, v, **kw: (q.shape[0], q.shape[1], q.shape[2],
                                    q.shape[3], k.shape[1], k.shape[2]),
    make_inputs=_fa_make_inputs, candidates=_fa_candidates,
    bwd_candidates=_fa_bwd_candidates)
registry.register("flash_attention", "xla",
                  tunables=("chunk",))(_attention_xla)
registry.register(
    "flash_attention", "pallas", tunables=("bq", "bk"),
    bwd_tunables=("bq_bwd", "bk_bwd"),
    vjp=(_attention_pallas_fwd, _attention_pallas_bwd),
    supports=lambda q, k, v, **kw: kw.get("kv_valid_len") is None,
)(_attention_pallas)
