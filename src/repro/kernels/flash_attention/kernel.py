"""Pallas TPU kernel: FlashAttention-style online-softmax GQA attention.

TPU adaptation (vs. the CUDA original): no warp-level shuffles or shared-mem
banking — instead, (bq x d) query tiles stay VMEM-resident while (bk x d)
key/value tiles stream HBM->VMEM along the innermost grid dimension; the MXU
consumes (bq x bk) score tiles, and the online-softmax running max/denominator
live in VMEM scratch across the kv sweep. Causal block-skipping prunes the
upper-triangle grid cells with pl.when (no wasted MXU issue slots).

Grid: (B*Hq, Sq/bq, Skv/bk) — kv innermost, sequential; output tile revisited
consecutively, accumulated in fp32 scratch, written once on the last kv block.
VMEM: (bq+2*bk)*d*4B + bq*bk*4B ≈ 1.3 MiB at bq=bk=512, d=128.
GQA is expressed in the BlockSpec index maps (q head h reads kv head
h // group) — no KV replication in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_body(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale: float, causal: bool, bq: int, bk: int,
                kv_blocks: int, kv_len: int, q_offset: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal block pruning: skip blocks strictly above the masked diagonal.
    # Global query position = iq*bq + row + q_offset (aligns decode windows).
    if causal:
        run = (ik * bk) <= (iq * bq + bq - 1 + q_offset)
    else:
        run = (ik * bk) < kv_len  # always true structurally; keeps types uniform

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if causal:
            qpos = iq * bq + q_offset + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        # Mask the zero-padded tail of the kv axis (exactness of ops.py pad).
        s = jnp.where(kpos < kv_len, s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)       # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                          # (bq, bk)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(p, v_ref[0, 0].astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(ik == kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)              # fully-masked rows -> 0
        o_ref[0, 0, :, :] = (acc_ref[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            # per-row logsumexp of the masked scores: the backward kernels
            # recompute p = exp(s - lse) from it tile-by-tile (FA-2)
            lse_ref[0, 0, :, :] = m_ref[...] + jnp.log(l)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, **kw):
    _flash_body(q_ref, k_ref, v_ref, o_ref, None, acc_ref, m_ref, l_ref, **kw)


def _flash_kernel_lse(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                      l_ref, **kw):
    _flash_body(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                **kw)


def _paged_body(table_ref, valid_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                o_ref, acc_ref, m_ref, l_ref, *, scale: float,
                page_size: int, pages: int):
    del table_ref                            # consumed by the index maps
    b = pl.program_id(0)
    h = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = valid_ref[b]

    def head_scale(s_ref):
        # the scale block holds every kv head of the page (rows, Hkv): pick
        # this program's head column with a lane mask -> (rows, 1)
        sc = s_ref[0]
        lane = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        return jnp.sum(jnp.where(lane == h, sc, 0.0), axis=1, keepdims=True)

    # Page pruning: pages whose first position is past this row's valid
    # length are never fetched into the softmax (their table entries may be
    # 0, the pool's scratch page — masked to exact zero weight regardless).
    @pl.when(ik * page_size < valid)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # (group, d)
        k = k_ref[0].astype(jnp.float32)                  # (rows, d)
        if ks_ref is not None:
            # int8 pool: per-(row, head) dequant rides the same
            # scalar-prefetched page address as the codes it scales
            k = k * head_scale(ks_ref)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        j = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # rows >= page_size (sublane pad): mask both the pad rows and the
        # positions past the row's decode depth
        s = jnp.where((j < page_size) & (ik * page_size + j < valid),
                      s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                            # (group, rows)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0].astype(jnp.float32)
        if vs_ref is not None:
            v = v * head_scale(vs_ref)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(ik == pages - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_kernel(table_ref, valid_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, **kw):
    _paged_body(table_ref, valid_ref, q_ref, k_ref, v_ref, None, None,
                o_ref, acc_ref, m_ref, l_ref, **kw)


def _paged_kernel_quant(table_ref, valid_ref, q_ref, k_ref, v_ref, ks_ref,
                        vs_ref, o_ref, acc_ref, m_ref, l_ref, **kw):
    _paged_body(table_ref, valid_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                o_ref, acc_ref, m_ref, l_ref, **kw)


@functools.partial(jax.jit, static_argnames=("scale", "page_size",
                                             "interpret"))
def paged_flash_decode(q, k_pool, v_pool, table, valid, k_scale=None,
                       v_scale=None, *, scale: float, page_size: int,
                       interpret: bool = True):
    """Decode attention through a scalar-prefetched page table.

    q (B,Hq,D); pools (num_pages, rows, Hkv, D) with rows >= page_size
    (sublane pad) and D a lane multiple; table (B, npages) int32; valid (B,)
    int32. The table and valid vector ride the scalar-prefetch lane so the
    k/v BlockSpec index maps can compute HBM page addresses before the body
    runs — the gather never materialises in HBM.

    Grid (B, Hkv, npages): one program serves all ``Hq/Hkv`` query heads of
    a kv head, so each page is read once per kv head. The pools are viewed
    as (num_pages, rows, Hkv*D) — a free reshape — so a K/V block is one
    head's (rows, D) tile of a page, which the TPU tiles natively.

    Quantized pools additionally pass ``k_scale``/``v_scale``
    (num_pages, rows, Hkv) f32; a page's whole scale tile rides the same
    prefetched page address and dequantization happens in-register before
    the MXU.
    """
    B, Hq, D = q.shape
    num_pages, rows, Hkv = k_pool.shape[:3]
    group = Hq // Hkv
    npages = table.shape[1]
    quant = k_scale is not None

    body = _paged_kernel_quant if quant else _paged_kernel
    kernel = functools.partial(body, scale=scale, page_size=page_size,
                               pages=npages)
    # index maps receive (*grid_indices, *scalar_prefetch_refs)
    q_spec = pl.BlockSpec((1, 1, group, D),
                          lambda b, h, ik, t, n: (b, h, 0, 0))
    kv_spec = pl.BlockSpec((1, rows, D),
                           lambda b, h, ik, t, n: (t[b, ik], 0, h))
    scale_spec = pl.BlockSpec((1, rows, Hkv),
                              lambda b, h, ik, t, n: (t[b, ik], 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [q.reshape(B, Hkv, group, D),
                k_pool.reshape(num_pages, rows, Hkv * D),
                v_pool.reshape(num_pages, rows, Hkv * D)]
    if quant:
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, npages),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((group, D), jnp.float32),    # acc
            pltpu.VMEM((group, 1), jnp.float32),    # running max m
            pltpu.VMEM((group, 1), jnp.float32),    # running denom l
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        interpret=interpret,
    )(table, valid, *operands)
    return out.reshape(B, Hq, D)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "bq", "bk", "kv_len", "q_offset", "interpret",
    "return_lse"))
def flash_attention(q, k, v, *, causal: bool, scale: float, bq: int, bk: int,
                    kv_len: int, q_offset: int, interpret: bool = True,
                    return_lse: bool = False):
    """Padded flash attention. q (B,Hq,Sq,D); k,v (B,Hkv,Skv,D); Sq % bq == 0,
    Skv % bk == 0, D MXU-aligned (ops.py guarantees). kv_len = unpadded Skv.

    return_lse: also return the per-row logsumexp (B, Hq, Sq, 1) fp32 — the
    residual the custom-VJP backward consumes. The plain forward keeps a
    single output (no extra write)."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    grid = (B * Hq, Sq // bq, Skv // bk)

    o_spec = pl.BlockSpec((1, 1, bq, D),
                          lambda bh, iq, ik: (bh // Hq, bh % Hq, iq, 0))
    out_specs, out_shape = o_spec, jax.ShapeDtypeStruct(q.shape, q.dtype)
    body = _flash_kernel
    if return_lse:
        body = _flash_kernel_lse
        out_specs = [o_spec,
                     pl.BlockSpec((1, 1, bq, 1),
                                  lambda bh, iq, ik: (bh // Hq, bh % Hq,
                                                      iq, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B, Hq, Sq, 1), jnp.float32)]

    kernel = functools.partial(
        body, scale=scale, causal=causal, bq=bq, bk=bk,
        kv_blocks=Skv // bk, kv_len=kv_len, q_offset=q_offset)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D),
                         lambda bh, iq, ik: (bh // Hq, bh % Hq, iq, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda bh, iq, ik: (bh // Hq, (bh % Hq) // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda bh, iq, ik: (bh // Hq, (bh % Hq) // group, ik, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom l
        ],
        interpret=interpret,
    )(q, k, v)
