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

Paged decode (``paged_flash_decode``): one program per batch row (and kv
head block) walks the row's live blocks of several whole pages, copying
each block's pages of one layer from the pool stacked over layers
(L, pages, P, Hkv, D), where it lies, into a double-buffered VMEM scratch
while the previous block is computed.
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


#: K+V bytes one block of the paged decode kernel aims to move: several
#: whole pages, so a grid step's fixed cost is spread over ~0.5 MiB of DMA
BLOCK_BYTES = 512 * 1024


def heads_dma_whole(hkv: int, itemsize: int) -> bool:
    """Whether a pool page's ``(Hkv, D)`` rows fill the chip's sublane
    tiling of the pool dtype, so one DMA moves a whole page (Mosaic refuses
    a copy whose kv-head axis leaves a packed tile part-filled)."""
    pack = 4 // itemsize
    return pack == 1 or (hkv % pack == 0
                         and (hkv // pack in (1, 2, 4) or hkv % 8 == 0))


def decode_blocks(page_size: int, hkv: int, d: int, itemsize: int,
                  npages: int) -> tuple[int, int]:
    """``(pages_per_block, heads_per_block)`` of :func:`paged_flash_decode`,
    from the shapes alone.

    A block is ``pages_per_block`` whole pages with ``heads_per_block`` kv
    heads of each. Heads are halved (keeping whole DMA tiles) only while
    one page of them holds more than :data:`BLOCK_BYTES` of K+V; pages then
    fill the block up to :data:`BLOCK_BYTES`, at most ``npages``. So the two
    VMEM buffers the kernel double-buffers hold about 1 MiB, unless a single
    page of the narrowest head block is larger. ``npages`` need not be a
    multiple of the block: the last block's missing pages read the scratch
    page 0 and are masked.
    """
    page = lambda h: 2 * page_size * h * d * itemsize     # K+V of one page
    hb = hkv
    while (page(hb) > BLOCK_BYTES and hb % 2 == 0
           and heads_dma_whole(hb // 2, itemsize)):
        hb //= 2
    return max(1, min(npages, BLOCK_BYTES // page(hb))), hb


def _paged_body(table_ref, valid_ref, layer_ref, q_ref, k_pages, v_pages,
                ks_pages, vs_pages, o_ref, kbuf, vbuf, ksbuf, vsbuf, sem,
                slot_ref, acc_ref, m_ref, l_ref, *, scale: float,
                page_size: int, pages: int, ppb: int):
    hblk, b = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]
    n_hb, rows = pl.num_programs(0), pl.num_programs(1)
    hb = kbuf.shape[3]                       # kv heads of this head block
    T = ppb * page_size                      # tokens of one block

    def n_blocks(row):
        # live blocks only; an empty row still takes its (masked) first
        # block, which the previous program has already fetched
        return jnp.maximum(1, pl.cdiv(valid_ref[row], T))

    def copies(row, hblk, blk, slot):
        """The block's page copies: whole ``(P, hb, D)`` slabs of the
        layer's pages, addressed through the scalar-prefetched table."""
        out = []
        for j in range(ppb):
            idx = blk * ppb + j
            page = jnp.where(idx < pages,
                             table_ref[row, jnp.minimum(idx, pages - 1)], 0)
            for src, dst in ((k_pages, kbuf), (v_pages, vbuf)):
                src = src.at[layer, page] if hb == src.shape[3] else \
                    src.at[layer, page, :, pl.ds(hblk * hb, hb)]
                out.append(pltpu.make_async_copy(src, dst.at[slot, j],
                                                 sem.at[slot]))
            if ks_pages is not None:
                # the page's whole (P, lanes) scale tile rides the same
                # copy
                for src, dst in ((ks_pages, ksbuf), (vs_pages, vsbuf)):
                    out.append(pltpu.make_async_copy(
                        src.at[layer, page], dst.at[slot, j], sem.at[slot]))
        return out

    @pl.when((hblk == 0) & (b == 0))
    def _first():
        slot_ref[0] = 0
        for c in copies(0, 0, 0, 0):
            c.start()

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    valid = valid_ref[b]
    nb = n_blocks(b)

    def head_scale(s_buf, h):
        # the scale tile holds every kv head of the page on its lanes:
        # pick this head's column with a lane mask -> (T, 1)
        sc = s_buf[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 2)
        col = jnp.sum(jnp.where(lane == hblk * hb + h, sc, 0.0), axis=2,
                      keepdims=True)
        return col.reshape(T, 1)

    def block(i, cur):
        # prefetch the next block of this row, else the first block of the
        # next program's row, into the other buffer before computing
        last = i + 1 == nb
        nrow = jnp.where(last, b + 1, b)
        nhblk = jnp.where(nrow == rows, hblk + 1, hblk)
        nrow = jnp.where(nrow == rows, 0, nrow)

        @pl.when(nhblk < n_hb)
        def _prefetch():
            for c in copies(nrow, nhblk, jnp.where(last, 0, i + 1), 1 - cur):
                c.start()

        for c in copies(b, hblk, i, cur):
            c.wait()
        pos = i * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        live = pos < valid
        for h in range(hb):
            q = q_ref[0, h].astype(jnp.float32)                 # (group, d)
            k = kbuf[cur, :, :, h, :].astype(jnp.float32)       # (ppb, P, d)
            k = k.reshape(T, k.shape[-1])
            if ksbuf is not None:
                # int8 pool: per-(row, head) dequant before the MXU
                k = k * head_scale(ksbuf.at[cur], h)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(live, s * scale, NEG_INF)
            m_prev, l_prev = m_ref[h], l_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # masked positions (past valid, the scratch page, missing pages
            # of the last block) get exactly zero weight
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)        # (group, T)
            l_ref[h] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            m_ref[h] = m_new
            v = vbuf[cur, :, :, h, :].astype(jnp.float32)
            v = v.reshape(T, v.shape[-1])
            if vsbuf is not None:
                v = v * head_scale(vsbuf.at[cur], h)
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_ref[h] = acc_ref[h] * alpha + pv
        return 1 - cur

    slot_ref[0] = jax.lax.fori_loop(0, nb, block, slot_ref[0])
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_kernel(table_ref, valid_ref, layer_ref, q_ref, k_pages, v_pages,
                  o_ref, kbuf, vbuf, sem, slot_ref, acc_ref, m_ref, l_ref,
                  **kw):
    _paged_body(table_ref, valid_ref, layer_ref, q_ref, k_pages, v_pages,
                None, None, o_ref, kbuf, vbuf, None, None, sem, slot_ref,
                acc_ref, m_ref, l_ref, **kw)


def _paged_kernel_quant(table_ref, valid_ref, layer_ref, q_ref, k_pages,
                        v_pages, ks_pages, vs_pages, o_ref, kbuf, vbuf, ksbuf,
                        vsbuf, sem, slot_ref, acc_ref, m_ref, l_ref, **kw):
    _paged_body(table_ref, valid_ref, layer_ref, q_ref, k_pages, v_pages,
                ks_pages, vs_pages, o_ref, kbuf, vbuf, ksbuf, vsbuf, sem,
                slot_ref, acc_ref, m_ref, l_ref, **kw)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_flash_decode(q, k_pool, v_pool, table, valid, layer, k_scale=None,
                       v_scale=None, *, scale: float, interpret: bool = True):
    """Decode attention through a scalar-prefetched page table.

    q (B,Hq,D); pools (L, num_pages, page_size, Hkv, D), stacked over
    layers in their native layout, with D a lane multiple and Hkv filling
    whole DMA tiles (:func:`heads_dma_whole`); table (B, npages) int32;
    valid (B,) int32; layer (1,) int32: the layer of the stack read.

    Grid (Hkv / heads_per_block, B), sequential: one program per row (and
    head block) walks only the row's live blocks, ``ceil(valid / T)`` of
    them for ``T = pages_per_block * page_size`` tokens
    (:func:`decode_blocks`); a dead block costs neither a copy nor a
    softmax update. The pools are passed whole, wherever XLA keeps them,
    and never reshaped: each block's pages are copied whole, every kv head
    at once, into a double-buffered VMEM scratch at addresses read from
    the scalar-prefetched table and layer, and the next block (or the next
    row's first block) is in flight while the current one is computed. Each kv
    head's ``Hq/Hkv`` query rows meet its keys in f32: scores, online
    softmax and accumulator as in :func:`flash_attention`.

    Quantized pools additionally pass ``k_scale``/``v_scale``
    (L, num_pages, page_size, lanes) f32, the kv heads on the first lanes; a
    page's whole scale tile rides the same copy and dequantization happens
    in-register before the MXU.
    """
    B, Hq, D = q.shape
    P, Hkv = k_pool.shape[2:4]
    group = Hq // Hkv
    npages = table.shape[1]
    quant = k_scale is not None
    ppb, hb = decode_blocks(P, Hkv, D, k_pool.dtype.itemsize, npages)

    body = _paged_kernel_quant if quant else _paged_kernel
    kernel = functools.partial(body, scale=scale, page_size=P,
                               pages=npages, ppb=ppb)
    # index maps receive (*grid_indices, *scalar_prefetch_refs)
    q_spec = pl.BlockSpec((1, hb, group, D),
                          lambda hblk, b, t, n, l: (b, hblk, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands = [q.reshape(B, Hkv, group, D), k_pool, v_pool]
    scratch = [pltpu.VMEM((2, ppb, P, hb, D), k_pool.dtype),   # K blocks
               pltpu.VMEM((2, ppb, P, hb, D), v_pool.dtype)]   # V blocks
    if quant:
        operands += [k_scale, v_scale]
        scratch += [pltpu.VMEM((2, ppb, P, k_scale.shape[3]),
                               jnp.float32)] * 2
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),          # one per buffer
        pltpu.SMEM((1,), jnp.int32),            # buffer of the next block
        pltpu.VMEM((hb, group, D), jnp.float32),    # acc
        pltpu.VMEM((hb, group, 1), jnp.float32),    # running max m
        pltpu.VMEM((hb, group, 1), jnp.float32),    # running denom l
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(Hkv // hb, B),
        in_specs=[q_spec] + [hbm] * (len(operands) - 1),
        out_specs=q_spec,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        # programs hand the next one its prefetched block: run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(table, valid, layer, *operands)
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
