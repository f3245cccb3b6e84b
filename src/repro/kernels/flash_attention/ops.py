"""Public wrapper for flash attention: padding (seq to block multiples, head
dim to 128 lanes), GQA validation, interpret-mode dispatch on CPU — now for
the backward pass too.

Zero-padding is exact: padded head-dim lanes contribute 0 to q.k and produce
0 output lanes (sliced off); padded kv rows are masked to -inf in-kernel;
padded q rows produce garbage rows that are sliced off. The backward kernels
re-pad independently (their ``bq_bwd``/``bk_bwd`` block sizes are separate
tunables), which is safe because padded ``do`` rows are zero and padded kv
columns are masked out of the recomputed probability tiles.

``flash_attention`` carries a :func:`jax.custom_vjp` (wired by
``registry.custom_vjp_fn``): the forward saves the per-row logsumexp, the
backward recomputes score tiles inside ``backward.flash_dq`` /
``backward.flash_dkv`` — differentiating it never touches a ``pallas_call``
interior.

This wrapper keeps the kernel's (B, H, S, D) layout; the registry op
``flash_attention`` (model layout, XLA fallback) is registered by
``repro.models.attention`` on top of it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import pad, registry
from repro.kernels.flash_attention import backward as _kb
from repro.kernels.flash_attention import kernel as _k

DEFAULT_BQ = 512
DEFAULT_BK = 512
LANE = 128


def _prep(q, k, v, scale, bq, bk, interpret):
    """Resolved (padded q/k/v, kernel kwargs) shared by fwd and bwd."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    if Hq % Hkv:
        raise ValueError(f"GQA requires Hq % Hkv == 0, got {Hq=} {Hkv=}")
    scale = (D ** -0.5) if scale is None else scale
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bq = bq or min(DEFAULT_BQ, pad.round_up(Sq, 8))
    bk = bk or min(DEFAULT_BK, pad.round_up(Skv, 8))

    Dp = pad.round_up(D, LANE)
    qp = pad.pad_dims(q, {2: pad.round_up(Sq, bq), 3: Dp})
    kp = pad.pad_dims(k, {2: pad.round_up(Skv, bk), 3: Dp})
    vp = pad.pad_dims(v, {2: pad.round_up(Skv, bk), 3: Dp})
    kw = dict(scale=scale, bq=bq, bk=bk, kv_len=Skv, q_offset=Skv - Sq,
              interpret=interpret)
    return qp, kp, vp, kw


def _flash_attention_impl(q, k, v, *, causal: bool = True,
                          scale: float | None = None, bq: int | None = None,
                          bk: int | None = None, bq_bwd: int | None = None,
                          bk_bwd: int | None = None,
                          interpret: bool | None = None):
    del bq_bwd, bk_bwd                          # backward-only tunables
    Sq, D = q.shape[2], q.shape[3]
    qp, kp, vp, kw = _prep(q, k, v, scale, bq, bk, interpret)
    out = _k.flash_attention(qp, kp, vp, causal=causal, **kw)
    return pad.unpad_dims(out, {2: Sq, 3: D})


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        scale: float | None = None, bq: int | None = None,
                        bk: int | None = None, bq_bwd: int | None = None,
                        bk_bwd: int | None = None,
                        interpret: bool | None = None):
    """custom_vjp fwd: run the kernel with ``return_lse`` and save
    (q, k, v, o, lse) — all unpadded — as residuals."""
    del bq_bwd, bk_bwd
    Sq, D = q.shape[2], q.shape[3]
    qp, kp, vp, kw = _prep(q, k, v, scale, bq, bk, interpret)
    out, lse = _k.flash_attention(qp, kp, vp, causal=causal, return_lse=True,
                                  **kw)
    o = pad.unpad_dims(out, {2: Sq, 3: D})
    return o, (q, k, v, o, pad.unpad_dims(lse, {2: Sq}))


def flash_attention_bwd(res, do, *, causal: bool = True,
                        scale: float | None = None, bq: int | None = None,
                        bk: int | None = None, bq_bwd: int | None = None,
                        bk_bwd: int | None = None,
                        interpret: bool | None = None):
    """custom_vjp bwd: (dq, dk, dv) via the FA-2-style backward kernels."""
    q, k, v, o, lse = res
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    qp, kp, vp, kw = _prep(q, k, v, scale, bq_bwd or bq, bk_bwd or bk,
                           interpret)
    Sqp, Dp = qp.shape[2], qp.shape[3]
    dop = pad.pad_dims(do, {2: Sqp, 3: Dp})
    # delta = rowsum(do * o): the constant FA-2 subtracts inside ds
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    delta = pad.pad_dims(delta, {2: Sqp})
    lsep = pad.pad_dims(lse, {2: Sqp})

    dq = _kb.flash_dq(qp, kp, vp, dop, lsep, delta, causal=causal, **kw)
    dkh, dvh = _kb.flash_dkv(qp, kp, vp, dop, lsep, delta, causal=causal,
                             **kw)
    # reduce the per-query-head dk/dv over the GQA group -> kv heads
    Skvp = kp.shape[2]
    dk = dkh.reshape(B, Hkv, group, Skvp, Dp).sum(axis=2)
    dv = dvh.reshape(B, Hkv, group, Skvp, Dp).sum(axis=2)
    dq = pad.unpad_dims(dq, {2: Sq, 3: D}).astype(q.dtype)
    dk = pad.unpad_dims(dk, {2: Skv, 3: D}).astype(k.dtype)
    dv = pad.unpad_dims(dv, {2: Skv, 3: D}).astype(v.dtype)
    return dq, dk, dv


def paged_flash_decode(q, k_pool, v_pool, page_table, kv_valid_len, layer,
                       *, k_scale=None, v_scale=None,
                       scale: float | None = None,
                       interpret: bool | None = None):
    """Decode attention over one layer of a paged KV pool, model layout.

    q (B,1,Hq,D); pools (L, num_pages, page_size, Hkv, D), stacked over
    layers (an unstacked pool passes ``pool[None]`` and layer 0, a
    bitcast); page_table (B, npages) int32; kv_valid_len scalar or (B,)
    int32; layer scalar int32 -> (B,1,Hq,D). Where the shapes need no
    padding (:func:`reads_pool_in_place`) the kernel reads the stack where
    it lies, at the scalar-prefetched layer. Otherwise the layer's pools
    are sliced and padded for the call: the head dim to the 128-lane
    boundary, the kv heads (with zero K/V and zero queries, sliced off the
    output) up to a count whose page rows fill the pool dtype's DMA tiles
    (``kernel.heads_dma_whole``), and the int8 scales' kv heads to full
    lanes. ``k_scale``/``v_scale`` (L, num_pages, page_size, Hkv) f32
    switch the kernel to the int8-dequantizing body.
    """
    B, S, Hq, D = q.shape
    if S != 1:
        raise ValueError(f"paged decode expects a single query, got S={S}")
    Hkv = k_pool.shape[3]
    if Hq % Hkv:
        raise ValueError(f"GQA requires Hq % Hkv == 0, got {Hq=} {Hkv=}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    scale = (D ** -0.5) if scale is None else scale
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    group = Hq // Hkv
    Dp = pad.round_up(D, LANE)
    Hp = kv_heads_padded(Hkv, k_pool.dtype)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    pools = [k_pool, v_pool] + ([] if k_scale is None else [k_scale, v_scale])
    if not reads_pool_in_place(Hkv, D, k_pool.dtype, k_scale is not None):
        # this layer alone, padded: a pad of the stack would copy every
        # layer on every call
        pools = [jax.lax.dynamic_index_in_dim(x, layer[0], 0)
                 for x in pools]
        layer = jnp.zeros((1,), jnp.int32)
        pools[:2] = [pad.pad_dims(x, {3: Hp, 4: Dp}) for x in pools[:2]]
        # a scale tile is copied whole only with its kv heads on full lanes
        Lp = pad.round_up(Hp, LANE)
        pools[2:] = [pad.pad_dims(x, {3: Lp}) for x in pools[2:]]
    kp, vp, *scales = pools
    ksp, vsp = scales if scales else (None, None)
    qp = pad.pad_dims(q.reshape(B, Hkv, group, D), {1: Hp, 3: Dp})
    table = jnp.asarray(page_table, jnp.int32)
    valid = jnp.broadcast_to(jnp.asarray(kv_valid_len, jnp.int32), (B,))
    out = _k.paged_flash_decode(qp.reshape(B, Hp * group, Dp), kp, vp, table,
                                valid, layer, ksp, vsp, scale=scale,
                                interpret=interpret)
    out = out.reshape(B, Hp, group, Dp)[:, :Hkv, :, :D]
    return out.reshape(B, 1, Hq, D)


def reads_pool_in_place(hkv: int, head_dim: int, dtype,
                        quantized: bool) -> bool:
    """Whether :func:`paged_flash_decode` reads a stacked pool of ``hkv``
    kv heads of ``head_dim`` where it lies: the head dim fills whole lanes,
    the kv heads whole DMA tiles, and there are no int8 scales to pad."""
    return (head_dim % LANE == 0 and kv_heads_padded(hkv, dtype) == hkv
            and not quantized)


def kv_heads_padded(hkv: int, dtype) -> int:
    """The kv-head count the kernel reads a pool of ``hkv`` heads at: the
    smallest ``>= hkv`` whose page rows DMA whole."""
    itemsize = jnp.dtype(dtype).itemsize
    h = hkv
    while not _k.heads_dma_whole(h, itemsize):
        h += 1
    return h


def block_tokens(page_size: int, hkv: int, head_dim: int, dtype,
                 npages: int) -> int:
    """Tokens of one block of :func:`paged_flash_decode` over a pool of
    ``(num_pages, page_size, hkv, head_dim)`` ``dtype`` pages and a table
    of ``npages`` pages a row: the kernel fetches a row's ``valid``
    positions rounded up to a multiple of this."""
    ppb, _ = _k.decode_blocks(page_size, kv_heads_padded(hkv, dtype),
                              pad.round_up(head_dim, LANE),
                              jnp.dtype(dtype).itemsize, npages)
    return ppb * page_size


flash_attention = registry.custom_vjp_fn(
    _flash_attention_impl, flash_attention_fwd, flash_attention_bwd)
flash_attention.__doc__ = """GQA flash attention with a custom VJP.
q (B,Hq,Sq,D), k/v (B,Hkv,Skv,D) -> (B,Hq,Sq,D).

For decode (Sq < Skv) the causal mask is right-aligned: query i attends to
keys [0, Skv - Sq + i]. ``bq``/``bk`` block the forward, ``bq_bwd``/
``bk_bwd`` the backward kernels (``None``: forward sizes, then defaults).
"""
