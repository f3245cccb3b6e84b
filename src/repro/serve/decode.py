"""Communication-avoiding k-step fused decode.

The classic serving loop pays one host<->device round trip per generated
token: dispatch ``serve_step``, fetch the next token, dispatch again. That
latency term is the serving analogue of the per-iteration collective the
paper removes — and the fix is the same regrouping (arXiv:1710.08883): run
``k`` decode steps inside one ``lax.scan`` under one jit dispatch, and sync
with the host once per block. FLOPs are unchanged; the host-sync cost per
token drops by exactly ``k``, mirroring how CA-SFISTA's one collective
covers k Gram iterations.

Prefill rides the same schedule ("prefill/decode interleaving"): each slot
carries per-slot positions (see ``repro.models.decode_step``), and slots
still consuming their prompt feed prompt tokens into the shared step while
decoding slots feed their last sampled token. A freshly admitted request
therefore needs no separate prefill dispatch — it catches up inside the next
k-block while its batch neighbours keep generating.

Within a block, per-slot EOS / max-length masks freeze finished slots: their
``done`` flag lifts, they stop emitting and stop advancing, and the host
retires them at the next sync. (A frozen slot still flows through the step —
masked compute is the price of the fused schedule — but its writes land
beyond its own ``kv_valid`` horizon and its SSM state is zeroed on the next
allocate, so nothing leaks across requests.)

Sampling rides the same schedule: when a ``SlotSampling`` bundle is passed,
all k next-token draws (temperature / top-p / top-k, per-slot PRNG keys)
happen inside the scan body — see ``repro.serve.sampling`` — so stochastic
decode costs exactly as many host syncs as greedy: one per k tokens.

The decode state is donated to the block: its buffers become the block's
carry and its result, so the paged K/V pool lives in one device buffer
from block to block and each step writes its rows into it in place. A
caller never touches a state after passing it to a block; what the host
fetches afterwards (``BlockOut``) has buffers of its own, which the next
block's donation leaves intact.

Overlap contract (the engine's double-buffered loop, ``overlap=True``): a
block is a pure function of its dispatch-time inputs — every host-side
argument (prompt buffer, sampling policy, page table) is device-copied at
the call, and the carry it returns is only ever *functionally* updated by
later admissions, so jax's data-flow ordering serializes a block's writes
before the next block's reads with no host barrier. While a block is in
flight its slot rows are *owned*: the engine must not free/reallocate them
(stale-slot fencing) and must not permute them (defrag flushes the pipeline
first). Frozen-slot writes during that window land beyond the slot's own
``kv_valid`` horizon, and — in the paged layout — never inside published
prompt pages (a done slot writes at ``lengths >= prompt_len``), which is
what makes an in-flight block's garbage invisible to every other request.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.launch.steps import make_serve_step
from repro.serve.sampling import SlotSampling, sample_tokens


class DecodeState(NamedTuple):
    """Device-side per-slot decode state (the fused block's carry)."""
    cache: Any               # pool cache pytree (per-slot rows)
    lengths: jnp.ndarray     # (B,) int32: tokens written == next write pos
    last_tok: jnp.ndarray    # (B,) int32: last sampled token per slot
    n_out: jnp.ndarray       # (B,) int32: tokens emitted per slot
    done: jnp.ndarray        # (B,) bool: EOS / length / cache-full reached
    eos_hit: jnp.ndarray     # (B,) bool: done fired on the EOS branch (and
                             # no length cause fired the same step)


class BlockOut(NamedTuple):
    """What the host fetches from one block, in buffers of its own."""
    toks: jnp.ndarray        # (k, B) int32: the k steps' tokens (-1: none)
    emitted: jnp.ndarray     # (k, B) bool: tokens[t, b] is valid
    done: jnp.ndarray        # (B,) bool, after the block
    eos_hit: jnp.ndarray     # (B,) bool, after the block
    lengths: jnp.ndarray     # (B,) int32, after the block
    moe: Any                 # the block's expert counts (MoE), else None


def init_decode_state(cache, num_slots: int) -> DecodeState:
    """A fresh state whose leaves are distinct buffers (the block donates
    each one; a buffer shared by two leaves cannot be donated twice)."""
    seen = set()

    def own(x):
        if id(x) in seen:
            return jnp.array(x, copy=True)
        seen.add(id(x))
        return x
    z = lambda dt: jnp.zeros((num_slots,), dt)
    return DecodeState(cache=jax.tree.map(own, cache), lengths=z(jnp.int32),
                       last_tok=z(jnp.int32), n_out=z(jnp.int32),
                       done=z(bool), eos_hit=z(bool))


def make_decode_block(cfg, rules, *, k: int, max_len: int,
                      eos_id: Optional[int] = None):
    """Build the jitted k-step block.

    block(params, state, prompts, prompt_len, max_new, active, samp=None,
          page_table=None) -> (state', BlockOut)

    ``state`` is donated: its buffers are the block's carry and become
    ``state'``'s, so the caller must not use ``state`` again.

    prompts (B, P) holds each slot's prompt; a slot is *prefilling* while
    ``lengths < prompt_len`` and *decoding* after. ``out.toks[t, b]`` is
    valid iff ``out.emitted[t, b]`` (non-emitting steps carry -1). One host
    sync retrieves k tokens: the k-fold latency saving.

    samp: optional ``SlotSampling`` — per-slot temperature/top-p/top-k and
    PRNG keys; every draw happens inside the scan (``sample_tokens``), so
    the sync count is unchanged. None (or all temperatures 0) is the greedy
    path, bit-identical to the pre-sampling block.

    page_table: optional (B, pages_per_slot) int32 when the cache K/V
    leaves are a paged pool (``repro.serve.paging``). The engine reserves
    pages covering the block's k steps before dispatch, so the table is a
    constant input to the scan, not part of the carry.

    MoE caches carry the held experts' counts (``cache["moe"]``): the
    block zeroes them first, so ``out.moe`` holds its own k steps' sums.
    """
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    # kernel backend resolved by make_serve_step (registry policy at build
    # time)
    serve = make_serve_step(cfg, rules)

    def block(params, state: DecodeState, prompts, prompt_len, max_new,
              active, samp: Optional[SlotSampling] = None, page_table=None):
        P = prompts.shape[1]
        B = state.lengths.shape[0]
        # Decode rewrites some cache leaves in compute dtype (the mamba conv
        # window comes out bf16 inside an f32-initialized buffer, matching
        # the classic path's behaviour after its first step). A scan carry
        # must be dtype-stable from iteration 0, so cast once up front.
        sds = jax.ShapeDtypeStruct
        target = jax.eval_shape(serve, params, state.cache,
                                sds((B, 1), jnp.int32),
                                sds((B,), jnp.int32), page_table)[2]
        state = state._replace(cache=jax.tree.map(
            lambda x, t: x.astype(t.dtype), state.cache, target))
        if "moe" in state.cache:
            # the block's own expert counts, fetched with its tokens
            state = state._replace(cache=dict(state.cache, moe=jax.tree.map(
                jnp.zeros_like, state.cache["moe"])))

        # a slot whose prompt overflows the prompt buffer or the cache can
        # never satisfy ``lengths >= prompt_len - 1`` and would spin through
        # k-blocks forever without emitting; admission rejects these, and
        # this guard retires a stray one at the next sync instead
        unservable = prompt_len > jnp.minimum(P, max_len - 1)

        # named scopes: the step's own parts are decode.attn / decode.mlp /
        # decode.head (models); the block's per-slot bookkeeping around it
        # is decode.state
        def body(st: DecodeState, _):
            with jax.named_scope("decode.state"):
                done0 = st.done | (active & unservable)
                live = active & ~done0
                in_prefill = st.lengths < prompt_len
                idx = jnp.clip(st.lengths, 0, P - 1)
                ptok = jnp.take_along_axis(prompts, idx[:, None],
                                           axis=1)[:, 0]
                tok = jnp.where(in_prefill, ptok,
                                st.last_tok).astype(jnp.int32)
                pos = jnp.minimum(st.lengths, max_len - 1)
            nxt, logits, cache = serve(params, st.cache, tok[:, None], pos,
                                       page_table)
            nxt = nxt[:, 0]
            if samp is not None:
                # all k draws live inside this scan — zero extra host syncs;
                # greedy rows take the argmax above verbatim (bit parity)
                with jax.named_scope("decode.head"):
                    nxt = sample_tokens(logits[:, -1], nxt, samp, st.n_out)
            with jax.named_scope("decode.state"):
                # the step consuming the LAST prompt token produces the
                # first generated token; pure-prefill steps emit nothing
                emit = live & (st.lengths >= prompt_len - 1)
                n_out = st.n_out + emit.astype(jnp.int32)
                # length causes (max_new, cache-full) take precedence over a
                # coincident EOS draw: finish_reason is derived from eos_hit
                len_done = (emit & (n_out >= max_new)) \
                    | (live & (st.lengths >= max_len - 1))
                done = done0 | len_done
                eos_hit = st.eos_hit
                if eos_id is not None:
                    eos_now = emit & (nxt == eos_id)
                    done = done | eos_now
                    eos_hit = eos_hit | (eos_now & ~len_done & ~done0)
                new = DecodeState(
                    cache=cache,
                    lengths=st.lengths + live.astype(jnp.int32),
                    last_tok=jnp.where(live, nxt, st.last_tok),
                    n_out=n_out,
                    done=done,
                    eos_hit=eos_hit)
                out = jnp.where(emit, nxt, -1)
            return new, (out, emit)

        state, (toks, emitted) = jax.lax.scan(body, state, xs=None, length=k)
        return state, BlockOut(toks, emitted, state.done, state.eos_hit,
                               state.lengths, state.cache.get("moe"))

    return jax.jit(block, donate_argnums=(1,))
