"""Continuous-batching engine: ingest queue -> schedule -> k-step fused
decode -> retire slots -> stats.

One ``step()`` is one scheduling round plus one fused decode block: admit
queued requests into free cache slots (writing their prompts into the
device-resident prompt buffer, zeroing reused slot state, prefilling
whisper's cross-attention K/V), dispatch the k-step block, then make the
single host sync of the round — fetch the k emitted tokens and the per-slot
done masks, extend per-request outputs, and retire finished slots. The block
never recompiles: every shape (num_slots, max_prompt, k) is fixed at engine
construction, and admission only mutates slot rows between blocks.

Sampling (``Request.sampling``) changes none of that: per-slot temperature/
top-p/top-k and the request PRNG key are slot-row state written at admission,
and all k draws happen inside the fused block (``repro.serve.sampling``) —
the sync count with sampling on is identical to greedy.

Streaming: ``stream_step`` additionally returns per-request token deltas for
the round (``StreamDelta``), and ``stream`` is the generator form — tokens
surface every k-block instead of at retirement. ``step``/``run`` keep the
whole-response contract.

The cache is one ``CachePool`` (``repro.serve.paging``): request state in
slot rows, attention K/V in pages stacked over the layers, which the decode
block reads through a page table (``paged_attention``; the Pallas paged
kernel on TPU).

Double-buffering (``overlap=True``): the CA-k schedule already cut the sync
*count* to one per k steps; the overlapped loop hides the one that remains.
Rounds run a pipeline of :class:`_InFlight` records, at depth 0 (blocking:
each block is fetched in the round that dispatched it) or, overlapped, one
deep: ``jax.jit`` dispatch is asynchronous, so each round dispatches block
i+1 *before* blocking on block i's device->host transfer — all host work of
a round (admission, prompt staging, detokenize, stream deltas, scheduler
and defrag bookkeeping) overlaps device compute of the newer block.
Correctness rests on stale-slot fencing (mirroring the pool's page-table
discipline): a slot retired while a newer block is still in flight is
*fenced* — its pool row, pages, and PRNG key are released only when that
block completes, so admission can never hand the row to a new request the
in-flight block still writes. Structural moves (slot/page defrag) flush the
pipeline first. Admission updates are safe mid-flight because they are
functional updates on the in-flight block's *output* arrays — jax orders
them by data flow — and every block input (prompt buffers, sampling
policy, page tables) is snapshotted to the device at dispatch. Token
streams are bit-identical to the blocking engine: per-slot decode depends
only on the request (prompt, key, max_new), never on placement or fetch
timing.
"""
from __future__ import annotations

import time
from typing import Iterable, Iterator, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.flash_attention.ops import block_tokens
from repro.models import init_cache
from repro.models.attention import paged_pool_in_place
from repro.models.transformer import prefill_audio_cache
from repro.serve.api import (Request, Response, EngineStats, StreamDelta,
                             FINISH_EOS, FINISH_ERROR, FINISH_LENGTH,
                             FINISH_SHED)
from repro.serve.paging import CachePool
from repro.serve.decode import (BlockOut, init_decode_state,
                                make_decode_block)
from repro.serve.sampling import GREEDY, SlotSampling, host_fold_in
from repro.serve.scheduler import Scheduler

# ---------------------------------------------------------------------------
# observability handles (module-level: get-or-create once, mutate per round;
# every mutation is a no-op boolean check while repro.obs is disabled)
# ---------------------------------------------------------------------------
_M_SYNCS = obs.counter("repro_serve_syncs_total",
                       "host<->device round trips (one per fused k-block)")
_M_STEPS = obs.counter("repro_serve_steps_total",
                       "model decode steps (= syncs * k)")
_M_TOKENS = obs.counter("repro_serve_tokens_total",
                        "tokens delivered to responses")
_M_PREFILL = obs.counter("repro_serve_prefill_tokens_total",
                         "prompt tokens consumed in-loop")
_M_PROCESSED = obs.counter("repro_serve_tokens_processed_total",
                           "tokens the decode blocks wrote to the cache "
                           "(prompt and output)")
_M_KV_READS = obs.counter("repro_serve_kv_token_reads_total",
                          "K/V positions those tokens attended over")
_M_KV_FETCHED = obs.counter("repro_serve_kv_fetched_tokens_total",
                            "K/V positions the paged decode kernel fetched "
                            "for them (whole blocks)")
_M_KV_IN_PLACE = obs.counter("repro_serve_kv_in_place_blocks_total",
                             "decode blocks whose stacked paged K/V pool went "
                             "through without a copy")
_M_MOE_ASSIGN = obs.counter("repro_serve_moe_assignments_total",
                            "(token, held expert) pairs the decode steps "
                            "computed")
_M_MOE_HIT = obs.counter("repro_serve_moe_experts_hit_total",
                         "(step, layer, held expert) triples with a token")
_M_REQS = obs.counter("repro_serve_requests_total",
                      "completed requests by finish reason")
_M_PREFIX_HITS = obs.counter("repro_serve_prefix_hits_total",
                             "admissions that matched the prefix trie")
_M_PREFIX_TOKENS = obs.counter("repro_serve_prefix_tokens_total",
                               "prefill tokens skipped via prefix reuse")
_M_COW = obs.counter("repro_serve_cow_copies_total",
                     "copy-on-write page divergences")
_M_DEFRAGS = obs.counter("repro_serve_defrags_total",
                         "cache compactions by kind (slot/page)")
_M_TTFT = obs.histogram("repro_serve_ttft_seconds",
                        "submit -> first generated token")
_M_TPOT = obs.histogram("repro_serve_tpot_seconds",
                        "mean per-token latency after the first token")
_M_QWAIT = obs.histogram("repro_serve_queue_wait_seconds",
                         "submit -> slot assignment")
_M_LATENCY = obs.histogram("repro_serve_latency_seconds",
                           "submit -> retirement")
_M_HIDDEN = obs.counter("repro_serve_hidden_syncs_total",
                        "k-block fetches made while a newer block was "
                        "already in flight (double-buffered loop)")
_M_BLOCKED = obs.histogram("repro_serve_host_blocked_seconds",
                           "host wall time blocked per k-block result fetch")


def _fetched_upto(n: np.ndarray, block: int) -> np.ndarray:
    """K/V positions the paged decode kernel fetches for the tokens written
    at positions ``0 .. n-1`` of a row: the token at ``p`` attends over
    ``p + 1`` positions, fetched in whole blocks of ``block`` tokens, so
    the sum of ``ceil(v / block) * block`` over ``v = 1 .. n``."""
    q, r = np.divmod(n.astype(np.int64), block)
    return block * (block * q * (q + 1) // 2 + r * (q + 1))


class _InFlight:
    """One dispatched-but-not-fetched k-block (the pipeline entry).

    Holds the block's ``BlockOut``, buffers of their own that neither later
    admissions nor the next block's donation of ``Engine.state`` touch, so
    completion reads exactly what this block computed. ``slots``/``active``
    snapshot the slot
    ownership at dispatch: completion only touches rows this block owned,
    and ``deferred`` collects slots retired while the block was in flight —
    their pool rows stay fenced (allocated, unreusable) until the block
    lands, because its device writes still target them.
    """

    __slots__ = ("out", "slots", "active", "live", "ticket", "deferred")

    def __init__(self, out: BlockOut, slots, active, live, ticket):
        self.out = out                  # the block's device results
        self.slots = slots              # slot ids owned at dispatch
        self.active = active            # (B,) host bool snapshot at dispatch
        self.live = live                # active slot count at dispatch
        self.ticket = ticket            # obs.mark_dispatch ticket
        self.deferred: List[int] = []   # retired slots fenced on this block


class Engine:
    """Continuous-batching serving engine over a paged cache pool.

    params/cfg: model weights + arch config (any of the 10 assigned archs).
    num_slots: concurrent sequences (the fused block's batch dimension).
    max_len: per-slot cache depth; k: decode steps per host sync.
    eos_id: greedy decode stops a slot on this token (None: length-only).
    scheduler: admission policy; default plain FIFO (pass
    ``Scheduler(gate=DeadlineGate(...))`` for overload shedding).
    page_size: tokens per page of the attention K/V pool
    (``repro.serve.paging``); must be >= 1. A pure-SSM arch has no
    attention K/V: its pool has no pages and its block no page table.
    prefix_cache: reuse radix-trie shared prompt-prefix
    pages across requests (their prefill steps are skipped). Enabled only
    for families whose prompt K/V depends on the tokens alone — recurrent
    state must consume every prompt token, and whisper's decoder K/V mixes
    in per-request encoder output — so ssm/hybrid/audio decline it.
    num_pages: page-pool depth override (default: full slot backing + 1
    scratch page; doubled when quantized).
    kv_dtype: ``"f32"`` keeps the init_cache dtypes; ``"int8"`` stores
    paged K/V as int8 codes with per-(page row, head)
    f32 scales — quantized on scatter, dequantized inside both
    ``paged_attention`` impls — roughly doubling resident-request capacity
    at fixed pool bytes. Greedy token parity vs the f32 pool is statistical
    (per-element rounding ≤ absmax/254), not bitwise.
    overlap: double-buffer the host loop — dispatch each round's block
    before blocking on the previous round's results, hiding the per-block
    host work behind device compute (see module docstring). Token streams
    are bit-identical either way; ``stats.hidden_syncs`` /
    ``stats.host_blocked_s`` report the effect.

    ``Request.n > 1`` fans a request into n slots that share its prompt's
    whole pages (refcount bump at admission, no copies) and draw from
    ``fold_in(request_key, stream)``; each stream is bit-identical to a
    standalone request carrying that derived key, and each finishes with
    its own ``Response`` (``stream`` field set).
    """

    def __init__(self, params, cfg, *, rules=None, num_slots: int = 8,
                 max_len: int = 128, k: int = 4,
                 max_prompt: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 scheduler: Optional[Scheduler] = None,
                 enc_len: Optional[int] = None,
                 defrag_threshold: float = 0.5,
                 page_size: int = 16,
                 prefix_cache: bool = False,
                 num_pages: Optional[int] = None,
                 kv_dtype: str = "f32",
                 overlap: bool = False):
        self.params = params
        self.cfg = cfg
        self.k = int(k)
        self.max_len = int(max_len)
        self.max_prompt = int(max_prompt if max_prompt is not None
                              else max_len)
        self.eos_id = eos_id
        enc_len = (enc_len if enc_len is not None else max_len) \
            if cfg.family == "audio" else None
        self.pool = pool = CachePool(cfg, num_slots, max_len,
                                     page_size=page_size, rules=rules,
                                     enc_len=enc_len, num_pages=num_pages,
                                     kv_dtype=kv_dtype)
        # tokens of one block of the paged decode kernel (0: no pages). The
        # layer scan carries the pool stacked over its layers, the block's
        # donated state keeps it in one buffer, and the attention op reads
        # it where it lies unless the kernel must pad it
        self._kv_block, self._kv_in_place = 0, False
        if pool.has_paged:
            kv = pool.kv_leaf()
            self._kv_block = block_tokens(pool.page_size, kv.shape[-2],
                                          kv.shape[-1], kv.dtype,
                                          pool.pages_per_slot)
            self._kv_in_place = paged_pool_in_place(kv, pool.quantized)
        self.prefix_on = (bool(prefix_cache)
                          and cfg.family in ("dense", "vlm", "moe"))
        self.scheduler = scheduler if scheduler is not None else Scheduler()
        self.defrag_threshold = float(defrag_threshold)
        self.overlap = bool(overlap)
        self._pipe: List[_InFlight] = []    # dispatched, not yet fetched
        self._block = make_decode_block(cfg, rules, k=self.k,
                                        max_len=self.max_len, eos_id=eos_id)
        self.state = init_decode_state(self.pool.make_cache(), num_slots)
        B, P = num_slots, self.max_prompt
        self._prompt_buf = np.zeros((B, P), np.int32)
        self._prompt_len = np.zeros((B,), np.int32)
        self._len_host = np.zeros((B,), np.int32)   # host mirror of lengths
        self._max_new = np.ones((B,), np.int32)
        self._active = np.zeros((B,), bool)
        # per-slot sampling policy (written at admission; keys live in the
        # pool so they follow the request through defrag)
        self._temp = np.zeros((B,), np.float32)
        self._top_p = np.ones((B,), np.float32)
        self._top_k = np.zeros((B,), np.int32)
        self._seed_rng = np.random.RandomState()    # for seedless requests
        self._slot_req: dict = {}
        self._slot_toks: dict = {}
        self._slot_t0: dict = {}
        self._slot_prompt: dict = {}    # int token lists for the prefix trie
        self._slot_first: dict = {}     # first-token wall time (TTFT metric)
        self._slot_stream: dict = {}    # fan-out stream index per slot
        self._groups: dict = {}         # request id -> unfinished streams
        self.stats = EngineStats()
        if cfg.family == "audio":
            row = lambda p, enc: dict(cross=prefill_audio_cache(
                p, cfg, init_cache(cfg, 1, self.max_len, enc_len=enc_len),
                enc[None].astype(jnp.bfloat16))["cross"])
            self._audio_row = jax.jit(row)

    # -------------------------------------------------------------- ingest
    def submit(self, req: Request) -> None:
        """Enqueue a request. Malformed requests (empty prompt, missing
        enc_embeds) raise immediately; an over-long prompt is accepted here
        but rejected with a ``finish_reason="error"`` Response at admission
        — the same guard that catches requests submitted straight to the
        scheduler, which previously entered a slot they could never finish
        (the prompt can never satisfy ``lengths >= prompt_len - 1``)."""
        n = len(req.prompt)
        if n < 1:
            raise ValueError(f"request {req.id}: empty prompt")
        n_streams = int(req.n) if getattr(req, "n", None) is not None else 1
        if n_streams < 1:
            raise ValueError(f"request {req.id}: n must be >= 1, "
                             f"got {req.n}")
        if n_streams > self.pool.num_slots:
            # a group admits atomically (all streams prefill in lockstep to
            # share prompt pages) — wider than the pool can never be placed
            raise ValueError(
                f"request {req.id}: n={n_streams} exceeds "
                f"num_slots={self.pool.num_slots}")
        if self.cfg.family == "audio":
            want = (self.pool.enc_len, self.cfg.d_model)
            got = np.shape(req.enc_embeds) if req.enc_embeds is not None \
                else None
            if got != want:
                raise ValueError(f"request {req.id}: enc-dec arch needs "
                                 f"enc_embeds of shape {want}, got {got}")
        self.scheduler.submit(req)

    # -------------------------------------------------------------- admit
    def _admit(self, now: float) -> List[Response]:
        out: List[Response] = []
        admit, shed = self.scheduler.schedule(self.pool.free_count, now)
        for r in shed:
            wait = now - r.arrival_s
            out.append(Response(id=r.id, tokens=[], finish_reason=FINISH_SHED,
                                prompt_len=len(r.prompt), queue_wait_s=wait,
                                latency_s=wait))
            self.stats.shed += 1
            _M_REQS.inc(reason=FINISH_SHED)
        st = self.state
        slots: List[int] = []
        init_lens: List[int] = []
        for r in admit:
            n = len(r.prompt)
            if n > self.max_prompt or n >= self.max_len:
                # an over-long prompt can never reach its first emit
                # (lengths >= prompt_len - 1 is unsatisfiable within the
                # prompt buffer / cache depth): reject without a slot
                # instead of spinning in the k-block forever
                wait = now - r.arrival_s
                out.append(Response(
                    id=r.id, tokens=[], finish_reason=FINISH_ERROR,
                    prompt_len=n, queue_wait_s=wait, latency_s=wait))
                self.stats.rejected += 1
                _M_REQS.inc(reason=FINISH_ERROR)
                continue
            n_streams = int(getattr(r, "n", 1) or 1)
            sp = r.sampling if r.sampling is not None else GREEDY
            base_key = None
            if not sp.greedy:
                seed = sp.seed if sp.seed is not None \
                    else int(self._seed_rng.randint(0, 2 ** 31 - 1))
                base_key = np.array([seed >> 32, seed & 0xFFFFFFFF],
                                    np.uint32)
            prompt = [int(t) for t in r.prompt]
            P = self.pool.page_size
            group_slots: List[int] = []
            m0, cow, pinned = 0, None, False
            for i in range(n_streams):
                slot = self.pool.allocate(r.id)
                group_slots.append(slot)
                slots.append(slot)
                if self.cfg.family == "audio":
                    cache = self.pool.set_slot(
                        st.cache, slot,
                        self._audio_row(self.params,
                                        jnp.asarray(r.enc_embeds)))
                else:
                    cache = self.pool.zero_slot(st.cache, slot)
                st = st._replace(cache=cache)
                if i == 0:
                    if self.prefix_on:
                        # shared-prefix reuse: trie-matched pages map
                        # read-only into this slot's table and their prefill
                        # steps vanish — the slot starts at lengths == m0
                        m0, cow = self.pool.map_prefix(slot, prompt)
                        if cow is not None:
                            st = st._replace(
                                cache=self.pool.copy_page(st.cache, *cow))
                            self.stats.cow_copies += 1
                            _M_COW.inc()
                        if m0:
                            self.stats.prefix_hits += 1
                            # every stream of the group starts at m0
                            self.stats.prefix_tokens += m0 * n_streams
                            _M_PREFIX_HITS.inc()
                            _M_PREFIX_TOKENS.inc(m0 * n_streams)
                    if n_streams > 1:
                        # reserve the whole-prompt page span up front so
                        # the siblings below adopt (refcount-share) it
                        # instead of allocating duplicate pages
                        self.pool.reserve(slot, (n // P) * P)
                        if cow is not None:
                            # keep the CoW source page off the LRU eviction
                            # path until every sibling's copy is issued
                            self.pool.pin_page(cow[0])
                            pinned = True
                else:
                    self.stats.shared_prompt_pages += \
                        self.pool.adopt_prompt_pages(group_slots[0], slot, n)
                    if cow is not None and (m0 // P) >= (n // P):
                        # the trie match runs into the private boundary
                        # page: this sibling needs its own CoW copy
                        dst = self.pool.map_cow_page(slot, n // P)
                        st = st._replace(cache=self.pool.copy_page(
                            st.cache, cow[0], dst))
                        self.stats.cow_copies += 1
                        _M_COW.inc()
                self._prompt_buf[slot, :] = 0
                self._prompt_buf[slot, :n] = np.asarray(r.prompt, np.int32)
                self._prompt_len[slot] = n
                self._len_host[slot] = m0
                init_lens.append(m0)
                self._slot_prompt[slot] = prompt
                self._max_new[slot] = max(int(r.max_new_tokens), 1)
                self._active[slot] = True
                self._temp[slot] = sp.temperature
                self._top_p[slot] = sp.top_p
                self._top_k[slot] = sp.top_k
                if base_key is not None:
                    # stream i draws from fold_in(request_key, i): derived
                    # host-side (no hidden sync) and bit-identical to a
                    # standalone request seeded with fold_in_seed(seed, i)
                    self.pool.set_slot_key(
                        slot, base_key if n_streams == 1
                        else host_fold_in(base_key, i))
                self._slot_req[slot] = r
                self._slot_stream[slot] = i
                self._slot_toks[slot] = []
                self._slot_t0[slot] = now
                if obs.enabled():
                    obs.instant("serve.admit_request", id=r.id, slot=slot,
                                prompt_len=n, prefix_reused=m0, stream=i)
            if pinned:
                self.pool.unpin_page(cow[0])
            self._groups[r.id] = n_streams
            self.stats.admitted += 1
            if n_streams > 1:
                self.stats.fanout_groups += 1
                self.stats.fanout_streams += n_streams
            if obs.enabled():
                _M_QWAIT.observe(now - r.arrival_s)
        if slots:
            idx = jnp.asarray(slots, jnp.int32)
            z = jnp.zeros((len(slots),), jnp.int32)
            st = st._replace(
                lengths=st.lengths.at[idx].set(
                    jnp.asarray(init_lens, jnp.int32)),
                last_tok=st.last_tok.at[idx].set(z),
                n_out=st.n_out.at[idx].set(z),
                done=st.done.at[idx].set(False),
                eos_hit=st.eos_hit.at[idx].set(False))
        self.state = st
        return out

    # -------------------------------------------------------------- defrag
    def _needs_defrag(self) -> bool:
        """Threshold check only — decides whether a pipeline flush (and,
        overlapped, its one-round bubble) is worth it. Fenced slots awaiting
        release still count as live here; their frees land next completion
        and the check runs every round, so a triggered defrag is at most one
        round late."""
        if self.pool.live_count and \
                self.pool.fragmentation() >= self.defrag_threshold:
            return True
        return self.pool.page_fragmentation() >= self.defrag_threshold

    def _defrag(self) -> None:
        # defrag permutes slot rows / page tables in place: the loop must
        # flush its pipeline first (no block may own moved rows)
        assert not self._pipe, "defrag with a block in flight"
        with obs.span("serve.defrag"):
            if self.pool.live_count and \
                    self.pool.fragmentation() >= self.defrag_threshold:
                cache, perm, mapping = self.pool.defrag(self.state.cache)
                take = lambda a: self.pool.take_rows(a, perm)
                self.state = self.state._replace(
                    cache=cache, lengths=take(self.state.lengths),
                    last_tok=take(self.state.last_tok),
                    n_out=take(self.state.n_out),
                    done=take(self.state.done),
                    eos_hit=take(self.state.eos_hit))
                hperm = np.asarray(perm)
                self._prompt_buf = self._prompt_buf[hperm]
                self._prompt_len = self._prompt_len[hperm]
                self._len_host = self._len_host[hperm]
                self._max_new = self._max_new[hperm]
                self._active = self._active[hperm]
                self._temp = self._temp[hperm]
                self._top_p = self._top_p[hperm]
                self._top_k = self._top_k[hperm]
                self._slot_req = {mapping[s]: r
                                  for s, r in self._slot_req.items()}
                self._slot_toks = {mapping[s]: t
                                   for s, t in self._slot_toks.items()}
                self._slot_t0 = {mapping[s]: t
                                 for s, t in self._slot_t0.items()}
                self._slot_prompt = {mapping[s]: p
                                     for s, p in self._slot_prompt.items()}
                self._slot_first = {mapping[s]: t
                                    for s, t in self._slot_first.items()}
                self._slot_stream = {mapping[s]: i
                                     for s, i in self._slot_stream.items()}
                self.stats.defrags += 1
                _M_DEFRAGS.inc(kind="slot")
            if self.pool.page_fragmentation() >= self.defrag_threshold:
                # pure page permutation: slot contents (and the
                # emission-count PRNG stream) are unchanged, so defrag stays
                # invisible to tokens
                self.state = self.state._replace(
                    cache=self.pool.defrag_pages(self.state.cache))
                self.stats.page_defrags += 1
                _M_DEFRAGS.inc(kind="page")

    # ------------------------------------------------------ dispatch/fetch
    def _dispatch_block(self) -> _InFlight:
        """Dispatch one fused k-step block (async — no host sync here).

        Every input the block reads is snapshotted at this point: prompt
        buffers / sampling policy / page tables copy host->device now, and
        the returned record captures the raw output arrays before any later
        admission functionally updates ``self.state`` on top of them.
        """
        live = int(self._active.sum())
        with obs.span("serve.dispatch", k=self.k, live=live):
            samp = SlotSampling(temperature=jnp.asarray(self._temp),
                                top_p=jnp.asarray(self._top_p),
                                top_k=jnp.asarray(self._top_k),
                                key=jnp.asarray(self.pool.slot_keys))
            # pre-reserve pages for every position this block can write, so
            # the table is constant across the k in-scan steps; under overlap
            # ``_len_host`` is one un-fetched block stale, so the horizon
            # covers the in-flight block's k steps plus this one's
            horizon = self.k * (2 if self.overlap else 1)
            for slot in self._slot_req:
                self.pool.reserve(slot, int(self._len_host[slot]) + horizon)
            page_table = jnp.asarray(self.pool.tables) \
                if self.pool.has_paged else None
            self.stats.peak_live_pages = max(self.stats.peak_live_pages,
                                             self.pool.live_page_count())
            ticket = obs.mark_dispatch("serve.dispatch")
            # the state is donated: the block's result replaces it
            self.state, out = self._block(
                self.params, self.state, jnp.asarray(self._prompt_buf),
                jnp.asarray(self._prompt_len), jnp.asarray(self._max_new),
                jnp.asarray(self._active), samp, page_table)
            return _InFlight(out, list(self._slot_req), self._active.copy(),
                             live, ticket)

    def _complete_block(self, inf: _InFlight
                        ) -> Tuple[List[StreamDelta], List[Response]]:
        """Fetch one in-flight block's results (the round's single host
        sync) and run the host half of the round: stats, prefix publishing,
        token extension, retirement. Completion only touches slots the block
        owned at dispatch — rows admitted after are left to their own block."""
        obs.mark_fetch(inf.ticket)
        t0 = time.perf_counter()
        with obs.span("serve.fetch"):
            # one coalesced device->host transfer: k tokens + per-slot masks
            # (+ the block's expert counts)
            fetched = jax.device_get(inf.out)
        blocked = time.perf_counter() - t0
        with obs.span("serve.complete"):
            return self._finish_block(inf, *fetched, blocked)

    def _finish_block(self, inf: _InFlight, toks, emitted, done, eos_hit,
                      len_after, moe, blocked: float
                      ) -> Tuple[List[StreamDelta], List[Response]]:
        # fence release: slots retired while ``inf`` was in flight return to
        # the pool only now — nothing could reallocate them while the
        # block's device writes still targeted their rows/pages
        for slot in inf.deferred:
            self.pool.free(slot)
        overlapped = bool(self._pipe)   # a newer block is already in flight
        out: List[Response] = []
        deltas: List[StreamDelta] = []
        self.stats.syncs += 1
        self.stats.steps += self.k
        self.stats.kv_in_place_blocks += int(self._kv_in_place)
        self.stats.occupancy_sum += inf.live / self.pool.num_slots
        self.stats.host_blocked_s += blocked
        if overlapped:
            self.stats.hidden_syncs += 1
        # host length mirror: only rows this block owned advanced; rows
        # admitted while it was in flight keep their admission-time value
        len_before = self._len_host
        plen = self._prompt_len
        new_prefill = int(
            (np.minimum(len_after, plen) - np.minimum(len_before, plen))
            [inf.active].sum())
        # work of the block's steps: each token written at position p
        # attended over the p + 1 positions up to it
        lb = len_before[inf.active].astype(np.int64)
        la = len_after[inf.active].astype(np.int64)
        processed = int((la - lb).sum())
        kv_reads = int((la * (la + 1) - lb * (lb + 1)).sum()) // 2
        self.stats.tokens_processed += processed
        self.stats.kv_token_reads += kv_reads
        kv_fetched = 0
        if self._kv_block:
            kv_fetched = int((_fetched_upto(la, self._kv_block)
                              - _fetched_upto(lb, self._kv_block)).sum())
            self.stats.kv_fetched_tokens += kv_fetched
        self._len_host = np.where(inf.active, len_after, self._len_host)
        self.stats.prefill_tokens += new_prefill
        if moe is not None:
            self.stats.moe_assignments += int(moe["assignments"])
            self.stats.moe_experts_hit += int(moe["experts_hit"])
        if obs.enabled():
            _M_SYNCS.inc()
            _M_STEPS.inc(self.k)
            _M_PREFILL.inc(new_prefill)
            _M_PROCESSED.inc(processed)
            _M_KV_READS.inc(kv_reads)
            _M_KV_FETCHED.inc(kv_fetched)
            _M_KV_IN_PLACE.inc(int(self._kv_in_place))
            if moe is not None:
                _M_MOE_ASSIGN.inc(int(moe["assignments"]))
                _M_MOE_HIT.inc(int(moe["experts_hit"]))
            _M_BLOCKED.observe(blocked)
            if overlapped:
                _M_HIDDEN.inc()
        if self.prefix_on:
            # publish fully written whole-prompt pages to the trie *before*
            # the retire loop releases this round's finished slots
            for slot in inf.slots:
                if slot in self._slot_req:
                    self.pool.register_prefix(slot, self._slot_prompt[slot],
                                              int(len_after[slot]))
        end = self.scheduler.clock()   # same clock as admission timestamps
        for slot in inf.slots:
            if slot not in self._slot_req:
                continue                # retired by an earlier completion
            got = [int(t) for t in toks[:, slot][emitted[:, slot]]]
            self._slot_toks[slot].extend(got)
            self.stats.tokens_out += len(got)
            if obs.enabled():
                if got:
                    _M_TOKENS.inc(len(got))
                    if slot not in self._slot_first:
                        # first tokens of the block all land at the sync, so
                        # TTFT is block-granular — exactly the latency the
                        # CA-k tradeoff spends
                        ttft = end - self._slot_req[slot].arrival_s
                        self._slot_first[slot] = ttft
                        _M_TTFT.observe(ttft)
            if not done[slot]:
                if got:
                    deltas.append(StreamDelta(
                        id=self._slot_req[slot].id, tokens=got,
                        stream=self._slot_stream.get(slot, 0)))
                continue
            r = self._slot_req.pop(slot)
            seq = self._slot_toks.pop(slot)
            t0 = self._slot_t0.pop(slot)
            stream = self._slot_stream.pop(slot, 0)
            self._slot_prompt.pop(slot, None)
            # reason comes from the device-side done branch: a max_new/
            # cache-full retirement whose last draw happens to equal eos_id
            # is still a length finish
            reason = FINISH_EOS if bool(eos_hit[slot]) else FINISH_LENGTH
            resp = Response(id=r.id, tokens=seq, finish_reason=reason,
                            prompt_len=len(r.prompt),
                            queue_wait_s=t0 - r.arrival_s,
                            latency_s=end - r.arrival_s, stream=stream)
            # group bookkeeping: the request is fully retired when its last
            # stream finishes (each stream ships its own Response)
            left = self._groups.get(r.id)
            if left is not None:
                if left <= 1:
                    del self._groups[r.id]
                else:
                    self._groups[r.id] = left - 1
            if obs.enabled():
                _M_REQS.inc(reason=reason)
                _M_LATENCY.observe(resp.latency_s)
                ttft = self._slot_first.get(slot)
                if ttft is not None and len(seq) > 1:
                    _M_TPOT.observe((resp.latency_s - ttft) / (len(seq) - 1))
                obs.instant("serve.retire", id=r.id, reason=reason,
                            tokens=len(seq))
            self._slot_first.pop(slot, None)
            out.append(resp)
            deltas.append(StreamDelta(id=r.id, tokens=got, done=True,
                                      response=resp, stream=stream))
            if self._pipe:
                # stale-slot fence: a newer in-flight block still owns this
                # row (it was active at that block's dispatch) — defer the
                # pool free until that block completes, so admission can't
                # hand the row to a request the block still writes
                self._pipe[-1].deferred.append(slot)
            else:
                self.pool.free(slot)
            self._active[slot] = False
            # reset the slot's sampling policy with it: a stale temperature
            # in a freed slot would keep the whole-batch-greedy fast path
            # (lax.cond in sample_tokens) from ever firing again
            self._temp[slot] = 0.0
            self._top_p[slot] = 1.0
            self._top_k[slot] = 0
            self.stats.retired += 1
        return deltas, out

    # ---------------------------------------------------------------- step
    def stream_step(self, now: Optional[float] = None
                    ) -> Tuple[List[StreamDelta], List[Response]]:
        """One scheduling round + one fused k-step block + one host sync.

        Returns ``(deltas, responses)``: ``responses`` are the round's
        completed requests (retired / shed / rejected — the ``step()``
        contract); ``deltas`` additionally surface the tokens every live
        request gained this block, so callers can stream k tokens per sync
        instead of waiting for retirement.

        The round clock is taken at entry — *before* the block dispatch and
        before blocking on any previous block's results — so DeadlineGate
        waits are measured against dispatch time. Evaluating them after the
        completion fetch would silently extend every deadline by one block
        under the double-buffered loop.
        """
        now = self.scheduler.clock() if now is None else now
        with obs.span("serve.admit"):
            out = self._admit(now)
        # shed / rejected requests never held a slot: terminal delta only
        deltas = [StreamDelta(id=r.id, tokens=[], done=True, response=r)
                  for r in out]
        if self._active.any():
            self._pipe.append(self._dispatch_block())
        # fetch the oldest block while the pipeline is deeper than its depth
        # — blocking (0) fetches the block just dispatched; overlapped (1)
        # fetches a block once a newer one is in flight, so its host work
        # hides behind device compute — and drain fully when nothing new
        # was dispatched (tail of the stream)
        depth = 1 if self.overlap else 0
        while self._pipe and (len(self._pipe) > depth
                              or not self._active.any()):
            d, o = self._complete_block(self._pipe.pop(0))
            deltas += d
            out += o
        if self._needs_defrag():
            # structural slot/page moves: flush the pipeline first — defrag
            # must never permute rows an in-flight block still owns
            while self._pipe:
                d, o = self._complete_block(self._pipe.pop(0))
                deltas += d
                out += o
            self._defrag()
        return deltas, out

    def step(self, now: Optional[float] = None) -> List[Response]:
        """One scheduling round + one fused k-step block + one host sync;
        returns the round's completed responses (see ``stream_step`` for the
        token-delta form)."""
        return self.stream_step(now)[1]

    # ----------------------------------------------------------------- run
    def _drained(self) -> bool:
        return (not len(self.scheduler) and self.pool.live_count == 0
                and not self._pipe)

    def run(self, requests: Iterable[Request] = (), *,
            max_syncs: int = 1_000_000) -> List[Response]:
        """Drain: submit ``requests``, then step until queue and slots empty."""
        for r in requests:
            self.submit(r)
        out: List[Response] = []
        for _ in range(max_syncs):
            if self._drained():
                return out
            out.extend(self.step())
        # re-check after the final step: a workload that drains in exactly
        # max_syncs rounds is a success, not a timeout
        if self._drained():
            return out
        raise RuntimeError(f"engine did not drain within {max_syncs} syncs")

    def stream(self, requests: Iterable[Request] = (), *,
               max_syncs: int = 1_000_000) -> Iterator[StreamDelta]:
        """Streaming drain: yields a ``StreamDelta`` per request per k-block
        as tokens land; each request's final delta has ``done=True`` and
        carries its ``Response``. Tokens therefore surface with one block of
        latency instead of whole-response latency, at the same sync count."""
        for r in requests:
            self.submit(r)
        for _ in range(max_syncs):
            if self._drained():
                return
            deltas, _ = self.stream_step()
            yield from deltas
        if self._drained():
            return
        raise RuntimeError(f"engine did not drain within {max_syncs} syncs")
