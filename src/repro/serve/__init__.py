"""Serving: continuous batching with communication-avoiding k-step decode.

Six modules, one contract:

- ``api``       — ``Request`` / ``Response`` / ``StreamDelta`` /
                  ``EngineStats`` dataclasses.
- ``sampling``  — ``SamplingParams`` (temperature / top-p / top-k / seed)
                  and the batched in-scan draw (``sample_tokens``): every
                  stochastic token is drawn inside the fused block, so
                  sampling costs zero extra host syncs.
- ``paging``    — ``CachePool``: the serving cache. Request state in
                  slots (allocate / free / defrag, per-slot request PRNG
                  keys); attention K/V in fixed-size pages stacked over the
                  layers, behind per-slot page tables, with refcounted
                  radix-trie shared-prefix reuse (``PrefixCache``),
                  page-level defrag, and optional int8 page storage
                  (``kv_dtype="int8"``: codes + f32 row/head scales, ~2x
                  resident capacity at matched pool bytes); sharded via
                  ``repro.dist.cache_specs`` when rules are bound.
- ``scheduler`` — FIFO admission + ``repro.dist.DeadlineGate`` overload
                  shedding.
- ``decode``    — the ``lax.scan``-fused k-step decode block: k tokens per
                  host sync (the paper's CA-k schedule on the serve path).
- ``engine``    — the run loop: ingest -> schedule -> k-step decode ->
                  retire -> stats; ``stream``/``stream_step`` surface token
                  deltas every k-block. ``Request.n > 1`` fans one request
                  into n streams sharing its prompt pages, stream i seeded
                  with ``fold_in_seed(seed, i)`` — bit-identical to the
                  standalone request carrying that seed.
"""
from repro.serve.api import (Request, Response, StreamDelta, EngineStats,
                             FINISH_EOS, FINISH_ERROR, FINISH_LENGTH,
                             FINISH_SHED)
from repro.serve.sampling import (SamplingParams, SlotSampling,
                                  fold_in_seed, host_fold_in, sample_tokens)
from repro.serve.paging import CachePool, PrefixCache, PageError, SlotError
from repro.serve.scheduler import Scheduler
from repro.serve.decode import (DecodeState, init_decode_state,
                                make_decode_block)
from repro.serve.engine import Engine

__all__ = [
    "Request", "Response", "StreamDelta", "EngineStats",
    "FINISH_EOS", "FINISH_ERROR", "FINISH_LENGTH", "FINISH_SHED",
    "SamplingParams", "SlotSampling", "sample_tokens",
    "fold_in_seed", "host_fold_in",
    "CachePool", "SlotError", "Scheduler", "PrefixCache", "PageError",
    "DecodeState", "init_decode_state", "make_decode_block",
    "Engine",
]
