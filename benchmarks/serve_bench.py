"""Serving benchmark: steady-state decode throughput of the continuous-
batching engine as a function of k (decode steps per host sync), greedy vs
sampled.

Saturated-decode methodology: exactly ``slots`` requests with length-1
prompts and a common token budget, so every slot decodes in lockstep for the
whole run (no admission churn in the timed region) and ``stats.steps`` is
the true decode-step count. One untimed drain compiles the fused block; the
timed drain then measures per-step wall time. The k=1 row IS the classic
one-sync-per-token schedule, so ms/step falling with k is the paper's
latency-by-k claim measured on the serve path.

Sampled rows rerun the same sweep with temperature/top-p sampling attached
to every request. The engine's sync counter is the instrumentation for the
PR's core claim, asserted here on every pair of runs: sampling draws all k
tokens inside the fused block, so the sampled run makes EXACTLY as many host
syncs as the greedy run — the ``mode=sampled`` ms/step rows price the
in-scan sampling math (sort + gumbel per step), not extra round trips.

Overlap rows (``mode=overlap``) rerun the greedy sweep through the
double-buffered host loop (``Engine(overlap=True)``) and assert
token-bit-identical output; every cell reports its hidden vs blocking sync
split and the mean per-block host-blocked time next to the blocking
engine's, and the compile drain's auditor additionally checks
``audit.overlap_epochs == stats.hidden_syncs`` bitwise — the engine's
overlap bookkeeping verified at the intercepted jax boundary.

Every row runs the engine over its paged ``CachePool`` (16-token pages
unless a row says otherwise). The ``serve-prefix`` rows drain a shared-system-prompt workload (a common
32-token prefix, one unique tail token per request, three waves through the
slots) with the radix prefix cache off and on: the on-run must emit
bit-identical tokens while consuming at most half the prefill tokens, with
the CA-k invariant (steps == syncs * k) intact on both runs. Rows record
prefill tokens and mean resident requests per sync.

The ``serve-capacity`` rows price the int8 page pool: two pools sized from
the same byte budget, residents admitted (allocate + full-span reserve)
until ``PageError`` — the quantized pool must hold >= 2x the resident
requests of the f32 pool at matched bytes (an int8 page plus its f32
row/head scales costs ~(Dh+4)/(2*Dh) of the bf16 page it replaces, and the
page-granular remainder the f32 pool strands converts into whole spans).
The ``serve-fanout`` rows drain one n=4 request against 4 separate
admissions carrying the derived ``fold_in_seed`` seeds: token streams must
match bitwise, at no extra syncs and a strictly lower page high-water mark
(the siblings share the prompt's whole pages by refcount).

Observability gates (``repro.obs``): every compile drain runs under
``obs.sync_audit()`` and asserts the audited host round-trip epochs equal
``EngineStats.syncs`` bitwise — the engine's bookkeeping checked against
interception at the jax/numpy boundary, for every (k, slots, mode) cell.
The final ``serve-obs/disabled_overhead`` row times the per-round
instrumentation bundle with obs disabled and asserts it costs < 1% of a
real k=1 sync.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from benchmarks.common import emit
from repro import obs
from repro.configs import get_arch, smoke_config
from repro.models import init_params
from repro.serve import CachePool, Engine, PageError, Request, SamplingParams
from repro.serve.sampling import fold_in_seed

ARCH = "internlm2-1.8b"
NEW_TOKENS = 64
SAMPLED = SamplingParams(temperature=0.8, top_p=0.9)


def _requests(cfg, n, seed=0, sampling=None):
    rng = np.random.RandomState(seed)
    sp = lambda i: None if sampling is None \
        else dataclasses.replace(sampling, seed=i)
    return [Request(id=f"r{i}", prompt=[int(rng.randint(cfg.vocab))],
                    max_new_tokens=NEW_TOKENS, sampling=sp(i))
            for i in range(n)]


def _timed_drain(cfg, params, slots, k, sampling, overlap=False):
    eng = Engine(params, cfg, num_slots=slots, max_len=NEW_TOKENS + 8,
                 k=k, max_prompt=4, overlap=overlap)
    # untimed compile drain, under the jax-boundary sync auditor: the
    # engine's own sync counter must agree bitwise with the audited number
    # of host round-trip epochs — EngineStats.syncs is bookkeeping, the
    # audit is ground truth measured at the intercepted jax/numpy reads
    with obs.sync_audit() as audit:
        eng.run(_requests(cfg, slots, sampling=sampling))
    assert audit.syncs == eng.stats.syncs, \
        f"k={k}: audited sync epochs {audit.syncs} != " \
        f"EngineStats.syncs {eng.stats.syncs} (audit: {audit.as_dict()})"
    # ... and so must the hidden/blocking split: exactly the fetches made
    # with a newer block in flight count as hidden (zero on the blocking
    # engine, where every fetch targets its own latest dispatch)
    assert audit.overlap_epochs == eng.stats.hidden_syncs, \
        f"k={k}: audited hidden epochs {audit.overlap_epochs} != " \
        f"EngineStats.hidden_syncs {eng.stats.hidden_syncs}"
    base_steps, base_syncs = eng.stats.steps, eng.stats.syncs
    base_blocked = eng.stats.host_blocked_s
    base_hidden = eng.stats.hidden_syncs
    reqs = _requests(cfg, slots, seed=1, sampling=sampling)
    t0 = time.perf_counter()
    out = eng.run(reqs)
    dt = time.perf_counter() - t0
    steps = eng.stats.steps - base_steps
    syncs = eng.stats.syncs - base_syncs
    toks = sum(len(r.tokens) for r in out)
    seqs = {r.id: list(r.tokens) for r in out}
    blocked = eng.stats.host_blocked_s - base_blocked
    hidden = eng.stats.hidden_syncs - base_hidden
    return dt, steps, syncs, toks, seqs, blocked, hidden


PREFIX_PAGE = 8
PREFIX_SHARED = 32          # 4 full pages of system prompt
PREFIX_NEW = 16


def _prefix_requests(cfg, n, seed=0):
    rng = np.random.RandomState(seed)
    shared = rng.randint(0, cfg.vocab, size=PREFIX_SHARED).tolist()
    return [Request(id=f"p{i}", prompt=shared + [int(rng.randint(cfg.vocab))],
                    max_new_tokens=PREFIX_NEW) for i in range(n)]


def _prefix_drain(cfg, params, slots, k, prefix_cache):
    eng = Engine(params, cfg, num_slots=slots,
                 max_len=PREFIX_SHARED + PREFIX_NEW + 16, k=k,
                 max_prompt=PREFIX_SHARED + 1, page_size=PREFIX_PAGE,
                 prefix_cache=prefix_cache)
    t0 = time.perf_counter()
    out = eng.run(_prefix_requests(cfg, 3 * slots))
    dt = time.perf_counter() - t0
    return dt, eng.stats, {r.id: list(r.tokens) for r in out}


def _prefix_sweep(cfg, params, slots=4, k=4):
    """Shared-system-prompt workload: prefix cache off vs on."""
    dt_off, s_off, seq_off = _prefix_drain(cfg, params, slots, k, False)
    dt_on, s_on, seq_on = _prefix_drain(cfg, params, slots, k, True)
    # token streams must be bit-identical with reuse on
    assert seq_on == seq_off, "prefix cache changed the token streams"
    # the CA-k invariant must survive prefix reuse: k steps per host sync,
    # and skipping prefill must not ADD round trips
    assert s_off.steps == s_off.syncs * k
    assert s_on.steps == s_on.syncs * k, \
        f"prefix cache broke CA-k: steps {s_on.steps} != " \
        f"syncs {s_on.syncs} * {k}"
    assert s_on.syncs <= s_off.syncs, \
        f"prefix cache added syncs ({s_on.syncs} vs {s_off.syncs})"
    # the headline claim: >= 2x fewer prefill tokens with the cache on
    assert 2 * s_on.prefill_tokens <= s_off.prefill_tokens, \
        f"prefix cache saved too little prefill " \
        f"({s_on.prefill_tokens} vs {s_off.prefill_tokens})"
    assert s_on.prefix_hits >= slots, s_on.prefix_hits
    for tag, dt, s in (("off", dt_off, s_off), ("on", dt_on, s_on)):
        resident = s.occupancy * slots
        emit(f"serve-prefix/{cfg.name}/k={k},slots={slots},prefix={tag}",
             dt / s.steps * 1e6,
             f"prefill_tokens={s.prefill_tokens};resident={resident:.2f};"
             f"syncs={s.syncs};prefix_hits={s.prefix_hits};"
             f"prefix_tokens={s.prefix_tokens};cow_copies={s.cow_copies}")


CAP_PAGE = 4
CAP_MAX_LEN = 32


def _capacity_sweep(cfg):
    """Matched-byte resident capacity: f32 vs int8 page pools.

    Host-bookkeeping only (no device arrays): admit residents — allocate a
    slot, reserve the full max_len span — until the pool raises PageError.
    Both pools are sized from the same byte budget (2.5 f32 request-spans:
    enough that page granularity strands the f32 remainder while the
    ~half-cost int8 pages convert it into whole spans); the >= 2x gate is
    the PR's capacity claim asserted in-process."""
    span = CachePool(cfg, 1, CAP_MAX_LEN, page_size=CAP_PAGE)
    span_q = CachePool(cfg, 1, CAP_MAX_LEN, page_size=CAP_PAGE,
                       kv_dtype="int8")
    budget = int(2.5 * span.pages_per_slot) * span.page_bytes()

    def residents(kv_dtype, page_bytes):
        pool = CachePool(cfg, 64, CAP_MAX_LEN, page_size=CAP_PAGE,
                         kv_dtype=kv_dtype,
                         num_pages=1 + budget // page_bytes)
        count = 0
        try:
            while True:
                slot = pool.allocate(f"r{count}")
                pool.reserve(slot, CAP_MAX_LEN)
                count += 1
        except PageError:
            pass
        return count, pool

    n_f32, pool_f = residents("f32", span.page_bytes())
    n_int8, pool_q = residents("int8", span_q.page_bytes())
    assert n_int8 >= 2 * n_f32, \
        f"int8 pool fits {n_int8} residents vs f32 {n_f32} " \
        f"at {budget} matched bytes (need >= 2x)"
    for tag, n, pool, pb in (("f32", n_f32, pool_f, span.page_bytes()),
                             ("int8", n_int8, pool_q, span_q.page_bytes())):
        emit(f"serve-capacity/{cfg.name}/kv={tag}", float(n),
             f"resident_requests={n};pool_bytes={budget};"
             f"page_bytes={pb};num_pages={pool.num_pages}",
             metrics=dict(resident_requests=n, pool_bytes=budget,
                          page_bytes=pb))


FAN_PROMPT = 16
FAN_PAGE = 4
FAN_NEW = 16
FAN_N = 4


def _fanout_sweep(cfg, params, k=4):
    """One n=4 fan-out vs 4 separate admissions with the derived seeds."""
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, cfg.vocab, size=FAN_PROMPT).tolist()
    sp = SamplingParams(temperature=0.8, top_p=0.9, seed=11)

    def drain(reqs):
        eng = Engine(params, cfg, num_slots=FAN_N,
                     max_len=FAN_PROMPT + FAN_NEW + 8, k=k,
                     max_prompt=FAN_PROMPT + 1, page_size=FAN_PAGE)
        t0 = time.perf_counter()
        out = eng.run(reqs)
        return time.perf_counter() - t0, eng.stats, out

    dt_f, s_f, out_f = drain([Request(id="fan", prompt=prompt,
                                      max_new_tokens=FAN_NEW, sampling=sp,
                                      n=FAN_N)])
    dt_s, s_s, out_s = drain([
        Request(id=f"sep{i}", prompt=prompt, max_new_tokens=FAN_NEW,
                sampling=dataclasses.replace(sp, seed=fold_in_seed(11, i)))
        for i in range(FAN_N)])
    # the determinism contract, end to end: stream i of the fan-out IS the
    # standalone request carrying fold_in_seed(base, i), bit for bit
    fan = {r.stream: list(r.tokens) for r in out_f}
    sep = {int(r.id[3:]): list(r.tokens) for r in out_s}
    assert fan == sep, "fan-out streams diverged from separate admissions"
    assert s_f.syncs <= s_s.syncs, \
        f"fan-out added syncs ({s_f.syncs} vs {s_s.syncs})"
    # residency is what fan-out buys: the prompt's whole pages are mapped
    # once and shared, so the page high-water mark drops
    shared = (FAN_N - 1) * (FAN_PROMPT // FAN_PAGE)
    assert s_f.shared_prompt_pages == shared, s_f.shared_prompt_pages
    assert s_f.peak_live_pages + shared <= s_s.peak_live_pages, \
        f"fan-out page high-water {s_f.peak_live_pages} vs " \
        f"separate {s_s.peak_live_pages}"
    for tag, dt, s in (("fanout", dt_f, s_f), ("separate", dt_s, s_s)):
        emit(f"serve-fanout/{cfg.name}/k={k},n={FAN_N},mode={tag}",
             dt / s.steps * 1e6,
             f"syncs={s.syncs};prefill_tokens={s.prefill_tokens};"
             f"peak_live_pages={s.peak_live_pages};"
             f"shared_prompt_pages={s.shared_prompt_pages};"
             f"tokens_out={s.tokens_out}",
             metrics=dict(syncs=s.syncs, prefill_tokens=s.prefill_tokens,
                          peak_live_pages=s.peak_live_pages,
                          shared_prompt_pages=s.shared_prompt_pages))


def _disabled_overhead_guard(us_per_sync: float, iters: int = 20_000):
    """The acceptance gate on zero-overhead-when-disabled: time the full
    per-round instrumentation bundle the engine executes with obs off and
    no profile running (one ``mark_dispatch``, the round's four phase spans,
    the counter/histogram mutations and ``enabled()`` checks) and assert it
    costs < 1% of a real k=1 sync."""
    assert not obs.enabled(), "guard must run with obs disabled"
    c = obs.counter("repro_serve_syncs_total")
    h = obs.histogram("repro_serve_ttft_seconds")
    t0 = time.perf_counter()
    for _ in range(iters):
        with obs.span("serve.admit"):
            pass
        with obs.span("serve.dispatch", k=1, live=4):
            obs.mark_dispatch("serve.dispatch")
        with obs.span("serve.fetch"):
            pass
        with obs.span("serve.complete"):
            pass
        c.inc()
        c.inc(4)
        c.inc()
        c.inc()
        c.inc()
        h.observe(0.01)
        h.observe(0.001)
        obs.enabled()
        obs.enabled()
    bundle_us = (time.perf_counter() - t0) / iters * 1e6
    frac = bundle_us / us_per_sync
    assert frac < 0.01, \
        f"disabled-obs instrumentation costs {bundle_us:.3f} us/round = " \
        f"{frac:.2%} of a {us_per_sync:.0f} us k=1 sync (budget 1%)"
    emit("serve-obs/disabled_overhead", bundle_us,
         f"frac_of_k1_sync={frac:.5f};us_per_sync={us_per_sync:.0f}")


def run():
    cfg = smoke_config(get_arch(ARCH))
    params = init_params(cfg, jax.random.PRNGKey(0))
    us_per_sync_k1 = None
    for slots in (4, 16):
        for k in (1, 4, 16):
            dt, steps, syncs, toks, seqs, blocked, _ = _timed_drain(
                cfg, params, slots, k, None)
            if k == 1 and us_per_sync_k1 is None:
                us_per_sync_k1 = dt / syncs * 1e6
            emit(f"serve/{cfg.name}/k={k},slots={slots}", dt / steps * 1e6,
                 f"tok_per_s={toks / dt:.0f};ms_per_step={dt / steps * 1e3:.3f}")
            sdt, ssteps, ssyncs, stoks, _, _, _ = _timed_drain(
                cfg, params, slots, k, SAMPLED)
            # the CA-k invariant under sampling: one host sync per k steps,
            # zero extra syncs relative to the greedy schedule
            assert ssteps == ssyncs * k, \
                f"k={k}: steps {ssteps} != syncs {ssyncs} * k"
            assert ssyncs == syncs, \
                f"k={k}: sampling changed the sync count " \
                f"({ssyncs} vs greedy {syncs})"
            emit(f"serve/{cfg.name}/k={k},slots={slots},mode=sampled",
                 sdt / ssteps * 1e6,
                 f"tok_per_s={stoks / sdt:.0f};"
                 f"ms_per_step={sdt / ssteps * 1e3:.3f};syncs={ssyncs}")
            # double-buffered loop: identical tokens, hidden vs blocking
            # syncs split out, per-block host-blocked time priced against
            # the blocking engine's
            odt, osteps, osyncs, otoks, oseqs, oblk, ohid = _timed_drain(
                cfg, params, slots, k, None, overlap=True)
            assert oseqs == seqs, \
                f"k={k}: overlapped tokens diverged from blocking"
            assert osteps == osyncs * k, \
                f"k={k}: overlap broke CA-k ({osteps} != {osyncs} * {k})"
            if osyncs > 1:
                assert ohid > 0, f"k={k}: double-buffered drain never " \
                    "overlapped a fetch"
            blocked_us = oblk / osyncs * 1e6
            base_us = blocked / syncs * 1e6
            emit(f"serve/{cfg.name}/k={k},slots={slots},mode=overlap",
                 odt / osteps * 1e6,
                 f"tok_per_s={otoks / odt:.0f};syncs={osyncs};"
                 f"hidden_syncs={ohid};blocking_syncs={osyncs - ohid};"
                 f"host_blocked_us={blocked_us:.0f};"
                 f"host_blocked_us_blocking_engine={base_us:.0f}")
    _prefix_sweep(cfg, params)
    _capacity_sweep(cfg)
    _fanout_sweep(cfg, params)
    _disabled_overhead_guard(us_per_sync_k1)


if __name__ == "__main__":
    run()
