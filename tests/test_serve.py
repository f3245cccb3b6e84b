"""repro.serve: k-step fused decode parity, cache-pool invariants, admission.

The load-bearing claim is token parity: the continuous-batching engine —
per-slot positions through page tables, interleaved prefill, slot reuse,
defrag — must produce exactly the tokens of the classical
one-request-at-a-time per-token loop (greedy argmax, same params, the
model's plain decode over ``init_cache``), for an attention arch and an SSM
arch, at every k. The pool property test drives seeded random
allocate/free/defrag sequences against a real cache and checks no slot is
ever double-assigned and defrag never disturbs live contents or pages.
"""
import functools
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_arch, smoke_config
from repro.dist import DeadlineGate
from repro.kernels import registry
from repro.launch.steps import make_serve_step
from repro.models import init_params, init_cache, decode_step
from repro.models.transformer import prefill_audio_cache
from repro.serve import (Engine, Request, CachePool, SamplingParams,
                         Scheduler, SlotError,
                         FINISH_ERROR, FINISH_LENGTH, FINISH_SHED)

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")

MAX_LEN = 32
PROMPTS = [[7], [3, 11, 5], [9, 2], [4, 4, 4, 8], [13]]
N_NEW = 6


@pytest.fixture(scope="module", params=["internlm2-1.8b", "mamba2-780m"])
def arch_setup(request):
    cfg = smoke_config(get_arch(request.param))
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _plain_cache(cfg, params, enc_embeds=None):
    """A one-request cache of the model's plain decode (``init_cache``);
    whisper's holds the cross K/V of ``enc_embeds``."""
    if cfg.family != "audio":
        return init_cache(cfg, 1, MAX_LEN)
    cache = init_cache(cfg, 1, MAX_LEN, enc_len=len(enc_embeds))
    return jax.jit(lambda p, c, e: prefill_audio_cache(p, cfg, c, e))(
        params, cache, jnp.asarray(enc_embeds)[None].astype(jnp.bfloat16))


@functools.lru_cache(maxsize=None)
def _plain_step(cfg, backend):
    """The model's plain serve step, jitted once per arch and backend."""
    with registry.use(backend):
        return jax.jit(make_serve_step(cfg, None))


def _classic_tokens(cfg, params, prompt, n_new, enc_embeds=None):
    """Reference: whole-prompt then per-token decode, one request, B=1."""
    step = _plain_step(cfg, registry.resolved_backend())
    cache = _plain_cache(cfg, params, enc_embeds)
    tok = None
    for t in prompt:
        tok, _, cache = step(params, cache, jnp.array([[t]], jnp.int32))
    out = [int(tok[0, 0])]
    for _ in range(n_new - 1):
        tok, _, cache = step(params, cache, tok)
        out.append(int(tok[0, 0]))
    return out


# ------------------------------------------------------------------ parity --
def test_vector_positions_match_scalar_ulp(arch_setup):
    """decode_step with per-slot positions through a page table (odd page
    size 5, rows on scattered pages) == the scalar-pos path over slot rows,
    bit for bit (the fused block is built from the paged path; the classic
    loop from the scalar path — ulp-identity here is what makes token
    parity exact). The SSM has no attention: no pages, no table."""
    cfg, params = arch_setup
    B = 3
    pool = CachePool(cfg, B, MAX_LEN, page_size=5)
    for i in range(B):
        pool.allocate(f"r{i}")
    for i in reversed(range(B)):                # rows on interleaved pages
        pool.reserve(i, MAX_LEN)
    table = jnp.asarray(pool.tables) if pool.has_paged else None
    c1, c2 = init_cache(cfg, B, MAX_LEN), pool.make_cache()
    f1 = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t))
    f2 = jax.jit(lambda p, c, t, pos: decode_step(p, cfg, c, t,
                                                  positions=pos,
                                                  page_table=table))
    tok = jnp.array([[5], [7], [9]], jnp.int32)
    for step in range(3):
        l1, c1 = f1(params, c1, tok)
        l2, c2 = f2(params, c2, tok, jnp.full((B,), step, jnp.int32))
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
        tok = jnp.argmax(l1[:, -1], -1).astype(jnp.int32)[:, None]


@pytest.mark.parametrize("sampling", [None, SamplingParams()],
                         ids=["no-params", "default-params"])
@pytest.mark.parametrize("k", [1, 4])
def test_engine_matches_classic_loop(arch_setup, k, sampling):
    """Continuous batching (5 ragged requests over 3 slots of the default
    paged pool: admission waves, slot reuse, defrag) is token-identical to
    the isolated per-token loop —
    both without sampling params and with the default ``SamplingParams()``
    (the greedy fast path must be bit-identical to the pre-sampling argmax
    engine, for an attention arch and an SSM arch)."""
    cfg, params = arch_setup
    want = {f"r{i}": _classic_tokens(cfg, params, p, N_NEW)
            for i, p in enumerate(PROMPTS)}
    eng = Engine(params, cfg, num_slots=3, max_len=MAX_LEN, k=k,
                 max_prompt=8)
    resps = eng.run([Request(id=f"r{i}", prompt=p, max_new_tokens=N_NEW,
                             sampling=sampling)
                     for i, p in enumerate(PROMPTS)])
    assert {r.id: r.tokens for r in resps} == want
    assert all(r.finish_reason == FINISH_LENGTH for r in resps)
    assert eng.stats.retired == len(PROMPTS)
    assert eng.stats.steps == eng.stats.syncs * k
    # every step costs one model eval; tokens emitted + prompt tokens
    # consumed can never exceed the step budget
    assert eng.stats.tokens_out + eng.stats.prefill_tokens <= \
        eng.stats.steps * 3


# ------------------------------------------------------------- cache pool --
CFG_TINY = smoke_config(get_arch("internlm2-1.8b"))


def _mark_slot(pool, cache, slot, value):
    """Stamp a slot's rows with a constant (exact in bf16 for small ints)."""
    def f(leaf, ax):
        if ax < 0:
            return leaf
        idx = (slice(None),) * ax + (slot,)
        return leaf.at[idx].set(jnp.full((), value, leaf.dtype))
    return jax.tree.map(f, cache, pool.batch_axes)


def _slot_values(pool, cache, slot):
    def f(leaf, ax):
        if ax < 0:
            return None
        return np.asarray(jnp.take(leaf, slot, axis=ax))
    return [v for v in jax.tree.leaves(
        jax.tree.map(f, cache, pool.batch_axes, is_leaf=lambda x: x is None))
        if v is not None]


#: slot-row recurrent state beside paged shared attention
CFG_HYBRID = smoke_config(get_arch("zamba2-2.7b"))


@given(st.integers(0, 2 ** 31 - 1))
def test_pool_allocate_free_defrag_invariants(seed):
    """Seeded random op sequences on a pool with both layouts (zamba2's
    slot-row mamba state and paged attention K/V): a slot is never
    double-assigned, frees only release owned slots and their pages, and
    defrag relocates live rows — the per-slot request PRNG key and the page
    table included — losslessly."""
    rng = random.Random(seed)
    pool = CachePool(CFG_HYBRID, 4, 8, page_size=2)
    cache = pool.make_cache()
    owned = {}          # slot -> stamp value
    rng_seeds = {}      # slot -> seed bound via seed_slot
    tables = {}         # slot -> its page table row
    stamp = 0
    for _ in range(20):
        op = rng.random()
        if op < 0.5 and pool.free_count:
            stamp += 1
            slot = pool.allocate(f"req{stamp}")
            assert slot not in owned, "double-assigned slot"
            assert 0 <= slot < pool.num_slots
            cache = _mark_slot(pool, cache, slot, stamp % 100)
            owned[slot] = stamp % 100
            pool.seed_slot(slot, stamp)
            rng_seeds[slot] = stamp
            pool.reserve(slot, 1 + stamp % 8)
            tables[slot] = pool.tables[slot].copy()
        elif op < 0.8 and owned:
            slot = rng.choice(sorted(owned))
            pool.free(slot)
            del owned[slot]
            del rng_seeds[slot]
            del tables[slot]
        elif owned:
            cache, perm, mapping = pool.defrag(cache)
            assert sorted(mapping) == sorted(owned)
            owned = {mapping[s]: v for s, v in owned.items()}
            rng_seeds = {mapping[s]: v for s, v in rng_seeds.items()}
            tables = {mapping[s]: t for s, t in tables.items()}
            # live slots are compacted to the front, in order
            assert pool.live_slots() == list(range(len(owned)))
        assert len(pool.live_slots()) + pool.free_count == pool.num_slots
        # every live page is held by exactly one slot's table
        held = np.concatenate([t[t > 0] for t in tables.values()] + [[]])
        assert pool.live_page_count() == len(held) == len(set(held))
    for slot, value in owned.items():
        for leaf in _slot_values(pool, cache, slot):
            np.testing.assert_array_equal(
                leaf, np.full_like(leaf, value),
                err_msg=f"slot {slot} contents lost")
    for slot, sd in rng_seeds.items():
        np.testing.assert_array_equal(
            pool.slot_keys[slot],
            np.asarray(jax.random.PRNGKey(sd), np.uint32),
            err_msg=f"slot {slot} rng key lost")
    for slot in range(pool.num_slots):
        if slot not in rng_seeds:
            np.testing.assert_array_equal(pool.slot_keys[slot], 0)
            np.testing.assert_array_equal(pool.tables[slot], 0)
        else:
            np.testing.assert_array_equal(pool.tables[slot], tables[slot],
                                          err_msg=f"slot {slot} pages lost")


def test_pool_exhaustion_and_double_free_raise():
    pool = CachePool(CFG_TINY, 2, 8, page_size=4)
    a, b = pool.allocate("a"), pool.allocate("b")
    assert a != b
    with pytest.raises(SlotError):
        pool.allocate("c")
    pool.free(a)
    with pytest.raises(SlotError):
        pool.free(a)


# -------------------------------------------------------------- admission --
def test_scheduler_gate_sheds_expired_under_overload():
    """Overload: requests past the deadline are shed, but never more than
    (1 - quorum) of the queue; fresh requests are admitted FIFO."""
    sch = Scheduler(gate=DeadlineGate(deadline_s=1.0, quorum=0.5),
                    clock=lambda: 0.0)
    waits = {"r0": 8.0, "r1": 7.0, "r2": 6.0, "r3": 5.0, "r4": 0.2,
             "r5": 0.1}
    for rid, w in waits.items():
        sch.submit(Request(id=rid, prompt=[1]), now=10.0 - w)
    admit, shed = sch.schedule(free_slots=2, now=10.0)
    assert [r.id for r in shed] == ["r0", "r1", "r2"]     # oldest expired
    assert [r.id for r in admit] == ["r3", "r4"]          # FIFO among kept
    assert len(sch) == 1                                  # r5 waits


def test_scheduler_fifo_when_not_overloaded():
    """The gate now runs on every non-empty round (not just when the queue
    exceeds free slots), but its quorum floor still guarantees FIFO admission
    when every queued request is equally stale: shedding both would drop
    below quorum, so both are kept."""
    sch = Scheduler(gate=DeadlineGate(deadline_s=0.01, quorum=0.5),
                    clock=lambda: 100.0)
    for i in range(2):
        sch.submit(Request(id=f"r{i}", prompt=[1]), now=0.0)  # long-expired
    admit, shed = sch.schedule(free_slots=4, now=100.0)
    assert [r.id for r in admit] == ["r0", "r1"] and not shed


def test_engine_sheds_via_gate():
    cfg = CFG_TINY
    params = init_params(cfg, jax.random.PRNGKey(0))
    t = [0.0]
    sch = Scheduler(gate=DeadlineGate(deadline_s=1.0, quorum=0.5),
                    clock=lambda: t[0])
    eng = Engine(params, cfg, num_slots=2, max_len=16, k=2, max_prompt=4,
                 scheduler=sch)
    for i in range(4):
        eng.submit(Request(id=f"old{i}", prompt=[i + 1], max_new_tokens=2))
    t[0] = 5.0          # all four are now 4s past the 1s deadline...
    for i in range(4):
        eng.submit(Request(id=f"new{i}", prompt=[i + 1], max_new_tokens=2))
    resps = eng.run()
    by_id = {r.id: r for r in resps}
    assert len(by_id) == 8
    shed = {rid for rid, r in by_id.items() if r.finish_reason == FINISH_SHED}
    # ...but quorum caps shedding at half the 8-deep queue
    assert shed == {"old0", "old1", "old2", "old3"}
    assert all(len(by_id[f"new{i}"].tokens) == 2 for i in range(4))
    assert eng.stats.shed == 4 and eng.stats.retired == 4


# ----------------------------------------------------------------- families --
def test_engine_whisper_encdec():
    """Enc-dec family: per-request cross-K/V prefill into the pool's slot
    rows, decoder self-attention K/V in pages."""
    cfg = smoke_config(get_arch("whisper-medium"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(params, cfg, num_slots=2, max_len=16, k=4, enc_len=16)
    rng = np.random.RandomState(0)
    reqs = [Request(id=f"a{i}", prompt=[3, 4 + i], max_new_tokens=5,
                    enc_embeds=rng.randn(16, cfg.d_model).astype(np.float32))
            for i in range(3)]
    resps = eng.run(reqs)
    assert sorted(len(r.tokens) for r in resps) == [5, 5, 5]
    with pytest.raises(ValueError):
        eng.submit(Request(id="x", prompt=[1]))   # enc-dec needs enc_embeds


def test_engine_rejects_oversized_prompt():
    """An over-long prompt gets an error Response at admission (it could
    never satisfy ``lengths >= prompt_len - 1`` and used to spin in the
    k-block without emitting); valid neighbours are unaffected."""
    params = init_params(CFG_TINY, jax.random.PRNGKey(0))
    eng = Engine(params, CFG_TINY, num_slots=2, max_len=16, k=2,
                 max_prompt=4)
    with pytest.raises(ValueError):
        eng.submit(Request(id="y", prompt=[]))    # malformed: still raises
    resps = eng.run([Request(id="long", prompt=[1] * 5, max_new_tokens=2),
                     Request(id="deep", prompt=[1] * 16, max_new_tokens=2),
                     Request(id="ok", prompt=[1, 2], max_new_tokens=2)])
    by_id = {r.id: r for r in resps}
    assert by_id["long"].finish_reason == FINISH_ERROR
    assert by_id["deep"].finish_reason == FINISH_ERROR   # >= max_len
    assert by_id["long"].tokens == [] and by_id["deep"].tokens == []
    assert by_id["ok"].finish_reason == FINISH_LENGTH
    assert len(by_id["ok"].tokens) == 2
    assert eng.stats.rejected == 2 and eng.pool.live_count == 0


def test_engine_rejects_scheduler_bypass_prompt():
    """Requests pushed straight into the scheduler (bypassing
    ``Engine.submit`` validation) hit the same admission guard."""
    params = init_params(CFG_TINY, jax.random.PRNGKey(0))
    eng = Engine(params, CFG_TINY, num_slots=2, max_len=16, k=2,
                 max_prompt=4)
    eng.scheduler.submit(Request(id="sneak", prompt=[1] * 30))
    resps = eng.run()
    assert [r.finish_reason for r in resps] == [FINISH_ERROR]
    assert eng.stats.rejected == 1 and eng.stats.admitted == 0


def test_decode_block_retires_unservable_prompt():
    """Defense in depth: a prompt_len beyond the prompt buffer or cache that
    somehow reaches the block is marked done at the first sync instead of
    spinning forever without emitting."""
    from repro.serve.decode import init_decode_state, make_decode_block
    params = init_params(CFG_TINY, jax.random.PRNGKey(0))
    block = make_decode_block(CFG_TINY, None, k=2, max_len=8)
    pool = CachePool(CFG_TINY, 2, 8, page_size=4)
    state = init_decode_state(pool.make_cache(), 2)
    prompts = jnp.zeros((2, 4), jnp.int32)
    prompt_len = jnp.asarray([30, 2], jnp.int32)   # slot 0 can never emit
    max_new = jnp.asarray([4, 4], jnp.int32)
    active = jnp.asarray([True, True])
    state, out = block(params, state, prompts, prompt_len, max_new, active,
                       None, jnp.asarray(pool.tables))
    assert bool(state.done[0]) and not np.asarray(out.emitted)[:, 0].any()
    assert not bool(state.done[1])                 # healthy slot unaffected
