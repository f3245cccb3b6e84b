"""Double-buffered (overlap=True) engine: token parity, hidden-sync audit,
stale-slot fencing, dispatch-time deadlines.

The tentpole claim mirrors PR 6's layout invisibility: the overlapped host
loop — dispatch block i+1 before blocking on block i, stale-slot fencing,
pipeline-flushing defrag — must emit exactly the tokens of the blocking
engine for every family, at every k, greedy and sampled. Slot tokens are
k-invariant (PR 5's emission-count PRNG), so one blocking reference per
family/mode anchors the sweep. Sync *counts* are not asserted equal across
the two loops: deferred frees can delay an admission by one round, adding a
(cheap) tail block — only the token streams are contractual.

Engine tests pin ``registry.use("xla")`` for the same reason test_paged does:
exact token equality across engine configurations, not float tolerance
across kernel backends.
"""
import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import get_arch, smoke_config
from repro.dist import DeadlineGate
from repro.kernels import registry
from repro.models import init_params
from repro.serve import (Engine, Request, SamplingParams, Scheduler,
                         FINISH_SHED)

MAX_LEN = 32
PROMPTS = [[7], [3, 11, 5], [9, 2], [4, 4, 4, 8], [13]]
N_NEW = 6
FAMILY_ARCHS = ["internlm2-1.8b", "granite-moe-1b-a400m", "mamba2-780m",
                "zamba2-2.7b", "whisper-medium", "qwen2-vl-2b"]
SAMPLED = SamplingParams(temperature=0.8, top_p=0.9, top_k=8)

#: blocking-engine reference streams, keyed (arch, mode) — k-invariant
_BLOCKING_REFS: dict = {}


@pytest.fixture(scope="module", params=FAMILY_ARCHS)
def family_setup(request):
    cfg = smoke_config(get_arch(request.param))
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _requests(cfg, sampling=None):
    rng = np.random.RandomState(0)
    reqs = []
    for i, p in enumerate(PROMPTS):
        enc = rng.randn(16, cfg.d_model).astype(np.float32) \
            if cfg.family == "audio" else None
        sp = None if sampling is None else \
            SamplingParams(temperature=sampling.temperature,
                           top_p=sampling.top_p, top_k=sampling.top_k,
                           seed=i)
        reqs.append(Request(id=f"r{i}", prompt=p, max_new_tokens=N_NEW,
                            enc_embeds=enc, sampling=sp))
    return reqs


def _drain(cfg, params, *, k, sampling, overlap, page_size=None,
           prefix_cache=False):
    with registry.use("xla"):
        eng = Engine(params, cfg, num_slots=3, max_len=MAX_LEN, k=k,
                     max_prompt=8, enc_len=16 if cfg.family == "audio"
                     else None, overlap=overlap, page_size=page_size,
                     prefix_cache=prefix_cache)
        out = eng.run(_requests(cfg, sampling))
    return {r.id: list(r.tokens) for r in out}, eng


# ------------------------------------------------------------------ parity --
@pytest.mark.parametrize("mode", ["greedy", "sampled"])
@pytest.mark.parametrize("k", [1, 4, 16])
def test_overlap_engine_matches_blocking_engine(family_setup, k, mode):
    """Every family, every k, greedy and sampled: the double-buffered engine
    is token-bit-identical to the blocking engine, and actually overlapped
    (hidden_syncs > 0 whenever more than one block ran)."""
    cfg, params = family_setup
    sampling = None if mode == "greedy" else SAMPLED
    ref_key = (cfg.name, mode)
    if ref_key not in _BLOCKING_REFS:
        _BLOCKING_REFS[ref_key] = _drain(cfg, params, k=4, sampling=sampling,
                                         overlap=False)[0]
    want = _BLOCKING_REFS[ref_key]
    got, eng = _drain(cfg, params, k=k, sampling=sampling, overlap=True)
    assert got == want
    assert eng.stats.steps == eng.stats.syncs * k
    if eng.stats.syncs > 1:
        assert eng.stats.hidden_syncs > 0
    assert eng.stats.blocking_syncs >= 1    # the pipeline tail always stalls
    assert not eng._pipe                    # drained clean


def test_overlap_paged_prefix_parity():
    """Overlap composes with the paged pool + prefix reuse: identical tokens,
    all pages returned, fencing never leaks a page."""
    cfg = smoke_config(get_arch("internlm2-1.8b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(k=4, sampling=None, page_size=5, prefix_cache=True)
    want, _ = _drain(cfg, params, overlap=False, **kw)
    got, eng = _drain(cfg, params, overlap=True, **kw)
    assert got == want
    assert eng.paged
    assert eng.pool.live_page_count() == 0


# ------------------------------------------------------------------- audit --
@pytest.mark.parametrize("mode", ["greedy", "sampled"])
def test_hidden_syncs_audited(mode):
    """sync_audit independently confirms the engine's own overlap
    bookkeeping: one audited epoch per engine sync, and exactly the fetches
    made with a newer block in flight count as hidden (overlap_epochs)."""
    cfg = smoke_config(get_arch("internlm2-1.8b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    sampling = None if mode == "greedy" else SAMPLED
    # warm the jit caches outside the audit (compile-time constant folding
    # must not pollute the counts)
    _drain(cfg, params, k=4, sampling=sampling, overlap=True)
    obs.enable()
    try:
        with obs.sync_audit() as audit:
            _, eng = _drain(cfg, params, k=4, sampling=sampling,
                            overlap=True)
        assert audit.syncs == eng.stats.syncs
        assert audit.dispatches == eng.stats.syncs
        assert audit.overlap_epochs == eng.stats.hidden_syncs
        assert audit.overlap_epochs > 0
        assert audit.blocking_syncs == eng.stats.blocking_syncs
        assert audit.by_span == {"serve.fetch": audit.syncs}

        with obs.sync_audit() as audit:
            _, eng = _drain(cfg, params, k=4, sampling=sampling,
                            overlap=False)
        assert audit.syncs == eng.stats.syncs
        assert audit.overlap_epochs == 0
        assert eng.stats.hidden_syncs == 0
    finally:
        obs.disable()


# ----------------------------------------------------------------- fencing --
def test_fenced_slot_not_reused_until_block_lands():
    """A slot retired while a newer block is in flight stays allocated
    (fenced) until that block completes — admission can never receive a row
    an in-flight block still writes. Staggered max_new forces retirements
    while the queue still holds work."""
    cfg = smoke_config(get_arch("internlm2-1.8b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    reqs = [Request(id=f"f{i}", prompt=[3 + i], max_new_tokens=1 + 3 * i)
            for i in range(6)]
    with registry.use("xla"):
        eng = Engine(params, cfg, num_slots=2, max_len=MAX_LEN, k=2,
                     max_prompt=8, overlap=True)
        for r in reqs:
            eng.submit(r)
        out = []
        for _ in range(200):
            if eng._drained():
                break
            # the fence invariant, checked every round: every slot owned by
            # an in-flight block is still allocated in the pool (its fenced
            # free has not landed), so admission cannot receive the row
            for inf in eng._pipe:
                for slot in inf.slots:
                    assert eng.pool.owner(slot) is not None, \
                        f"slot {slot} freed under an in-flight block"
            out.extend(eng.step())
        assert eng._drained()
    got = {r.id: list(r.tokens) for r in out}
    assert sorted(got) == sorted(r.id for r in reqs)
    for i, r in enumerate(reqs):
        assert len(got[r.id]) == r.max_new_tokens, (r.id, got[r.id])
    # blocking engine agrees token-for-token under the same staggered load
    with registry.use("xla"):
        eng2 = Engine(params, cfg, num_slots=2, max_len=MAX_LEN, k=2,
                      max_prompt=8, overlap=False)
        want = {r.id: list(r.tokens) for r in eng2.run(
            [Request(id=q.id, prompt=list(q.prompt),
                     max_new_tokens=q.max_new_tokens) for q in reqs])}
    assert got == want


def test_overlap_defrag_flushes_pipeline():
    """An aggressive defrag threshold under overlap: defrag still fires (via
    the pipeline flush) and tokens stay identical to the blocking engine."""
    cfg = smoke_config(get_arch("internlm2-1.8b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    # exactly num_slots requests, earliest slots finishing first: no queued
    # work refills the holes, so fragmentation crosses the threshold
    reqs = [Request(id=f"d{i}", prompt=[5, i + 1], max_new_tokens=2 + 4 * i)
            for i in range(4)]
    runs = {}
    for overlap in (False, True):
        with registry.use("xla"):
            eng = Engine(params, cfg, num_slots=4, max_len=MAX_LEN, k=2,
                         max_prompt=8, overlap=overlap,
                         defrag_threshold=0.25)
            out = eng.run([Request(id=r.id, prompt=list(r.prompt),
                                   max_new_tokens=r.max_new_tokens)
                           for r in reqs])
        runs[overlap] = {r.id: list(r.tokens) for r in out}
        if overlap:
            assert eng.stats.defrags > 0     # the flush path actually ran
    assert runs[True] == runs[False]


# --------------------------------------------------------------- deadlines --
class _Clock:
    """Counting clock: every call advances one tick, so any extra clock
    read between rounds is observable as extra queue wait."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def _deadline_run(overlap, deadline):
    cfg = smoke_config(get_arch("internlm2-1.8b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    gate = None if deadline is None else \
        DeadlineGate(deadline_s=deadline, quorum=0.5)
    clock = _Clock()
    with registry.use("xla"):
        eng = Engine(params, cfg, num_slots=1, max_len=MAX_LEN, k=2,
                     max_prompt=8, overlap=overlap,
                     scheduler=Scheduler(gate=gate, clock=clock))
        # 3 requests through 1 slot: the trailing two queue across rounds
        out = eng.run([Request(id=f"q{i}", prompt=[7 + i], max_new_tokens=2)
                       for i in range(3)])
    return {r.id: r for r in out}


def test_deadline_measured_at_dispatch_time():
    """DeadlineGate deadlines are evaluated against block *dispatch* time.
    Derive the worst observed queue wait from an ungated overlapped run,
    set the deadline just above it: correct (entry-clock) behaviour admits
    everything. Completion-time evaluation would add the fetch-side clock
    reads to every wait and shed the tail — the regression this pins."""
    ungated = _deadline_run(True, None)
    worst = max(r.queue_wait_s for r in ungated.values())
    got = _deadline_run(True, worst + 0.5)
    assert all(r.finish_reason != FINISH_SHED for r in got.values()), \
        {k: r.finish_reason for k, r in got.items()}
    # waits are identical to the ungated run: the gate's clock reads did not
    # inflate anyone's measured wait, and overlap added no hidden ticks
    for rid, r in got.items():
        assert r.queue_wait_s == ungated[rid].queue_wait_s
    # and the gate still bites when the budget is genuinely blown
    shed = _deadline_run(True, 0.5)
    assert any(r.finish_reason == FINISH_SHED for r in shed.values())
