"""Paged KV cache + shared-prefix reuse: parity, refcounts, regressions.

The tentpole claim is layout invisibility: the engine — sub-slot page
tables, scratch-page retirement, copy-on-write prefix sharing, page defrag —
must emit exactly the tokens of the model's plain whole-batch decode
(``init_cache`` slot rows, one request at a time) for every family, at
every k, greedy and sampled. Engine tokens are k-invariant (the emission-
count PRNG), so one plain reference per family/mode anchors the sweep.

Engine-level tests pin ``registry.use("xla")``: the plain decode's
attention falls back to XLA (kv_valid_len), while the engine would
otherwise pick the Pallas paged kernel under ``REPRO_BACKEND=pallas`` — the
backends agree only to float tolerance, and these tests assert exact token
equality. The op-level test below covers the pallas/xla agreement explicitly.
"""
import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

from repro.configs import get_arch, smoke_config
from repro.dist import DeadlineGate, cache_specs
from repro.dist.sharding import make_rules
from repro.kernels import registry
from repro.launch.mesh import make_host_mesh
from repro.models import init_params
from repro.models.attention import paged_attention, quantize_kv
from repro.serve import (CachePool, Engine, PageError, PrefixCache, Request,
                         SamplingParams, Scheduler, FINISH_EOS,
                         FINISH_LENGTH)
from repro.serve.paging import _NO_AXIS
from repro.serve.sampling import SlotSampling, sample_tokens
from test_serve import _classic_tokens, _plain_cache, _plain_step

MAX_LEN = 32
PROMPTS = [[7], [3, 11, 5], [9, 2], [4, 4, 4, 8], [13]]
N_NEW = 6
FAMILY_ARCHS = ["internlm2-1.8b", "granite-moe-1b-a400m", "mamba2-780m",
                "zamba2-2.7b", "whisper-medium", "qwen2-vl-2b"]
SAMPLED = SamplingParams(temperature=0.8, top_p=0.9, top_k=8)

CFG_TINY = smoke_config(get_arch("internlm2-1.8b"))

#: plain-decode reference streams, keyed (arch, mode) — engine tokens are
#: k-invariant, so one reference per family/mode anchors the k sweep
_PLAIN_REFS: dict = {}


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


def _drain(cfg, params, *, k, sampling, page_size=16, prefix_cache=False):
    with registry.use("xla"):
        eng = Engine(params, cfg, num_slots=3, max_len=MAX_LEN, k=k,
                     max_prompt=8, enc_len=16 if cfg.family == "audio"
                     else None, page_size=page_size,
                     prefix_cache=prefix_cache)
        out = eng.run(_requests(cfg, sampling))
    return {r.id: list(r.tokens) for r in out}, eng


@jax.jit
def _draw(logits, tok, samp, n_out):
    return sample_tokens(logits[:, -1], tok[:, 0], samp, n_out)[:, None]


def _sampled_tokens(cfg, params, req):
    """Reference for a sampled request: the model's plain decode of it
    alone (``make_serve_step`` over a one-row ``init_cache``), each token
    drawn by ``sample_tokens`` under the request's key and its emission
    count."""
    sp = req.sampling
    samp = SlotSampling(
        temperature=jnp.asarray([sp.temperature], jnp.float32),
        top_p=jnp.asarray([sp.top_p], jnp.float32),
        top_k=jnp.asarray([sp.top_k], jnp.int32),
        key=jnp.asarray([[sp.seed >> 32, sp.seed & 0xFFFFFFFF]], jnp.uint32))
    step = _plain_step(cfg, registry.resolved_backend())
    cache = _plain_cache(cfg, params, req.enc_embeds)
    for t in req.prompt:
        tok, logits, cache = step(params, cache, jnp.array([[t]], jnp.int32))
    out = []
    for i in range(req.max_new_tokens):
        if i:
            tok, logits, cache = step(params, cache, tok)
        tok = _draw(logits, tok, samp, jnp.asarray([i], jnp.int32))
        out.append(int(tok[0, 0]))
    return out


def _plain_tokens(cfg, params, sampling):
    with registry.use("xla"):
        if sampling is None:
            return {r.id: _classic_tokens(cfg, params, r.prompt,
                                          r.max_new_tokens, r.enc_embeds)
                    for r in _requests(cfg)}
        return {r.id: _sampled_tokens(cfg, params, r)
                for r in _requests(cfg, sampling)}


# ------------------------------------------------------------------ parity --
@pytest.mark.parametrize("mode", ["greedy", "sampled"])
@pytest.mark.parametrize("k", [1, 4, 16])
def test_engine_matches_plain_decode(family_setup, k, mode):
    """Every family, every k, greedy and sampled: the engine is
    token-identical to the model's plain decode of each request alone.
    Odd page size 5 vs MAX_LEN 32 forces a ragged final page per slot."""
    cfg, params = family_setup
    sampling = None if mode == "greedy" else SAMPLED
    ref_key = (cfg.name, mode)
    if ref_key not in _PLAIN_REFS:
        _PLAIN_REFS[ref_key] = _plain_tokens(cfg, params, sampling)
    want = _PLAIN_REFS[ref_key]
    got, eng = _drain(cfg, params, k=k, sampling=sampling, page_size=5)
    assert got == want
    if cfg.family == "ssm":
        # pure-SSM has no attention K/V: a pool without page leaves or
        # tables, not a degenerate page world
        assert not eng.pool.has_paged and eng.pool.pages_per_slot == 0
        assert not any(ax != _NO_AXIS
                       for ax in jax.tree.leaves(eng.pool.page_axes))
    else:
        assert eng.pool.live_page_count() == 0      # all pages returned
        assert eng.pool.free_page_count == eng.pool.num_pages - 1


def test_prefix_cache_streams_bit_identical(family_setup):
    """Prefix reuse on vs off: identical tokens, strictly less prefill for
    the families that support reuse; recurrent/enc-dec families must decline
    the flag rather than corrupt state."""
    cfg, params = family_setup
    rng = np.random.RandomState(1)
    shared = rng.randint(0, cfg.vocab, size=6).tolist()
    reqs = []
    for i in range(6):
        enc = rng.randn(16, cfg.d_model).astype(np.float32) \
            if cfg.family == "audio" else None
        reqs.append(Request(id=f"p{i}", prompt=shared + [i + 1],
                            max_new_tokens=4, enc_embeds=enc))
    runs = {}
    for on in (False, True):
        with registry.use("xla"):
            eng = Engine(params, cfg, num_slots=2, max_len=MAX_LEN, k=2,
                         max_prompt=8, page_size=4, prefix_cache=on,
                         enc_len=16 if cfg.family == "audio" else None)
            out = eng.run(list(reqs))
        runs[on] = ({r.id: list(r.tokens) for r in out}, eng.stats)
    assert runs[True][0] == runs[False][0]
    s_off, s_on = runs[False][1], runs[True][1]
    if cfg.family in ("dense", "vlm", "moe"):
        # 6 shared tokens, page_size 4: the first wave (2 slots) publishes
        # the shared page, the later 4 admissions reuse it
        assert s_on.prefix_hits >= 4
        assert s_on.prefix_tokens >= 4 * 4
        assert s_on.prefill_tokens < s_off.prefill_tokens
    else:
        assert s_on.prefix_hits == 0 and s_on.prefix_tokens == 0


@pytest.mark.parametrize("overlap", [False, True], ids=["blocking",
                                                      "overlap"])
def test_donated_pool_survives_the_engines_moves(overlap):
    """The decode block takes the state donated, so the paged pool lives in
    one buffer from block to block. Between blocks the engine admits, takes
    a prefix hit with a copy-on-write page, compacts its slots and its
    pages, and (overlapped) fences retired slots and fetches a block after
    the next one took the state: no buffer is used after its donation,
    every block carries the pool without a copy, and the streams equal
    those of an engine on 16-token pages without prefix reuse."""
    params = init_params(CFG_TINY, jax.random.PRNGKey(0))
    shared = [11, 12, 13, 14, 15, 16, 17, 18]
    # slots 0 and 2 retire first, leaving holes; the last two requests
    # arrive once the first wave has published the two shared pages: one
    # matches both, one diverges inside the second (copy on write)
    prompts = [shared + [i + 1] for i in range(5)] + [shared[:6] + [99, 98]]
    reqs = lambda: [Request(id=f"m{i}", prompt=p,
                            max_new_tokens=2 if i % 2 == 0 else 10)
                    for i, p in enumerate(prompts)]
    with registry.use("xla"):
        want = Engine(params, CFG_TINY, num_slots=4, max_len=MAX_LEN, k=2,
                      max_prompt=12, page_size=16).run(reqs())
        eng = Engine(params, CFG_TINY, num_slots=4, max_len=MAX_LEN, k=2,
                     max_prompt=12, page_size=4, prefix_cache=True,
                     defrag_threshold=0.25, overlap=overlap)
        for r in reqs():
            eng.submit(r)
        first_pool = eng.state.cache["layers"]["k"]
        out = eng.step()
        assert first_pool.is_deleted()           # donated to the block
        while not eng._drained():
            out += eng.step()
    assert {r.id: r.tokens for r in out} == {r.id: r.tokens for r in want}
    st = eng.stats
    assert st.admitted == 6 and st.prefix_hits >= 1 and st.cow_copies >= 1
    assert st.defrags >= 1 and st.page_defrags >= 1
    assert st.kv_in_place_blocks == st.syncs > 0


#: op-level cases of the paged decode kernel against the XLA gather+mask
#: impl: (B, Hq, Hkv, D, page_size, pages a row, valid lengths), the valid
#: lengths given from the page size ``P``, the case's kernel block ``bt``
#: (tokens) and the horizon ``full``, so boundaries stay boundaries
PAGED_CASES = {
    # odd page size, GQA 3, a row whose tail entries are the scratch page
    "odd_page_scratch_tail": (2, 6, 2, 16, 5, 3, lambda P, bt, full: [13, 4]),
    # valid 1, a page boundary, a block boundary and one past it, the
    # full horizon; 22 pages a row is no multiple of the block (4 pages
    # in f32, 16 in int8)
    "ragged_valid": (5, 16, 8, 128, 16, 22,
                     lambda P, bt, full: [1, P, bt, bt + 1, full]),
    "gqa1": (3, 4, 4, 64, 32, 18, lambda P, bt, full: [bt, bt + 1, 97]),
    "gqa2": (3, 8, 4, 32, 16, 36, lambda P, bt, full: [full, bt - 1, 5]),
    "gqa6": (3, 12, 2, 128, 16, 40, lambda P, bt, full: [1, 300, full]),
    "batch1": (1, 8, 2, 128, 16, 40, lambda P, bt, full: [bt + 1]),
}


#: pools stacked over layers: (shape as in ``PAGED_CASES``, pool dtype,
#: layers, the layer read). bf16 and int8 at the cell's 8 kv heads of 128,
#: and 32 kv heads on 64-token pages, where one page of K+V outgrows a
#: kernel block and the kernel splits the heads over its grid: of 128
#: channels, read in place, and of zamba2's 80, padded
STACKED_CASES = {
    "stacked_bf16_layer2": (PAGED_CASES["ragged_valid"], jnp.bfloat16, 3, 2),
    "stacked_int8_layer1": (PAGED_CASES["ragged_valid"], jnp.int8, 3, 1),
    "stacked_head_blocks_layer1": (
        (2, 32, 32, 128, 64, 3, lambda P, bt, full: [full, P + 3]),
        jnp.bfloat16, 2, 1),
    "stacked_zamba2_layer1": (
        (2, 32, 32, 80, 64, 3, lambda P, bt, full: [P - 1, full]),
        jnp.bfloat16, 2, 1),
}


def paged_case(name, dtype=jnp.float32, layers=None):
    """Inputs of ``PAGED_CASES[name]`` (or a tuple of its form): random
    pools, each row's live pages distinct, and the table's tail past
    ``valid`` pointing at the scratch page 0 (odd rows) or at stale pages
    of other rows (even rows). ``layers``: pools stacked over that many
    layers, each layer's values its own."""
    from repro.kernels.flash_attention.ops import block_tokens
    B, Hq, Hkv, D, P, npg, valid = PAGED_CASES.get(name, name)
    valid = valid(P, block_tokens(P, Hkv, D, dtype, npg), npg * P)
    assert max(valid) <= npg * P
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    lead = () if layers is None else (layers,)
    q = jax.random.normal(ks[0], (B, 1, Hq, D), jnp.float32)
    k_pool = jax.random.normal(ks[1], lead + (1 + B * npg, P, Hkv, D),
                               jnp.float32)
    v_pool = jax.random.normal(ks[2], lead + (1 + B * npg, P, Hkv, D),
                               jnp.float32)
    pages = 1 + np.random.default_rng(0).permutation(B * npg)
    table = pages.reshape(B, npg)
    live = np.arange(npg)[None] * P < np.asarray(valid)[:, None]
    tail = np.where(np.arange(B)[:, None] % 2, 0, table[::-1])
    table = np.where(live, table, tail).astype(np.int32)
    return q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(valid,
                                                              jnp.int32)


@pytest.mark.parametrize("case", list(PAGED_CASES) + list(STACKED_CASES))
def test_paged_attention_pallas_matches_xla(case):
    """Op level: the paged kernel, which walks each row's live blocks of
    whole pages, agrees with the XLA gather+mask reference at ragged valid
    lengths on page and block boundaries, a horizon its block does not
    divide, GQA groups 1 to 6, one row, and table entries past ``valid``
    pointing at the scratch page or at other rows' pages. Stacked pools:
    both impls read layer ``layer`` of the stack exactly as they read that
    layer's pool alone, in bf16, int8 (with scales) and head blocks."""
    if case in PAGED_CASES:
        q, k_pool, v_pool, table, valid = paged_case(case)
        k_pool, v_pool, layer, scales = k_pool[None], v_pool[None], 0, {}
    else:
        shape, dtype, layers, layer = STACKED_CASES[case]
        q, k_pool, v_pool, table, valid = paged_case(shape, dtype, layers)
        scales = {}
        if dtype == jnp.int8:
            k_pool, ks = quantize_kv(k_pool)
            v_pool, vs = quantize_kv(v_pool)
            scales = dict(k_scale=ks, v_scale=vs)
        else:
            k_pool, v_pool = k_pool.astype(dtype), v_pool.astype(dtype)
        with registry.use("xla"):
            alone = paged_attention(
                q, k_pool[layer][None], v_pool[layer][None], table, valid,
                0, **{n: s[layer][None] for n, s in scales.items()})
    with registry.use("xla"):
        ref = paged_attention(q, k_pool, v_pool, table, valid, layer,
                              **scales)
    with registry.use("pallas"):
        got = paged_attention(q, k_pool, v_pool, table, valid, layer,
                              **scales)
    # over bf16 pools the xla impl rounds the softmax weights to bf16 for
    # p.v (2**-9 relative), the kernel keeps them f32
    tol = 5e-3 if k_pool.dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=tol, atol=tol)
    if case in STACKED_CASES:
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(alone))


# ------------------------------------------------------- pool bookkeeping --
def test_prefix_trie_match_insert_evict():
    trie = PrefixCache(page_size=4)
    c1, c2 = (1, 2, 3, 4), (5, 6, 7, 8)
    assert trie.insert_path([c1, c2], [7, 9]) == [7, 9]
    assert trie.insert_path([c1, c2], [7, 9]) == []          # idempotent
    full, partial = trie.match([1, 2, 3, 4, 5, 6, 99])
    assert full == [7]
    assert partial == (9, 2)          # LCP of (5,6,99) against chunk c2
    # leaves evict first: dropping 9 leaves 7 as the new leaf
    assert trie.evict_lru() == 9
    assert trie.evict_lru() == 7
    assert trie.evict_lru() is None


def test_prefix_match_touches_only_the_winning_partial():
    """LRU hygiene: the CoW-candidate scan must not refresh losing
    branches. Three leaves inserted cold-to-hot (C, W, H); a probe whose
    divergent chunk best-matches W used to touch C on the way past, making
    H — the genuinely hottest leaf — the eviction victim."""
    trie = PrefixCache(page_size=4)
    trie.insert_path([(1, 2, 3, 4)], [7])       # C: oldest
    trie.insert_path([(1, 2, 8, 8)], [9])       # W: the winning partial
    trie.insert_path([(5, 6, 7, 8)], [8])       # H: most recent
    full, partial = trie.match([1, 2, 8, 9])
    assert full == [] and partial == (9, 3)     # W wins with lcp 3
    # only W was refreshed: C is still the LRU leaf, H stays hot
    assert trie.evict_lru() == 7


def test_exhaustion_with_slot_held_pages_fails_fast_keeping_trie():
    """Eviction-spiral regression: when every trie page is also slot-held,
    eviction can free nothing — reserve() must raise PageError *without*
    wiping the trie (the old loop destroyed every node on its way to the
    same error, forfeiting all future prefix reuse)."""
    pool = CachePool(CFG_TINY, 2, 8, page_size=4, num_pages=3)
    a = pool.allocate("a")
    pool.reserve(a, 8)
    pool.register_prefix(a, [1, 2, 3, 4, 5, 6, 7, 8], written_len=8)
    assert pool.prefix.n_nodes == 2 and pool.free_page_count == 0
    b = pool.allocate("b")          # a still holds its pages (refcount 2)
    with pytest.raises(PageError):
        pool.reserve(b, 4)
    assert pool.prefix.n_nodes == 2             # trie intact
    # with a retired, the same reserve succeeds via genuine LRU eviction
    pool.free(a)
    pool.reserve(b, 8)
    assert pool.prefix.n_nodes == 0


def test_paged_pool_refcounts_across_retire_and_defrag():
    """Pages stay alive while any slot table or trie node references them;
    retire drops the slot's reference but keeps published pages resident;
    page defrag permutes pool rows without disturbing what tables see."""
    pool = CachePool(CFG_TINY, 3, 16, page_size=4)
    cache = pool.make_cache()
    prompt = [1, 2, 3, 4, 5, 6, 7, 8]

    a = pool.allocate("a")
    pool.reserve(a, 9)                       # 3 pages
    assert int(pool._n_pages[a]) == 3
    assert pool.register_prefix(a, prompt, written_len=8) == 2
    shared = [int(pool.tables[a, i]) for i in range(2)]
    assert all(pool._ref[p] == 2 for p in shared)    # slot + trie
    pool.free(a)
    assert all(pool._ref[p] == 1 for p in shared)    # trie keeps them
    assert np.all(pool.tables[a] == 0)               # retired rows -> scratch

    # whole-prefix hit: the new slot maps the published pages read-only
    b = pool.allocate("b")
    m, cow = pool.map_prefix(b, prompt + [99])
    assert (m, cow) == (8, None)
    assert [int(pool.tables[b, i]) for i in range(2)] == shared
    assert all(pool._ref[p] == 2 for p in shared)

    # divergence inside the second chunk: CoW into a private page
    c = pool.allocate("c")
    m, cow = pool.map_prefix(c, prompt[:6] + [55, 66, 77])
    assert m == 6 and cow is not None
    src, dst = cow
    assert src == shared[1] and dst not in shared
    assert pool._ref[dst] == 1 and pool._ref[src] == 2

    # free b and evict the deeper trie leaf: its page becomes a hole below
    # c's still-live pages, so defrag has something to compact
    pool.free(b)
    pg = pool.prefix.evict_lru()
    assert pg == shared[1]
    pool._decref(pg)
    assert pool.page_fragmentation() > 0.0

    # stamp every page row with its own pool index, then defrag: c's table
    # must still gather exactly the rows it saw before the permutation
    def stamp(leaf, pax):
        if pax == _NO_AXIS:
            return leaf
        n = leaf.shape[pax - 1]
        shp = [1] * leaf.ndim
        shp[pax - 1] = n
        return jnp.broadcast_to(
            jnp.arange(n, dtype=leaf.dtype).reshape(shp), leaf.shape)
    cache = jax.tree.map(stamp, cache, pool.page_axes)
    before = pool.tables[c].copy()
    cache = pool.defrag_pages(cache)
    assert pool.page_fragmentation() == 0.0
    leaves = [(leaf, pax) for leaf, pax in zip(
        jax.tree.leaves(cache), jax.tree.leaves(pool.page_axes))
        if pax != _NO_AXIS]
    assert leaves
    for leaf, pax in leaves:
        got = np.asarray(jnp.moveaxis(leaf, pax - 1, 0)).reshape(
            leaf.shape[pax - 1], -1)[:, 0]
        np.testing.assert_array_equal(got[pool.tables[c]], before)

    pool.free(c)
    assert pool.live_page_count() == 1       # only the trie's root page


def test_page_pool_exhaustion_evicts_then_raises():
    """When the free heap runs dry, reserve() reclaims trie-only pages via
    LRU eviction; with nothing left to evict it raises PageError."""
    pool = CachePool(CFG_TINY, 2, 8, page_size=4, num_pages=3)
    a = pool.allocate("a")
    pool.reserve(a, 8)                       # both real pages
    pool.register_prefix(a, [1, 2, 3, 4, 5, 6, 7, 8], written_len=8)
    pool.free(a)
    assert pool.free_page_count == 0         # trie holds both
    b = pool.allocate("b")
    pool.reserve(b, 8)                       # evicts both trie leaves
    assert pool.prefix.n_nodes == 0
    c = pool.allocate("c")
    with pytest.raises(PageError):
        pool.reserve(c, 4)


def test_paged_cache_specs_shard_pages():
    """The documented sharding story: a paged pool's K/V leaves shard
    pages@dp and page rows@tp exactly where the slot layout sharded
    batch@dp and seq@tp."""
    rules = make_rules(make_host_mesh())        # (data=2, model=4) spoofed
    pool = CachePool(CFG_TINY, 2, 16, page_size=4, num_pages=16)
    specs = cache_specs(pool.make_cache(), rules)
    kv = [(jtu.keystr(path), spec)
          for path, spec in jtu.tree_leaves_with_path(specs)
          if "'k'" in jtu.keystr(path) or "'v'" in jtu.keystr(path)]
    assert kv
    for name, spec in kv:
        nd = len(spec)
        assert spec[nd - 4] == "data" and spec[nd - 3] == "model", \
            f"{name}: {spec}"


# ------------------------------------------------------------- regressions --
def test_run_drains_in_exactly_max_syncs():
    """A workload that finishes on the final allowed sync is a success, not
    a timeout (the drain check used to run only before each step, so the
    last round's completions were thrown away as a RuntimeError)."""
    params = init_params(CFG_TINY, jax.random.PRNGKey(0))
    eng = Engine(params, CFG_TINY, num_slots=1, max_len=16, k=2,
                 max_prompt=4)
    out = eng.run([Request(id="x", prompt=[1], max_new_tokens=4)],
                  max_syncs=2)
    assert len(out) == 1 and len(out[0].tokens) == 4
    assert eng.stats.syncs == 2


def test_finish_reason_from_device_done_branch():
    """finish_reason derives from which device-side branch retired the slot:
    a budget-exhausted slot whose final draw happens to equal eos_id is a
    length finish, not an eos finish."""
    params = init_params(CFG_TINY, jax.random.PRNGKey(0))

    def run(eos_id, max_new):
        eng = Engine(params, CFG_TINY, num_slots=1, max_len=16, k=2,
                     max_prompt=4, eos_id=eos_id)
        return eng.run([Request(id="x", prompt=[7],
                                max_new_tokens=max_new)])[0]

    t = run(None, 6).tokens                  # greedy reference stream
    r = run(int(t[0]), 1)                    # budget and eos fire together
    assert r.tokens == [t[0]]
    assert r.finish_reason == FINISH_LENGTH
    r = run(int(t[0]), 6)                    # eos fires with budget to spare
    assert r.tokens == [t[0]]
    assert r.finish_reason == FINISH_EOS


def test_scheduler_sheds_expired_under_light_load():
    """The deadline gate runs even when the queue fits the free slots: an
    expired request is shed instead of riding in on spare capacity (it used
    to be admitted whenever queue <= free_slots)."""
    sch = Scheduler(gate=DeadlineGate(deadline_s=1.0, quorum=0.5),
                    clock=lambda: 10.0)
    sch.submit(Request(id="stale", prompt=[1]), now=5.0)     # 5s past
    sch.submit(Request(id="fresh", prompt=[1]), now=9.9)
    admit, shed = sch.schedule(free_slots=4, now=10.0)
    assert [r.id for r in admit] == ["fresh"]
    assert [r.id for r in shed] == ["stale"]


def test_cachepool_free_heap_keeps_lowest_slot_first():
    """The free list is a heap: allocation after interleaved frees always
    takes the lowest slot index, in O(log n) per op."""
    pool = CachePool(CFG_TINY, 8, 8, page_size=4)
    slots = [pool.allocate(f"r{i}") for i in range(8)]
    assert slots == list(range(8))
    order = [6, 1, 4, 3]
    for s in order:
        pool.free(s)
    assert [pool.allocate(f"q{i}") for i in range(4)] == sorted(order)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_paged_decode_logits_match_full_forward(backend):
    """The serve CLI's logit check: cached decode through a fresh page pool
    (odd page size 5, so the last page is ragged) against one full forward
    over the same tokens, under each backend of the paged attention op."""
    from repro.launch.serve import LOGIT_RTOL, check_paged_logits
    params = init_params(CFG_TINY, jax.random.PRNGKey(0))
    with registry.use(backend):
        err = check_paged_logits(params, CFG_TINY, page_size=5, n_tokens=12)
    assert 0.0 <= err <= LOGIT_RTOL
