"""The expert layer that holds a share of the experts, on the CPU at a small
deepseek-shaped size (1 dense and 2 MoE layers, 16 routed experts of which
a chip holds 4, top-3, 2 shared experts):

- the serving path (paged pool, k = 4, prefill then cached decode) against
  the plain float32 reference ``perfbench/references/moe_lm.py``, at the
  logit level, with and without renormalised gates;
- the share test: the four shares' routed parts, plus the shared experts
  once, sum to the uncut layer;
- the ``held_experts_ffn`` kernel (interpret mode) against its ``xla``
  impl, experts with no token and groups over one tile included;
- the engine's expert counters.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # perfbench/

from perfbench.drivers.serve_moe import arch_config  # noqa: E402
from perfbench.references import moe_lm  # noqa: E402
from repro import obs  # noqa: E402
from repro.configs import get_arch, smoke_config  # noqa: E402
from repro.kernels import registry  # noqa: E402
from repro.launch.steps import make_serve_step  # noqa: E402
from repro.models import init_cache, init_params  # noqa: E402
from repro.models.moe import held_experts, route  # noqa: E402
from repro.models.mlp import swiglu  # noqa: E402
from repro.serve import CachePool, Engine, Request  # noqa: E402

SEED = 3_150_000_123


def small_cfg(norm_topk_prob: bool, first_expert: int = 4,
              n_held: int = 4) -> dict:
    """deepseek-moe-16b's layout at a CPU size, in the benchmark's keys."""
    return dict(
        name="dsmoe-small", hidden_size=128, intermediate_size=256,
        moe_intermediate_size=64, num_hidden_layers=3,
        first_k_dense_replace=1, num_attention_heads=4,
        num_key_value_heads=4, n_shared_experts=2, n_routed_experts=n_held,
        n_routed_experts_published=16, first_routed_expert=first_expert,
        num_experts_per_tok=3, norm_topk_prob=norm_topk_prob,
        vocab_size=512, rms_norm_eps=1e-6, rope_theta=10000,
        tie_word_embeddings=False)


@pytest.mark.parametrize("norm", [False, True], ids=["published", "renorm"])
def test_paged_decode_matches_the_reference_forward(norm):
    """Prefill and cached decode through the serving step on a paged pool
    give the reference's full-forward logits; the engine's greedy tokens
    are the reference's best.

    Tolerance: the program computes in bfloat16 from bfloat16 weights, the
    reference in float32 from the same weights. Over 3 layers the logits
    (std about 1) move by 0.013 to 0.015 on average (seeded; the largest
    single moves, 0.2 to 0.5, are routing choices that rounding flips), so
    the mean absolute gap must stay under 0.022. The reference with int8
    operands, the next precision down, misses by 0.036 to 0.041 and fails
    it."""
    cfg = small_cfg(norm)
    arch = arch_config(cfg)
    params = moe_lm.make_params(SEED, cfg)
    shape = moe_lm.shape_key(cfg)
    rng = np.random.default_rng(7)
    toks = rng.integers(0, cfg["vocab_size"], 48).astype(np.int32)
    want = np.asarray(moe_lm.logits(params, jnp.asarray(toks), shape))

    pool = CachePool(arch, 2, 64, page_size=8)
    slot = pool.allocate("r")
    pool.reserve(slot, len(toks))
    table = jnp.asarray(pool.tables)
    step = jax.jit(make_serve_step(arch, None))
    cache = pool.make_cache()
    got = []
    for p, t in enumerate(toks):
        _, logits, cache = step(params, cache,
                                jnp.asarray([[t], [0]], jnp.int32),
                                jnp.asarray([p, 0], jnp.int32), table)
        got.append(np.asarray(logits[0, 0], np.float32))
    err = np.abs(np.stack(got) - want).mean()
    assert err < 0.022, err
    q8 = moe_lm.logits(params, jnp.asarray(toks), shape, "int8")
    assert np.abs(np.asarray(q8) - want).mean() > 0.022

    # the engine (k = 4, prefill inside the blocks): each served token is
    # the reference's best or within bf16 reach of it
    eng = Engine(params, arch, num_slots=4, max_len=64, k=4, page_size=8)
    reqs = [Request(id=str(i), prompt=rng.integers(
        0, cfg["vocab_size"], 6 + 3 * i).tolist(), max_new_tokens=12)
        for i in range(5)]
    gaps = []
    for r in eng.run(reqs):
        seq = list(next(q.prompt for q in reqs if q.id == r.id)) + r.tokens
        ref = np.asarray(moe_lm.logits(params, jnp.asarray(seq, jnp.int32),
                                       shape))
        lo = r.prompt_len - 1
        best = ref[lo:-1].max(-1)
        gaps += list(best - ref[np.arange(lo, len(seq) - 1), seq[lo + 1:]])
    gaps = np.asarray(gaps)
    assert len(gaps) == 60 and gaps.mean() < 5e-3 and gaps.max() < 0.2, \
        gaps


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("norm", [False, True], ids=["published", "renorm"])
def test_shares_sum_to_the_uncut_layer(norm, backend):
    """Four chips each holding 4 of the 16 routed experts: their routed
    parts, with the shared experts (which every chip computes alike)
    counted once, add up to what the uncut reference gives for the whole
    layer. float32 weights, so the sum is exact to float32 rounding."""
    full = moe_lm.dims(small_cfg(norm, first_expert=0, n_held=16))
    params = jax.tree.map(lambda a: a[0].astype(jnp.float32),
                          moe_lm.make_params(SEED, small_cfg(
                              norm, first_expert=0, n_held=16))
                          ["layers"]["moe"])
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 128), jnp.float32)
    want = jax.vmap(lambda x: moe_lm._experts(x, params, full, None))(h)

    gates, sel, _ = route(params["router"], h, top_k=3, norm_topk_prob=norm)
    total = swiglu(params["shared"], h)
    with registry.use(backend):
        for s in range(4):
            share = {k: params[k][4 * s:4 * s + 4]
                     for k in ("w_gate", "w_up", "w_down")}
            total = total + held_experts(share, h, gates, sel,
                                         first_expert=4 * s)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # a single share leaves the others' part out: it is not the whole
    one = held_experts({k: params[k][:4] for k in ("w_gate", "w_up",
                                                  "w_down")},
                       h, gates, sel) + swiglu(params["shared"], h)
    assert float(jnp.abs(one - want).max()) > 1e-2


def _kernel_case(case, dtype):
    """(x, sel, gates, w_gate, w_up, w_down, layer, first_expert): 8 held
    experts of 32 at d = 128, F = 256, top-4, the second of two layers'
    stacked weights."""
    T, first = {"sparse": (3, 8), "many": (40, 0), "crowded": (20, 4)}[case]
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    x = jax.random.normal(ks[0], (T, 128), dtype)
    gates, sel = jax.lax.top_k(jax.nn.softmax(
        jax.random.normal(ks[1], (T, 32)), -1), 4)
    sel = sel.astype(jnp.int32)
    if case == "crowded":
        # every token chooses held expert 5: a group of 20 rows, two tiles
        sel = sel.at[:, 0].set(jnp.where((sel[:, 1:] == 5).any(1),
                                         sel[:, 0], 5))
    w = lambda k, s, fan: (jax.random.normal(k, (2, *s)) * fan ** -.5
                           ).astype(dtype)
    return (x, sel, gates, w(ks[2], (8, 128, 256), 128),
            w(ks[3], (8, 128, 256), 128), w(ks[4], (8, 256, 128), 256),
            jnp.int32(1), first)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", ["sparse", "many", "crowded"])
def test_held_experts_kernel_matches_xla(case, dtype):
    """The Pallas kernel (interpret mode) against the xla impl. "sparse":
    3 tokens, so at least 5 of the 8 held experts get none and are never
    read; "many": 40 tokens; "crowded": one expert takes all 20 tokens, a
    group over two row tiles. The weights are two layers' stacked, the
    second used in place. Tolerance: both accumulate in float32 over the
    same bf16 or f32 products, in another order."""
    from repro.kernels.moe import ops
    x, sel, gates, wg, wu, wd, layer, first = _kernel_case(case, dtype)
    local = np.asarray(sel) - first
    counts = [(local == e).sum() for e in range(8)]
    if case == "sparse":
        assert sum(c == 0 for c in counts) >= 5
    if case == "crowded":
        assert max(counts) == 20
    args = (x, sel, gates, wg, wu, wd, layer)
    with registry.use("pallas"):
        assert registry.select("held_experts_ffn", *args).backend == "pallas"
        got = registry.dispatch("held_experts_ffn", *args,
                                first_expert=first)
    with registry.use("xla"):
        want = registry.dispatch("held_experts_ffn", *args,
                                 first_expert=first)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4 if dtype == jnp.float32 else 2e-3,
                               rtol=1e-4)
    # the layout visits the hit experts only, each group in whole tiles
    order, tiles, start, _ = ops.layout(sel, first_expert=first, n_held=8)
    hit = [e for e in range(8) if counts[e]]
    assert list(np.asarray(order)[:len(hit)]) == hit
    assert list(np.asarray(tiles)) == [-(-counts[e] // 16) for e in hit] \
        + [0] * (8 - len(hit))


@pytest.mark.parametrize("arch", ["granite-moe-1b-a400m", "deepseek-moe-16b"])
def test_engine_counts_the_held_experts_work(arch):
    """``moe_assignments`` / ``moe_experts_hit`` come back with each block's
    tokens. Holding every expert (granite's smoke config), each step
    computes every row's top-k pairs in every layer; at a share of 2 of 4
    experts (deepseek's), at most that, and each hit expert has a pair."""
    cfg = smoke_config(get_arch(arch))
    if arch == "deepseek-moe-16b":
        cfg = cfg.scaled(first_expert=2, n_experts_held=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(params, cfg, num_slots=4, max_len=64, k=4, page_size=8)
    rng = np.random.default_rng(5)
    reqs = [Request(id=str(i), prompt=rng.integers(1, cfg.vocab, 5).tolist(),
                    max_new_tokens=int(rng.integers(4, 13)))
            for i in range(6)]
    obs.enable()
    try:
        eng.run(reqs)
        st = eng.stats
        pairs = st.steps * 4 * cfg.top_k * (cfg.n_layers
                                            - int(cfg.first_layer_dense))
        if arch == "granite-moe-1b-a400m":
            assert st.moe_assignments == pairs
        else:
            assert 0 < st.moe_assignments < pairs
        layer_steps = st.steps * (cfg.n_layers - int(cfg.first_layer_dense))
        assert layer_steps <= st.moe_experts_hit <= min(
            layer_steps * cfg.experts_held, st.moe_assignments)
        assert f"moe_assignments={st.moe_assignments}" in st.summary()
        r = obs.REGISTRY
        assert r.get("repro_serve_moe_assignments_total").total() \
            == st.moe_assignments
        assert r.get("repro_serve_moe_experts_hit_total").total() \
            == st.moe_experts_hit
    finally:
        obs.disable()
        obs.reset()


def test_dense_decode_carries_no_expert_state():
    """The dense family's cache, step and engine are untouched: no expert
    counters ride its carry, and its stats report none."""
    cfg = smoke_config(get_arch("internlm2-1.8b"))
    assert "moe" not in init_cache(cfg, 2, 16)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(params, cfg, num_slots=2, max_len=32, k=4, page_size=8)
    eng.run([Request(id="a", prompt=[1, 2, 3], max_new_tokens=5)])
    assert eng.stats.moe_assignments == eng.stats.moe_experts_hit == 0
    assert "moe_" not in eng.stats.summary()
