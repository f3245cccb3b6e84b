"""int8-quantized KV pages: error budget, op parity, pool layout, capacity.

The quantization contract is documented, not hand-waved: symmetric absmax
per (page row, KV head) over head_dim, so every dequantized element is
within ``scale / 2 = absmax / 254`` of the stored value — that bound is
asserted elementwise at the op level, and everything above it is derived:

- both ``paged_attention`` impls (Pallas scalar-prefetch kernel, XLA
  gather fallback) agree with each other tightly and with the f32 path to
  the propagated budget;
- greedy engine tokens vs the f32 pool are *statistically* identical —
  exact whenever logit gaps exceed the attention-output perturbation
  (dense/vlm/hybrid/audio in practice), and allowed to flip near-ties
  (the MoE router amplifies ties), so the per-family gate is a floor on
  agreement, not bitwise equality;
- capacity: an int8 page + its f32 scales costs ~(Dh+4)/(2*Dh) of the bf16
  page it replaces, so at a matched byte budget the quantized pool holds
  ≥ 2x the resident requests (the serve_bench capacity row asserts the
  same thing in-process).
"""
import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

from repro.configs import get_arch, smoke_config
from repro.dist import cache_specs
from repro.dist.sharding import make_rules
from repro.kernels import registry
from repro.launch.mesh import make_host_mesh
from repro.models import init_params
from repro.models.attention import paged_attention, quantize_kv
from repro.serve import Engine, PagedCachePool, PageError, Request
from test_paged import PAGED_CASES, paged_case

MAX_LEN = 32
PROMPTS = [[7], [3, 11, 5], [9, 2]]
N_NEW = 6
FAMILY_ARCHS = ["internlm2-1.8b", "granite-moe-1b-a400m", "mamba2-780m",
                "zamba2-2.7b", "whisper-medium", "qwen2-vl-2b"]
CFG_TINY = smoke_config(get_arch("internlm2-1.8b"))


@pytest.fixture(scope="module", params=FAMILY_ARCHS)
def family_setup(request):
    cfg = smoke_config(get_arch(request.param))
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ------------------------------------------------------------ error budget --
def test_quantize_kv_roundtrip_within_half_scale():
    """Elementwise: |dequant - x| <= scale / 2 = absmax / 254, the budget
    the module docstrings promise. All-zero rows take scale 1 (dequant 0)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 3, 16), jnp.float32) * 3
    x = x.at[1, 2].set(0.0)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == x.shape[:-1]
    amax = np.abs(np.asarray(x)).max(-1)
    np.testing.assert_allclose(
        np.asarray(s)[amax > 0], amax[amax > 0] / 127, rtol=1e-6)
    assert np.asarray(s)[1, 2] == 1.0 and not np.asarray(q)[1, 2].any()
    err = np.abs(np.asarray(q) * np.asarray(s)[..., None] - np.asarray(x))
    assert (err <= np.asarray(s)[..., None] / 2 + 1e-7).all()


@pytest.mark.parametrize("case", list(PAGED_CASES))
def test_paged_attention_quantized_parity(case):
    """Both impls dequantize identically (pallas ≈ xla, tight) and land
    within the propagated rounding budget of the f32 reference, over the
    same ragged/GQA/scratch-tail cases as the f32 kernel parity test
    (an int8 pool of 2 kv heads also exercises the kernel's head padding)."""
    q, k_pool, v_pool, table, valid = paged_case(case, jnp.int8)
    kq, kscale = quantize_kv(k_pool)
    vq, vscale = quantize_kv(v_pool)
    kq, vq, kscale, vscale = kq[None], vq[None], kscale[None], vscale[None]
    with registry.use("xla"):
        ref = paged_attention(q, k_pool[None], v_pool[None], table, valid, 0)
        got_x = paged_attention(q, kq, vq, table, valid, 0,
                                k_scale=kscale, v_scale=vscale)
    with registry.use("pallas"):
        got_p = paged_attention(q, kq, vq, table, valid, 0,
                                k_scale=kscale, v_scale=vscale)
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(got_x),
                               rtol=2e-5, atol=2e-5)
    # int8 vs f32: rounding <= absmax/254 per element propagates through
    # softmax(q.k) and p.v to ~1e-2 on unit-normal inputs
    np.testing.assert_allclose(np.asarray(got_x), np.asarray(ref),
                               rtol=3e-2, atol=3e-2)


# -------------------------------------------------------------- pool layout --
def test_quantized_pool_layout_and_defaults():
    """int8 codes + f32 scale siblings (parent shape minus head_dim), paged
    axes extended to the scales, doubled num_pages default, cheaper pages."""
    f = PagedCachePool(CFG_TINY, 3, 16, page_size=4)
    q = PagedCachePool(CFG_TINY, 3, 16, page_size=4, kv_dtype="int8")
    assert q.quantized and not f.quantized
    assert q.num_pages == 2 * (f.num_pages - 1) + 1
    assert q.page_bytes() < f.page_bytes()
    cache = q.make_cache()
    layers = cache["layers"]
    assert layers["k"].dtype == jnp.int8
    assert layers["k_scale"].dtype == jnp.float32
    assert layers["k_scale"].shape == layers["k"].shape[:-1]
    assert layers["v_scale"].shape == layers["v"].shape[:-1]
    # unwritten rows must dequantize to exactly 0 (codes 0 x scale 1)
    assert float(jnp.abs(layers["k"].astype(jnp.float32)
                         * layers["k_scale"][..., None]).max()) == 0.0
    assert float(layers["k_scale"].min()) == 1.0


def test_quantized_scale_leaves_shard_with_their_pages():
    """k_scale/v_scale take the k/v positional rule shifted one axis left:
    pages@dp, page rows@tp — codes and scales land on the same shard."""
    rules = make_rules(make_host_mesh())
    pool = PagedCachePool(CFG_TINY, 2, 16, page_size=4, num_pages=16,
                          kv_dtype="int8")
    specs = cache_specs(pool.make_cache(), rules)
    got = [(jtu.keystr(path), spec)
           for path, spec in jtu.tree_leaves_with_path(specs)
           if "scale" in jtu.keystr(path)]
    assert got
    for name, spec in got:
        nd = len(spec)
        assert spec[nd - 3] == "data" and spec[nd - 2] == "model", \
            f"{name}: {spec}"


def test_kv_dtype_validation():
    with pytest.raises(ValueError):
        PagedCachePool(CFG_TINY, 2, 16, page_size=4, kv_dtype="fp8")
    params = init_params(CFG_TINY, jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        Engine(params, CFG_TINY, num_slots=2, max_len=16, kv_dtype="int8")


# ------------------------------------------------------------ engine parity --
def test_quantized_engine_greedy_parity(family_setup):
    """Per family: the int8 engine drains the same workload as the f32
    paged engine with >= 75% greedy token agreement at equal stream lengths
    (exact in practice except where quantization noise crosses a logit
    near-tie — the MoE router). Pure-SSM archs have no pageable leaves and
    fall back to the unquantized slot pool."""
    cfg, params = family_setup
    rng = np.random.RandomState(0)
    encs = [rng.randn(16, cfg.d_model).astype(np.float32)
            if cfg.family == "audio" else None for _ in PROMPTS]
    toks = {}
    for dt in ("f32", "int8"):
        with registry.use("xla"):
            eng = Engine(params, cfg, num_slots=3, max_len=MAX_LEN, k=4,
                         max_prompt=8, page_size=5, kv_dtype=dt,
                         enc_len=16 if cfg.family == "audio" else None)
            out = eng.run([Request(id=f"r{i}", prompt=p, max_new_tokens=N_NEW,
                                   enc_embeds=encs[i])
                           for i, p in enumerate(PROMPTS)])
        toks[dt] = {r.id: r.tokens for r in out}
    if cfg.family == "ssm":
        assert not eng.paged and not getattr(eng.pool, "quantized", False)
        assert toks["int8"] == toks["f32"]      # fell back: bit-identical
        return
    assert eng.pool.quantized
    assert eng.pool.live_page_count() == 0
    assert {k: len(v) for k, v in toks["int8"].items()} == \
           {k: len(v) for k, v in toks["f32"].items()}
    agree = sum(a == b for rid in toks["f32"]
                for a, b in zip(toks["f32"][rid], toks["int8"][rid]))
    total = sum(len(v) for v in toks["f32"].values())
    assert agree / total >= 0.75, f"{agree}/{total} tokens agree"


# ----------------------------------------------------------------- capacity --
def test_quantized_pool_doubles_resident_requests_at_matched_bytes():
    """Same byte budget, requests reserving the same token span: the int8
    pool admits >= 2x as many before PageError. The budget is sized in f32
    pages (2.5 request-spans' worth): page granularity strands the f32
    remainder while the cheaper int8 pages convert it into whole spans."""
    span_pages = PagedCachePool(CFG_TINY, 1, MAX_LEN, page_size=4) \
        .pages_per_slot
    probe = PagedCachePool(CFG_TINY, 1, MAX_LEN, page_size=4)
    probe_q = PagedCachePool(CFG_TINY, 1, MAX_LEN, page_size=4,
                             kv_dtype="int8")
    budget = int(2.5 * span_pages) * probe.page_bytes()

    def resident(kv_dtype, page_bytes):
        pool = PagedCachePool(CFG_TINY, 16, MAX_LEN, page_size=4,
                              kv_dtype=kv_dtype,
                              num_pages=1 + budget // page_bytes)
        count = 0
        try:
            while True:
                slot = pool.allocate(f"r{count}")
                pool.reserve(slot, MAX_LEN)
                count += 1
        except PageError:
            pass
        return count

    n_f32 = resident("f32", probe.page_bytes())
    n_int8 = resident("int8", probe_q.page_bytes())
    assert n_f32 >= 1
    assert n_int8 >= 2 * n_f32, \
        f"int8 fits {n_int8} residents vs f32 {n_f32} at {budget} bytes"


def test_quantized_engine_end_to_end_with_fanout_and_prefix():
    """The whole stack composes: int8 pages + prefix reuse + an n=3 fan-out
    drain to completion and return every page."""
    params = init_params(CFG_TINY, jax.random.PRNGKey(0))
    from repro.serve import SamplingParams
    with registry.use("xla"):
        eng = Engine(params, CFG_TINY, num_slots=4, max_len=MAX_LEN, k=4,
                     max_prompt=8, page_size=4, kv_dtype="int8",
                     prefix_cache=True)
        # two drains: the first publishes w's whole prompt page to the trie,
        # so the fan-out group's stream 0 admits with a prefix hit
        out = eng.run([Request(id="w", prompt=[1, 2, 3, 4, 5],
                               max_new_tokens=4)])
        out += eng.run([
            Request(id="g", prompt=[1, 2, 3, 4, 5], max_new_tokens=4,
                    sampling=SamplingParams(temperature=0.8, seed=9), n=3),
        ])
    assert len(out) == 4
    assert sorted(r.stream for r in out if r.id == "g") == [0, 1, 2]
    assert eng.stats.shared_prompt_pages == 2       # 2 siblings x 1 page
    assert eng.stats.prefix_hits >= 1               # g reused w's pages
    assert all(len(r.tokens) == 4 for r in out)
