"""Per-architecture smoke tests (reduced same-family configs, one forward +
train step on CPU, shape + finiteness assertions) and the decode-consistency
property: running the decoder one token at a time through the cache must
reproduce the teacher-forced forward logits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config, SHAPES, input_specs, \
    cell_applicable, get_arch
from repro.models import (init_params, forward, loss_fn, init_cache,
                          decode_step, param_count)
from repro.models.transformer import prefill_audio_cache

KEY = jax.random.PRNGKey(0)
B, S = 2, 32


def make_batch(cfg, batch=B, seq=S):
    if cfg.family == "audio":
        return dict(
            enc_embeds=jax.random.normal(KEY, (batch, seq, cfg.d_model),
                                         jnp.bfloat16),
            tokens=jax.random.randint(KEY, (batch, cfg.dec_len), 0, cfg.vocab),
            labels=jax.random.randint(KEY, (batch, cfg.dec_len), 0, cfg.vocab))
    if cfg.family == "vlm":
        txt = seq - cfg.vision_patches
        return dict(
            vision_embeds=jax.random.normal(
                KEY, (batch, cfg.vision_patches, cfg.d_model), jnp.bfloat16),
            tokens=jax.random.randint(KEY, (batch, txt), 0, cfg.vocab),
            labels=jax.random.randint(KEY, (batch, txt), 0, cfg.vocab))
    return dict(tokens=jax.random.randint(KEY, (batch, seq), 0, cfg.vocab),
                labels=jax.random.randint(KEY, (batch, seq), 0, cfg.vocab))


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_arch_smoke_forward_and_train_step(name):
    cfg = smoke_config(ARCHS[name])
    params = init_params(cfg, KEY)
    batch = make_batch(cfg)
    logits, aux = jax.jit(lambda p, b: forward(p, cfg, b))(params, batch)
    exp_S = cfg.dec_len if cfg.family == "audio" else S
    assert logits.shape == (B, exp_S, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()

    # one real SGD step decreases nothing catastrophically (finite grads)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, b: loss_fn(p, cfg, b)))(params, batch)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.vdot(g, g)) for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_arch_decode_step(name):
    cfg = smoke_config(ARCHS[name])
    params = init_params(cfg, KEY)
    cache = init_cache(cfg, B, 64, enc_len=S)
    if cfg.family == "audio":
        enc = make_batch(cfg)["enc_embeds"]
        cache = jax.jit(lambda p, c, e: prefill_audio_cache(p, cfg, c, e))(
            params, cache, enc)
    tok = jnp.ones((B, 1), jnp.int32)
    step = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t))
    logits, cache = step(params, cache, tok)
    logits2, cache = step(params, cache, tok)
    assert logits.shape == (B, 1, cfg.vocab)
    assert int(cache["pos"]) == 2
    assert np.isfinite(np.asarray(logits2, np.float32)).all()


@pytest.mark.parametrize("family_arch", ["llama3-8b", "granite-moe-1b-a400m",
                                         "mamba2-780m", "zamba2-2.7b",
                                         "qwen2-vl-2b", "whisper-medium"])
def test_decode_matches_teacher_forcing(family_arch):
    """Sequential cached decode == teacher-forced forward (same tokens;
    whisper decodes against the cross K/V prefilled from the same encoder
    input). This is the check of the decode's layer indexing that the
    engine's parity tests, whose reference shares it, cannot make.

    MoE uses an over-provisioned capacity factor so no token is dropped —
    capacity dropping is batch-composition-dependent and legitimately differs
    between teacher-forcing and decode.

    Both sides run with the xla backend pinned: cached decode can ONLY run
    xla (the pallas kernel rejects dynamic kv_valid masks and falls back),
    and under a forced-pallas policy a pallas teacher-forced forward would
    differ by kernel rounding — enough to flip MoE expert routing at
    decision boundaries. Cross-backend numerics are asserted op-by-op in
    tests/test_kernels.py::test_registry_backend_parity."""
    from repro.kernels import registry
    cfg = smoke_config(ARCHS[family_arch]).scaled(capacity_factor=8.0)
    params = init_params(cfg, KEY)
    seq = 8
    if cfg.family == "vlm":
        pytest.skip("vlm decode starts after a vision prefix; covered by "
                    "decode smoke + dense path")
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, seq), 0, cfg.vocab)
    batch = dict(tokens=toks, labels=toks)
    if cfg.family == "audio":
        batch["enc_embeds"] = make_batch(cfg)["enc_embeds"]
    with registry.use("xla"):
        tf_logits, _ = jax.jit(lambda p, b: forward(p, cfg, b))(params, batch)

    cache = init_cache(cfg, B, seq, enc_len=S)
    if cfg.family == "audio":
        cache = jax.jit(lambda p, c, e: prefill_audio_cache(p, cfg, c, e))(
            params, cache, batch["enc_embeds"])
    step = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t))
    outs = []
    with registry.use("xla"):       # pin the decode trace as well (encdec
        for t in range(seq):        # cross-attention would otherwise take
            logits, cache = step(params, cache, toks[:, t:t + 1])   # pallas
            outs.append(logits[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec_logits, np.float32),
                               np.asarray(tf_logits, np.float32),
                               atol=0.05, rtol=0.05)


def test_input_specs_cover_all_cells():
    """Every applicable (arch x shape) cell has well-formed input specs."""
    n_cells = 0
    n_skipped = 0
    for arch in ARCHS.values():
        for shape in SHAPES.values():
            ok, reason = cell_applicable(arch, shape)
            if not ok:
                n_skipped += 1
                assert reason
                continue
            specs = input_specs(arch, shape)
            assert "tokens" in specs
            for sds in specs.values():
                assert all(d > 0 for d in sds.shape)
            n_cells += 1
    assert n_cells + n_skipped == 40
    assert n_skipped == 8          # long_500k x 8 full-attention archs


def test_param_counts_full_configs():
    """Full (unreduced) configs hit the published parameter scale."""
    import jax.tree_util as jtu
    expected = {"llama3-8b": (8.0e9, 0.25), "mistral-nemo-12b": (12.2e9, 0.25),
                "phi3-medium-14b": (14e9, 0.3), "internlm2-1.8b": (1.9e9, 0.3),
                "mamba2-780m": (0.78e9, 0.4)}
    for name, (target, tol) in expected.items():
        cfg = ARCHS[name]
        sds = jax.eval_shape(lambda k, c=cfg: init_params(c, k),
                             jax.ShapeDtypeStruct((2,), jnp.uint32))
        n = sum(int(np.prod(l.shape)) for l in jtu.tree_leaves(sds))
        assert abs(n - target) / target < tol, (name, n)


def test_moe_matches_dense_reference_at_full_capacity():
    """The optimized scatter/gather MoE (vmap + custom-VJP combine) must equal
    the straightforward all-experts einsum reference when nothing is dropped,
    for both the forward value and the gradients."""
    from repro.models.moe import init_moe, moe_ffn
    import jax

    B, S, d, E, k_top, ff = 2, 16, 32, 4, 2, 64
    params = init_moe(jax.random.PRNGKey(0), d, ff, E, 0, 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d))

    def dense_ref(params, x):
        logits = jnp.einsum("bsd,de->bse", x, params["router"])
        gates = jax.nn.softmax(logits, -1)
        w, sel = jax.lax.top_k(gates, k_top)
        w = w / w.sum(-1, keepdims=True)
        mask = jax.nn.one_hot(sel, E).sum(2) * 0 + \
            (jax.nn.one_hot(sel, E) * w[..., None]).sum(2)   # (B,S,E)
        h = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, params["w_gate"]))
        h = h * jnp.einsum("bsd,edf->bsef", x, params["w_up"])
        y = jnp.einsum("bsef,efd->bsed", h, params["w_down"])
        return (y * mask[..., None]).sum(2)

    def opt_path(params, x):
        out, aux = moe_ffn(params, x, top_k=k_top, capacity_factor=8.0)
        return out

    y_ref = dense_ref(params, x)
    y_opt = opt_path(params, x)
    np.testing.assert_allclose(np.asarray(y_opt), np.asarray(y_ref),
                               atol=2e-5)

    g_ref = jax.grad(lambda p: (dense_ref(p, x) ** 2).sum())(params)
    g_opt = jax.grad(lambda p: (opt_path(p, x) ** 2).sum())(params)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_opt)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)
