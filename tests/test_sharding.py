"""Sharding-rule unit tests: param/cache spec inference on the production
mesh shapes (using a spoofed 512-entry device array — no XLA flag needed for
spec computation since Mesh accepts any ndarray of devices)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, get_arch
from repro.launch.mesh import spoof_mesh as fake_mesh
from repro.dist.sharding import (make_rules, param_specs, cache_specs,
                                 fit_spec)
from repro.models import init_params, init_cache


@pytest.fixture(scope="module")
def prod_rules():
    return make_rules(fake_mesh((16, 16), ("data", "model")))


@pytest.fixture(scope="module")
def pod_rules():
    return make_rules(fake_mesh((2, 16, 16), ("pod", "data", "model")))


def _spec_divides(spec, shape, mesh):
    for dim, entry in zip(shape, tuple(spec) + (None,) * len(shape)):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        assert dim % size == 0, (spec, shape)


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_param_specs_valid_all_archs(name, prod_rules, pod_rules):
    """Every param leaf of every FULL config gets a divisible spec on both
    production meshes (eval_shape only — no weights materialized)."""
    cfg = get_arch(name)
    sds = jax.eval_shape(lambda k: init_params(cfg, k),
                         jax.ShapeDtypeStruct((2,), jnp.uint32))
    for rules in (prod_rules, pod_rules):
        specs = param_specs(sds, rules)
        leaves = jax.tree.leaves(sds)
        spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert len(leaves) == len(spec_leaves)
        n_sharded = 0
        for leaf, spec in zip(leaves, spec_leaves):
            _spec_divides(spec, leaf.shape, rules.mesh)
            if any(e is not None for e in spec):
                n_sharded += 1
        # the bulk of parameters must actually be sharded
        big = [l for l in leaves if l.size > 1_000_000]
        assert n_sharded >= len(big) * 3 // 4, name


def test_gather_fsdp_drops_data_axis(prod_rules):
    cfg = get_arch("llama3-8b")
    sds = jax.eval_shape(lambda k: init_params(cfg, k),
                         jax.ShapeDtypeStruct((2,), jnp.uint32))
    sharded = param_specs(sds, prod_rules)
    gathered = param_specs(sds, prod_rules, gather_fsdp=True)
    for s, g in zip(jax.tree.leaves(sharded, is_leaf=lambda x: isinstance(x, P)),
                    jax.tree.leaves(gathered, is_leaf=lambda x: isinstance(x, P))):
        for es, eg in zip(s, g):
            if eg is not None:
                assert eg == es        # tp axes preserved
            if es == "data" or (isinstance(es, tuple) and "data" in es):
                assert eg is None      # fsdp axes gathered


@pytest.mark.parametrize("name", ["llama3-8b", "mamba2-780m", "zamba2-2.7b",
                                  "whisper-medium"])
def test_cache_specs_valid(name, prod_rules):
    cfg = get_arch(name)
    sds = jax.eval_shape(lambda: init_cache(cfg, 128, 32768, enc_len=32768))
    specs = cache_specs(sds, prod_rules)
    for leaf, spec in zip(jax.tree.leaves(sds),
                          jax.tree.leaves(specs,
                                          is_leaf=lambda x: isinstance(x, P))):
        _spec_divides(spec, leaf.shape, prod_rules.mesh)


def test_kv_cache_seq_sharded(prod_rules):
    """decode flash-decoding layout: KV cache seq over model axis."""
    cfg = get_arch("llama3-8b")
    sds = jax.eval_shape(lambda: init_cache(cfg, 128, 32768))
    specs = cache_specs(sds, prod_rules)
    kspec = specs["layers"]["k"]
    assert kspec[1] in (None,) or True   # leading stack dim
    # (n_layers, B, S, n_kv, hd): batch@data, seq@model
    assert kspec == P(None, "data", "model", None, None)


def test_fit_spec_multi_axis_degrade():
    mesh = fake_mesh((2, 16, 16), ("pod", "data", "model"))
    # batch=64: divisible by pod*data=32 -> keep both
    assert fit_spec(P(("pod", "data")), (64,), mesh) == P(("pod", "data"))
    # batch=2: only pod fits
    assert fit_spec(P(("pod", "data")), (2,), mesh) == P("pod")
