"""The main-path kernels compile for a TPU v5e chip, at real widths.

Nothing runs: each test compiles for a described (not attached) v5e chip,
so the chip's own compiler refuses what it would refuse on the machine — a
block that does not tile, a primitive Mosaic cannot lower, more VMEM than a
kernel may use — and the compiled text must hold the kernel
(``tpu_custom_call``). The topology is described inside a module fixture,
never at import: only the worker given this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import SolverConfig
from repro.core.distributed import make_distributed_solver
from repro.kernels import registry
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.gram import ops as gram_ops
from repro.kernels.prox_step import ops as prox_ops
from repro.roofline.hlo_cost import analyze_hlo

#: internlm2-1.8b attention widths and a 4-slot engine on 16-token pages
HQ, HKV, D = 16, 8, 128
SLOTS, PAGE, PAGES_PER_SLOT = 4, 16, 8
#: the internlm2.decode_sat cell's pool: 32 slots of 36 pages
CELL_SLOTS, CELL_PAGES = 32, 36
#: zamba2-2.7b's shared attention: 32 q/kv heads of 80 channels
ZAMBA_H, ZAMBA_D = 32, 80
#: mamba2-780m SSD widths: 48 heads of 64 channels, state 128
SSD_H, SSD_P, SSD_N = 48, 64, 128
#: the dsmoe16b.decode_sat cell's expert layers: 16 rows, top-6, 8 held
#: experts of d 2048 and d_ff 1408 in each of 27 layers
MOE_T, MOE_K, MOE_E, MOE_D, MOE_F, MOE_L = 16, 6, 8, 2048, 1408, 27


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """Shape builder on one described chip; the persistent compilation
    cache is off meanwhile (an entry compiled for a described chip cannot
    be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache
    one = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compile_kernel(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _pallas(op):
    return registry.get_op(op).impls["pallas"].fn


#: layers of the stacked pools the paged decode kernel is compiled over
POOL_LAYERS = 3


def _paged_decode(q, k, v, t, n, layer, *s):
    return fa_ops.paged_flash_decode(
        q, k, v, t, n, layer, k_scale=s[0] if s else None,
        v_scale=s[1] if s else None, interpret=False)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_compiles(chip, kv_dtype):
    pages = 1 + SLOTS * PAGES_PER_SLOT
    pool = chip((POOL_LAYERS, pages, PAGE, HKV, D),
                jnp.int8 if kv_dtype == "int8" else jnp.bfloat16)
    shapes = [chip((SLOTS, 1, HQ, D), jnp.bfloat16), pool, pool,
              chip((SLOTS, PAGES_PER_SLOT), jnp.int32),
              chip((SLOTS,), jnp.int32), chip((), jnp.int32)]
    if kv_dtype == "int8":
        shapes += [chip((POOL_LAYERS, pages, PAGE, HKV))] * 2
    compile_kernel(_paged_decode, *shapes)


@pytest.mark.parametrize("case", ["cell-bf16", "cell-int8",
                                  "zamba2-head-blocks"])
def test_paged_decode_compiles_at_cell_widths(chip, case):
    """The paged decode kernel at the benchmark cell's widths (32 slots x
    36 pages of 16, Hq 16, Hkv 8, D 128), bf16 and int8, and at zamba2's
    32 kv heads on 64-token pages, where one page of K+V outgrows a block
    and the kernel splits the heads over its grid, each over pools stacked
    over layers. The kernel reads one layer of the pools: no copy, reshape
    or transpose of a layer's pool precedes the call, and where no padding
    is needed (the bf16 cell) no slice or fusion either — the kernel reads
    the stack where it lies."""
    import re
    from repro.kernels.flash_attention import kernel as fa_k
    if case == "zamba2-head-blocks":
        B, npg, P, hq, hkv, d = 4, 8, 64, ZAMBA_H, ZAMBA_H, ZAMBA_D
        dt = jnp.bfloat16
        _, hb = fa_k.decode_blocks(P, hkv, 128, 2, npg)
        assert hb < hkv
    else:
        B, npg, P, hq, hkv, d = CELL_SLOTS, CELL_PAGES, PAGE, HQ, HKV, D
        dt = jnp.int8 if case == "cell-int8" else jnp.bfloat16
    pages = 1 + B * npg
    pool = chip((POOL_LAYERS, pages, P, hkv, d), dt)
    shapes = [chip((B, 1, hq, d), jnp.bfloat16), pool, pool,
              chip((B, npg), jnp.int32), chip((B,), jnp.int32),
              chip((), jnp.int32)]
    if dt == jnp.int8:
        shapes += [chip((POOL_LAYERS, pages, P, hkv))] * 2
    text = compile_kernel(_paged_decode, *shapes)
    entry = text[text.index("ENTRY"):]
    assert re.search(r"%paged_flash_decode(\.\d+)? = ", entry)
    in_place = fa_ops.reads_pool_in_place(hkv, d, dt, dt == jnp.int8)
    assert in_place == (case == "cell-bf16")
    moves = "copy|copy-start|reshape|transpose" + \
        ("|dynamic-slice|fusion" if in_place else "")
    layer_elems = pages * P * hkv * d
    for ln in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \(?\w+\[([\d,]*)\]\S* "
                     rf"({moves})\(", ln)
        if m:
            n = int(np.prod([int(x) for x in m.group(1).split(",") if x]))
            assert n < layer_elems, ln


def test_engine_decode_block_names_its_parts_on_the_chip(chip,
                                                         monkeypatch):
    """The engine's k-step block compiled for the chip: the paged decode
    kernel keeps the instruction name ``paged_flash_decode.*`` that the
    benchmark's readers match, under the named scope ``decode.attn``."""
    import re
    from repro.configs import get_arch, smoke_config
    from repro.models import init_params
    from repro.serve import Engine
    from repro.serve.sampling import SlotSampling
    cfg = smoke_config(get_arch("internlm2-1.8b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    # steer the kernel choice to the chip's (the block is only compiled)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = Engine(params, cfg, num_slots=SLOTS, max_len=PAGE * PAGES_PER_SLOT,
                 k=4, max_prompt=PAGE, page_size=PAGE)
    samp = SlotSampling(temperature=eng._temp, top_p=eng._top_p,
                        top_k=eng._top_k, key=eng.pool.slot_keys)
    args = (eng.params, eng.state, eng._prompt_buf, eng._prompt_len,
            eng._max_new, eng._active, samp, eng.pool.tables)
    shapes = jax.tree.map(lambda a: chip(a.shape, a.dtype), args)
    text = eng._block.lower(*shapes).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and " custom-call(" in ln]
    assert calls
    for ln in calls:
        name = ln.split(" = ", 1)[0].split()[-1].lstrip("%")
        assert re.match(r"paged_flash_decode(\.|$)", name), name
        assert "/decode.attn/" in re.search(r'op_name="([^"]*)"',
                                            ln).group(1)


def _cell_block_text(chip, cfg, slots):
    """The engine's k-step block, compiled for the chip, over a cell's pool
    of ``slots`` slots of ``CELL_PAGES`` pages of ``PAGE`` (weights as
    shapes only: nothing is allocated at their size)."""
    from repro.models import init_params
    from repro.serve import Engine
    from repro.serve.sampling import SlotSampling
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    eng = Engine(params, cfg, num_slots=slots, max_len=CELL_PAGES * PAGE,
                 k=4, max_prompt=64, page_size=PAGE)
    assert eng._kv_in_place
    samp = SlotSampling(temperature=eng._temp, top_p=eng._top_p,
                        top_k=eng._top_k, key=eng.pool.slot_keys)
    args = (eng.params, eng.state, eng._prompt_buf, eng._prompt_len,
            eng._max_new, eng._active, samp, eng.pool.tables)
    shapes = jax.tree.map(lambda a: chip(a.shape, a.dtype), args)
    return eng, eng._block.lower(*shapes).compile().as_text()


def _assert_pool_in_place(text, kv_leaf):
    """The paged pool stays in one buffer through the block: no copy,
    slice, slab update or fusion makes an array as large as one layer's K
    pool, apart from the in-place scatters of each step's rows
    (``decode.kv_write``); every pool the block takes is aliased to its
    output (donated); and the attention kernel keeps its name
    ``paged_flash_decode.*`` under ``decode.attn``."""
    import re
    layer_elems = int(np.prod(kv_leaf.shape[-4:]))
    elems = lambda dims: np.prod([int(x) for x in dims.split(",") if x])
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \(?\w+\[([\d,]*)\]\S* "
                     r"(copy|copy-start|dynamic-slice|dynamic-update-slice"
                     r"|fusion)\(", ln)
        if m and elems(m.group(1)) >= layer_elems:
            assert m.group(2) == "fusion" and \
                "/decode.kv_write/" in ln, ln
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    aliased = {int(n) for n in re.findall(r"\((\d+), \{", alias)}
    entry = text[text.index("\nENTRY"):]
    pools = [int(num) for dims, num in re.findall(
        r"%state_cache\S* = \w+\[([\d,]*)\]\S* parameter\((\d+)\)", entry)
        if elems(dims) >= layer_elems]
    assert pools and set(pools) <= aliased, (pools, alias)
    calls = [ln for ln in text.splitlines()
             if re.search(r"%paged_flash_decode(\.\d+)? = ", ln)]
    assert calls
    for ln in calls:
        assert "/decode.attn/" in re.search(r'op_name="([^"]*)"',
                                            ln).group(1)


def test_engine_decode_block_keeps_the_pool_in_place_at_cell_widths(
        chip, monkeypatch):
    """internlm2's attention widths over the internlm2.decode_sat cell's
    pool (32 slots of 36 pages of 16, 8 kv heads of 128), at a smoke depth
    of 3 layers: the pool goes through the block without a copy."""
    from repro.configs import get_arch, smoke_config
    cfg = smoke_config(get_arch("internlm2-1.8b")).scaled(
        n_layers=3, n_heads=HQ, n_kv_heads=HKV, head_dim=D)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng, text = _cell_block_text(chip, cfg, CELL_SLOTS)
    _assert_pool_in_place(text, eng.pool.kv_leaf())


def test_moe_decode_block_keeps_the_pool_in_place_at_cell_widths(
        chip, monkeypatch):
    """deepseek's layout (a dense first layer, then held experts) over the
    dsmoe16b.decode_sat cell's pool (16 slots of 36 pages of 16, 16 kv
    heads of 128), at a smoke depth of 3 layers: the stacked pool and the
    dense layer's own go through the block without a copy."""
    from repro.configs import get_arch, smoke_config
    cfg = smoke_config(get_arch("deepseek-moe-16b")).scaled(
        n_layers=3, n_heads=16, n_kv_heads=16, head_dim=D, first_expert=2,
        n_experts_held=2)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng, text = _cell_block_text(chip, cfg, 16)
    _assert_pool_in_place(text, eng.pool.kv_leaf())


def test_held_experts_compiles_at_cell_widths(chip):
    """The held-experts kernel at the MoE cell's widths: one custom call,
    named ``held_experts_ffn`` for the benchmark's reader, that reads one
    layer of the stacked expert weights in place (no slice or copy of them
    precedes it)."""
    import re
    from repro.kernels.moe import ops as moe_ops
    bf = jnp.bfloat16
    w_in, w_out = (MOE_L, MOE_E, MOE_D, MOE_F), (MOE_L, MOE_E, MOE_F, MOE_D)
    text = compile_kernel(
        lambda x, s, g, wg, wu, wd, i: moe_ops.held_experts_ffn(
            x, s, g, wg, wu, wd, i, first_expert=0, interpret=False),
        chip((MOE_T, MOE_D), bf), chip((MOE_T, MOE_K), jnp.int32),
        chip((MOE_T, MOE_K)), chip(w_in, bf), chip(w_in, bf),
        chip(w_out, bf), chip((), jnp.int32))
    entry = text[text.index("ENTRY"):]
    calls = [ln.split(" = ", 1)[0].split()[-1].lstrip("%")
             for ln in entry.splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert len(calls) == 1 and re.match(r"held_experts_ffn(\.|$)",
                                        calls[0]), calls
    layer_elems = MOE_E * MOE_D * MOE_F
    for ln in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \(?bf16\[([\d,]*)\]\S* "
                     r"(copy|copy-start|dynamic-slice|slice|fusion|reshape"
                     r"|transpose)\(", ln)
        if m:
            n = int(np.prod([int(x) for x in m.group(1).split(",") if x]))
            assert n < layer_elems, ln


def test_moe_decode_block_names_its_experts_on_the_chip(chip, monkeypatch):
    """The engine's k-step block for deepseek's layout at a share of the
    experts, compiled for the chip: the expert kernel is named
    ``held_experts_ffn.*`` under ``decode.mlp/decode.experts``, the
    attention kernel ``paged_flash_decode.*`` under ``decode.attn``."""
    import re
    from repro.configs import get_arch, smoke_config
    from repro.models import init_params
    from repro.serve import Engine
    from repro.serve.sampling import SlotSampling
    cfg = smoke_config(get_arch("deepseek-moe-16b")).scaled(
        first_expert=2, n_experts_held=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = Engine(params, cfg, num_slots=SLOTS, max_len=PAGE * PAGES_PER_SLOT,
                 k=4, max_prompt=PAGE, page_size=PAGE)
    samp = SlotSampling(temperature=eng._temp, top_p=eng._top_p,
                        top_k=eng._top_k, key=eng.pool.slot_keys)
    args = (eng.params, eng.state, eng._prompt_buf, eng._prompt_len,
            eng._max_new, eng._active, samp, eng.pool.tables)
    shapes = jax.tree.map(lambda a: chip(a.shape, a.dtype), args)
    text = eng._block.lower(*shapes).compile().as_text()
    scopes = {}
    for ln in text.splitlines():
        if "tpu_custom_call" in ln and " custom-call(" in ln:
            name = ln.split(" = ", 1)[0].split()[-1].lstrip("%")
            op = re.search(r'op_name="([^"]*)"', ln).group(1)
            scopes.setdefault(re.sub(r"\.\d+$", "", name), []).append(op)
    assert set(scopes) == {"held_experts_ffn", "paged_flash_decode"}
    assert all("/decode.mlp/decode.experts/" in op
               for op in scopes["held_experts_ffn"])
    assert all("/decode.attn/" in op for op in scopes["paged_flash_decode"])


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_ssd_compiles(chip, grad):
    S = 512
    shapes = (chip((1, S, SSD_H, SSD_P), jnp.bfloat16), chip((1, S, SSD_H)),
              chip((SSD_H,)), chip((1, S, SSD_N), jnp.bfloat16),
              chip((1, S, SSD_N), jnp.bfloat16))
    ssd = lambda *a: _pallas("ssd")(*a, interpret=False)
    if grad:
        loss = lambda *a: ssd(*a)[0].astype(jnp.float32).sum()
        compile_kernel(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), *shapes)
    else:
        compile_kernel(ssd, *shapes)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_attention_compiles(chip, grad):
    S = 1024                                       # kernel layout (B,H,S,D)
    shapes = (chip((1, HQ, S, D), jnp.bfloat16),
              chip((1, HKV, S, D), jnp.bfloat16),
              chip((1, HKV, S, D), jnp.bfloat16))
    attn = lambda q, k, v: fa_ops.flash_attention(q, k, v, causal=True,
                                                  interpret=False)
    if grad:
        loss = lambda *a: attn(*a).astype(jnp.float32).sum()
        text = compile_kernel(jax.grad(loss, argnums=(0, 1, 2)), *shapes)
        assert text.count("tpu_custom_call") >= 3     # fwd, dq, dkv
    else:
        compile_kernel(attn, *shapes)


def test_gram_compiles_at_covtype_width(chip):
    compile_kernel(lambda X: gram_ops.gram(X, interpret=False),
                   chip((54, 58_101)))


@pytest.mark.parametrize("op", ["prox_loop", "prox_step"])
def test_prox_compiles_at_vmem_limit(chip, op):
    d = prox_ops.VMEM_MAX_D
    fn = getattr(prox_ops, op)
    kw = dict(Q=5) if op == "prox_loop" else {}
    compile_kernel(lambda G, R, z: fn(G, R, z, 0.1, 0.01, interpret=False,
                                      **kw),
                   chip((d, d)), chip((d,)), chip((d,)))


def test_distributed_solvers_all_reduce_t_over_k_on_four_chips(chip, topo):
    """The paper's Table I on the chip's own compiled HLO: on a 4-chip
    ``data`` mesh CA-sFISTA all-reduces T/k times, sFISTA T times, with the
    same bytes in total (the TPU's HLO carries no known_trip_count, so the
    analyzer reads the loop bounds from the loop conditions)."""
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    spec = lambda shape, p, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, p))
    d, n, cfg = 54, 4096, SolverConfig(T=64, k=16, b=0.1)
    args = (spec((d, n), P(None, "data")), spec((n,), P("data")),
            spec((d,), P()), spec((), P()), spec((2,), P(), jnp.uint32))
    ar = {}
    for alg in ("ca_sfista", "sfista"):
        solve = make_distributed_solver(alg, mesh, cfg, 0.01)
        text = solve.lower(*args).compile().as_text()
        ar[alg] = analyze_hlo(text).collectives["all-reduce"]
    assert ar["ca_sfista"]["count"] == cfg.T // cfg.k
    assert ar["sfista"]["count"] == cfg.T
    assert ar["ca_sfista"]["bytes"] == ar["sfista"]["bytes"]
