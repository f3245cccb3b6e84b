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


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_compiles(chip, kv_dtype):
    pages = 1 + SLOTS * PAGES_PER_SLOT
    pool = chip((pages, PAGE, HKV, D),
                jnp.int8 if kv_dtype == "int8" else jnp.bfloat16)
    shapes = [chip((SLOTS, 1, HQ, D), jnp.bfloat16), pool, pool,
              chip((SLOTS, PAGES_PER_SLOT), jnp.int32),
              chip((SLOTS,), jnp.int32)]
    if kv_dtype == "int8":
        shapes += [chip((pages, PAGE, HKV)), chip((pages, PAGE, HKV))]
    compile_kernel(lambda q, k, v, t, n, *s: fa_ops.paged_flash_decode(
        q, k, v, t, n, k_scale=s[0] if s else None,
        v_scale=s[1] if s else None, interpret=False), *shapes)


@pytest.mark.parametrize("case", ["cell-bf16", "cell-int8",
                                  "zamba2-head-blocks"])
def test_paged_decode_compiles_at_cell_widths(chip, case):
    """The paged decode kernel at the benchmark cell's widths (32 slots x
    36 pages of 16, Hq 16, Hkv 8, D 128), bf16 and int8, and at zamba2's
    32 kv heads on 64-token pages, where one page of K+V outgrows a block
    and the kernel splits the heads over its grid. The kernel reads the
    pools as they are: no copy, reshape or transpose of a pool-sized
    array precedes the call."""
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
    pool = chip((pages, P, hkv, d), dt)
    shapes = [chip((B, 1, hq, d), jnp.bfloat16), pool, pool,
              chip((B, npg), jnp.int32), chip((B,), jnp.int32)]
    if dt == jnp.int8:
        shapes += [chip((pages, P, hkv)), chip((pages, P, hkv))]
    text = compile_kernel(lambda q, k, v, t, n, *s: fa_ops.paged_flash_decode(
        q, k, v, t, n, k_scale=s[0] if s else None,
        v_scale=s[1] if s else None, interpret=False), *shapes)
    entry = text[text.index("ENTRY"):]
    assert re.search(r"%paged_flash_decode(\.\d+)? = ", entry)
    pool_elems = pages * P * hkv * d
    for ln in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \(?\w+\[([\d,]*)\]\S* "
                     r"(copy|copy-start|reshape|transpose)\(", ln)
        if m:
            n = int(np.prod([int(x) for x in m.group(1).split(",") if x]))
            assert n < pool_elems, ln


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
