"""The HLO cost analyzer (roofline backbone) against analytically known
programs: exact dot FLOPs, loop trip multiplication, collective weighting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.hlo_cost import analyze_hlo, _shape_bytes
from repro.roofline.analysis import analyze_compiled, model_flops
from repro.roofline.hw import TPU_V5E, chip_for
from repro.configs import get_arch, get_shape


def _cost(f, *args):
    return analyze_hlo(jax.jit(f).lower(*args).compile().as_text())


def test_single_dot_flops():
    a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 64), jnp.float32)
    c = _cost(lambda x, y: x @ y, a, b)
    assert c.flops == 2 * 128 * 256 * 64
    assert c.dot_count == 1


def test_scan_multiplies_trip_count():
    def f(xs, w):
        def body(c, x):
            return c @ w + x, None
        c, _ = jax.lax.scan(body, xs[0], xs)
        return c
    c = _cost(f, jax.ShapeDtypeStruct((24, 64, 64), jnp.float32),
              jax.ShapeDtypeStruct((64, 64), jnp.float32))
    assert c.flops == 24 * 2 * 64 ** 3
    assert c.dot_count == 24


def test_nested_scan_multiplies():
    def g(xs, w):
        def outer(c, x):
            def inner(c2, _):
                return c2 @ w, None
            c2, _ = jax.lax.scan(inner, c + x, None, length=5)
            return c2, None
        c, _ = jax.lax.scan(outer, xs[0], xs)
        return c
    c = _cost(g, jax.ShapeDtypeStruct((8, 32, 32), jnp.float32),
              jax.ShapeDtypeStruct((32, 32), jnp.float32))
    assert c.flops == 8 * 5 * 2 * 32 ** 3
    assert c.dot_count == 40


def test_batched_einsum_flops():
    c = _cost(lambda q, k: jnp.einsum("bsd,btd->bst", q, k),
              jax.ShapeDtypeStruct((2, 128, 64), jnp.float32),
              jax.ShapeDtypeStruct((2, 128, 64), jnp.float32))
    assert c.flops == 2 * 2 * 128 * 128 * 64


def test_shape_bytes():
    assert _shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert _shape_bytes("bf16[8]") == 16
    assert _shape_bytes("(f32[2,2], s32[4])") == 16 + 16
    assert _shape_bytes("pred[]") == 1


def test_hbm_slice_awareness():
    """A scan doing dynamic-slice reads of a big buffer must NOT count the
    whole buffer every iteration."""
    N, T = 4096, 32
    def f(buf):
        def body(c, i):
            sl = jax.lax.dynamic_slice(buf, (i * 4, 0), (4, N))
            return c + sl.sum(), None
        c, _ = jax.lax.scan(body, jnp.float32(0), jnp.arange(T))
        return c
    c = _cost(f, jax.ShapeDtypeStruct((T * 4, N), jnp.float32))
    full = T * (T * 4 * N * 4)                 # naive whole-buffer count
    assert c.hbm_bytes < full / 4, (c.hbm_bytes, full)


def test_model_flops_helper():
    arch = get_arch("llama3-8b")
    shape = get_shape("train_4k")
    mf = model_flops(arch, shape, 8_000_000_000, "train")
    assert mf == 6.0 * 8e9 * 256 * 4096


def test_peaks_are_keyed_by_device_kind():
    """A device kind without an entry in the peak table raises: a roofline
    share against another chip's peaks would be meaningless."""
    assert chip_for("TPU v5 lite") is TPU_V5E
    with pytest.raises(KeyError, match="no peak rates"):
        chip_for(jax.devices()[0].device_kind)      # the test host's CPU
    compiled = jax.jit(lambda x: x @ x).lower(
        jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
    roof = analyze_compiled(compiled, "TPU v5 lite")
    assert roof.flops == 2 * 128 ** 3
    assert roof.t_compute == roof.flops / TPU_V5E.peak_flops_bf16
    with pytest.raises(KeyError):
        analyze_compiled(compiled, "cpu")
