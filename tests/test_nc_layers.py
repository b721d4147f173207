"""In-cache layer execution vs jnp oracles (small shapes; bit-exact int path)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import nc_layers as nc
from repro.core import quantize as q
from repro.core import schedule as sched
from repro.core.cache_geometry import XEON_E5_35MB
from repro.core.mapper import LayerSpec

jax.config.update("jax_enable_x64", True)


def test_nc_dot_exact():
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, size=(16,), dtype=np.uint8)
    w = rng.integers(0, 256, size=(16,), dtype=np.uint8)
    val, cycles = nc.nc_dot(jnp.asarray(x), jnp.asarray(w), acc_bits=32)
    assert int(val) == int(x.astype(np.int64) @ w.astype(np.int64))
    assert cycles > 0


def test_nc_conv2d_matches_float_conv():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(6, 6, 3)).astype(np.float32)
    w = rng.normal(size=(3, 3, 3, 4)).astype(np.float32) * 0.5
    x_qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    w_qp = q.choose_qparams(jnp.float32(w.min()), jnp.float32(w.max()))

    acc, _ = nc.nc_conv2d(jnp.asarray(x), jnp.asarray(w), x_qp, w_qp)
    got = np.asarray(acc, np.float64) * float(x_qp.scale) * float(w_qp.scale)

    ref = jax.lax.conv_general_dilated(
        jnp.asarray(x)[None], jnp.asarray(w), (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )[0]
    err = np.abs(got - np.asarray(ref))
    # error bounded by quantization noise of the operands
    bound = (float(x_qp.scale) * np.abs(w).sum(axis=(0, 1, 2)).max()
             + float(w_qp.scale) * np.abs(x).sum()) * 0.5 * 0.1 + 0.15
    assert err.max() < max(bound, 0.35), (err.max(), bound)


def test_nc_conv2d_int_exact_vs_integer_conv():
    """The in-cache accumulator must equal the exact integer conv."""
    rng = np.random.default_rng(2)
    xq = rng.integers(0, 256, size=(5, 5, 2), dtype=np.uint8)
    wq = rng.integers(0, 256, size=(2, 2, 2, 3), dtype=np.uint8)
    x_qp = q.QuantParams(scale=1.0, zero_point=0)
    w_qp = q.QuantParams(scale=1.0, zero_point=0)
    acc, _ = nc.nc_conv2d(jnp.asarray(xq, jnp.float32), jnp.asarray(wq, jnp.float32), x_qp, w_qp)
    ref = jax.lax.conv_general_dilated(
        jnp.asarray(xq, jnp.int64)[None], jnp.asarray(wq, jnp.int64), (1, 1),
        "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )[0]
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(ref, np.int32))


def test_nc_maxpool_exact():
    rng = np.random.default_rng(3)
    x = rng.integers(0, 256, size=(6, 6, 4), dtype=np.uint8)
    out, _ = nc.nc_maxpool2d(jnp.asarray(x), window=2, stride=2)
    ref = np.asarray(
        jax.lax.reduce_window(
            jnp.asarray(x, jnp.int32), jnp.int32(0), jax.lax.max,
            (2, 2, 1), (2, 2, 1), "VALID"
        )
    )
    np.testing.assert_array_equal(np.asarray(out, np.int32), ref)


def test_relu_requant():
    acc = jnp.asarray([-500, -1, 0, 100, 100000], jnp.int32)
    out = nc.nc_relu_requant(acc, real_multiplier=0.01)
    ref = np.clip(np.round(np.maximum(np.asarray(acc), 0) * 0.01), 0, 255)
    assert np.max(np.abs(np.asarray(out, np.int64) - ref)) <= 1


def test_nc_fc():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(8,)).astype(np.float32)
    w = rng.normal(size=(8, 5)).astype(np.float32)
    x_qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    w_qp = q.choose_qparams(jnp.float32(w.min()), jnp.float32(w.max()))
    out, _ = nc.nc_fc(jnp.asarray(x), jnp.asarray(w), x_qp, w_qp)
    got = np.asarray(out, np.float64) * float(x_qp.scale) * float(w_qp.scale)
    np.testing.assert_allclose(got, x @ w, atol=0.2)


def test_nc_conv2d_default_plan_is_plan_layer():
    """``plan=None`` runs the dense default plan, ``plan_layer(spec, geom,
    batch=B)`` for the spec the conv derives from its operands: the same
    values, cycles and ``ConvStats`` (the plan included) as that plan
    given explicitly, on a batch of 2 with SAME padding and stride 2."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(2, 9, 9, 3)).astype(np.float32)
    w = rng.normal(size=(3, 3, 3, 6)).astype(np.float32)
    x_qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    w_qp = q.choose_qparams(jnp.float32(w.min()), jnp.float32(w.max()))
    geom = XEON_E5_35MB.scaled(1)
    # SAME at stride 2 pads 9 px by (1, 1): 11 px in, 5 px out
    spec = LayerSpec(name="nc_conv2d", kind="conv", H=11, R=3, S=3, C=3,
                     M=6, E=5, stride=2)
    args = (x, w, [x_qp] * 2, w_qp, 2)
    out0, cyc0, st0 = nc.nc_conv2d(*args, padding="SAME", geom=geom,
                                   return_stats=True)
    out1, cyc1, st1 = nc.nc_conv2d(
        *args, padding="SAME", geom=geom, return_stats=True,
        plan=sched.plan_layer(spec, geom, batch=2))
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(out1))
    assert np.asarray(out0).dtype == np.asarray(out1).dtype
    assert cyc0 == cyc1
    assert st0 == st1
