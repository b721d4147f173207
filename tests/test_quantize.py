"""Quantization pipeline tests (paper §IV-D requantization)."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import quantize as q

jax.config.update("jax_enable_x64", True)


def test_qparams_includes_zero():
    qp = q.choose_qparams(jnp.float32(2.0), jnp.float32(10.0))
    # min is pulled to 0 -> zero exactly representable
    assert int(qp.zero_point) == 0
    x = jnp.asarray([0.0, 5.0, 10.0])
    back = q.dequantize(q.quantize(x, qp), qp)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=float(qp.scale))


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_roundtrip_error_bounded(seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(256,)).astype(np.float32) * rng.uniform(0.1, 10)
    qp = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    back = np.asarray(q.dequantize(q.quantize(jnp.asarray(x), qp), qp))
    assert np.max(np.abs(back - x)) <= float(qp.scale) * 0.501 + 1e-6


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_per_channel_symmetric(seed):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(64, 32)).astype(np.float32)
    qw, scale = q.quantize_per_channel(jnp.asarray(w), axis=-1)
    assert qw.dtype == jnp.int8
    back = np.asarray(qw, np.float32) * np.asarray(scale)
    assert np.max(np.abs(back - w)) <= np.max(np.abs(w), axis=0).max() / 127 * 0.51 + 1e-6


@given(seed=st.integers(0, 2**31 - 1), mult=st.floats(1e-4, 0.99))
@settings(max_examples=50, deadline=None)
def test_fixedpoint_requant_matches_float(seed, mult):
    rng = np.random.default_rng(seed)
    acc = rng.integers(-(1 << 20), 1 << 20, size=(512,), dtype=np.int32)
    m, s = q.fixed_point_multiplier(jnp.float32(mult))
    got = np.asarray(q.requantize_fixedpoint(jnp.asarray(acc), m, s, zero_point=3))
    want = np.asarray(q.requantize_reference(jnp.asarray(acc), jnp.float32(mult), zero_point=3))
    # integer fixed-point vs float rounding may differ by 1 LSB at ties
    assert np.max(np.abs(got - want)) <= 1


def test_quantized_matmul_pipeline():
    """Float matmul vs int8 W8A8 + fixed-point requant: error ~ quant noise."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 64)).astype(np.float32)
    w = rng.normal(size=(64, 32)).astype(np.float32)
    ref = x @ w

    xq_p = q.choose_qparams(jnp.float32(x.min()), jnp.float32(x.max()))
    xq = q.quantize(jnp.asarray(x), xq_p)
    wq, wscale = q.quantize_per_channel(jnp.asarray(w), axis=-1)

    acc = jnp.einsum(
        "mk,kn->mn",
        (xq.astype(jnp.int32) - xq_p.zero_point),
        wq.astype(jnp.int32),
    )
    out = acc.astype(jnp.float32) * xq_p.scale * wscale[0]
    err = np.abs(np.asarray(out) - ref)
    # quant-noise bound: per-product err <= (s_x/2)|w| + (s_w/2)|x|, K=64 accum
    assert err.max() < 0.4, err.max()
    assert err.mean() < 0.08, err.mean()
