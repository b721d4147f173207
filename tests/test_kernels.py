"""Pallas kernels (interpret mode) vs pure-jnp oracles: shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import quantize as q
from repro.kernels import ref
from repro.kernels.bitserial_matmul import bitserial_matmul, plane_block_mask
from repro.kernels.quant_matmul import quant_matmul


def _rand_q(rng, m, k, n):
    x = rng.integers(-128, 128, size=(m, k)).astype(np.int8)
    w = rng.integers(-128, 128, size=(k, n)).astype(np.int8)
    xs = np.float32(rng.uniform(0.001, 0.1))
    ws = rng.uniform(0.001, 0.1, size=(n,)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(w), xs, jnp.asarray(ws)


SHAPES = [
    (1, 8, 8), (4, 16, 32), (128, 128, 128), (100, 130, 60),  # ragged
    (256, 512, 128), (3, 1024, 5), (128, 256, 256),
]


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_quant_matmul_matches_ref(m, k, n):
    rng = np.random.default_rng(m * 1000 + k + n)
    x, w, xs, ws = _rand_q(rng, m, k, n)
    bias = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    got = quant_matmul(x, w, xs, ws, bias, interpret=True)
    want = ref.quant_matmul_ref(x, w, xs, ws, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_bitserial_matmul_matches_ref(m, k, n):
    rng = np.random.default_rng(m + k * 31 + n)
    x, w, xs, ws = _rand_q(rng, m, k, n)
    planes = ref.pack_bitplanes(w, 8)
    got = bitserial_matmul(x, planes, xs, ws, interpret=True)
    want = ref.bitserial_matmul_ref(x, planes, xs, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-5)


def test_bitserial_equals_int8_gemm():
    """Plane decomposition must be bit-exact with the int8 GEMM."""
    rng = np.random.default_rng(7)
    x, w, xs, ws = _rand_q(rng, 64, 96, 48)
    planes = ref.pack_bitplanes(w, 8)
    a = ref.bitserial_matmul_ref(x, planes, xs, ws)
    b = ref.quant_matmul_ref(x, w, xs, ws)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n_bits", [1, 2, 3, 4, 6, 8])
def test_flexible_precision(n_bits):
    """Paper §III-A: flexible operand width — n-bit weights use n planes."""
    rng = np.random.default_rng(n_bits)
    lo, hi = -(1 << (n_bits - 1)), (1 << (n_bits - 1)) - 1
    w = rng.integers(lo, hi + 1, size=(32, 16)).astype(np.int8)
    x = rng.integers(-128, 128, size=(8, 32)).astype(np.int8)
    planes = ref.pack_bitplanes(jnp.asarray(w), n_bits)
    assert planes.shape[0] == n_bits
    got = bitserial_matmul(jnp.asarray(x), planes, jnp.float32(1.0),
                           jnp.ones(16, jnp.float32), interpret=True)
    want = jnp.dot(jnp.asarray(x, jnp.int32), jnp.asarray(w, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got, np.int64), np.asarray(want, np.int64))


def test_zero_plane_mask_skips():
    """Weights with only low-order bits set leave high planes empty."""
    rng = np.random.default_rng(3)
    w = rng.integers(0, 4, size=(256, 128)).astype(np.int8)  # 2 live planes
    planes = ref.pack_bitplanes(jnp.asarray(w), 8)
    mask = plane_block_mask(planes, bk=128, bn=128)
    m = np.asarray(mask)
    assert m[:2].all()
    assert not m[2:].any()  # planes 2..7 skipped entirely
    x = rng.integers(-128, 128, size=(16, 256)).astype(np.int8)
    got = bitserial_matmul(jnp.asarray(x), planes, jnp.float32(1.0),
                           jnp.ones(128, jnp.float32), interpret=True)
    want = jnp.dot(jnp.asarray(x, jnp.int32), jnp.asarray(w, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got, np.int64), np.asarray(want, np.int64))


@given(
    m=st.integers(1, 64), k=st.integers(1, 128), n=st.integers(1, 64),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=10, deadline=None)
def test_quant_matmul_property(m, k, n, seed):
    rng = np.random.default_rng(seed)
    x, w, xs, ws = _rand_q(rng, m, k, n)
    got = quant_matmul(x, w, xs, ws, interpret=True)
    want = ref.quant_matmul_ref(x, w, xs, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("blocks", [(32, 32, 32), (64, 128, 256), (128, 64, 64)])
def test_quant_matmul_block_shapes(blocks):
    bm, bn, bk = blocks
    rng = np.random.default_rng(bm)
    x, w, xs, ws = _rand_q(rng, 200, 300, 100)
    got = quant_matmul(x, w, xs, ws, bm=bm, bn=bn, bk=bk, interpret=True)
    want = ref.quant_matmul_ref(x, w, xs, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-5)


def test_quantize_then_matmul_end_to_end():
    """Float -> per-channel int8 -> kernel ~= float matmul."""
    rng = np.random.default_rng(11)
    x = rng.normal(size=(32, 256)).astype(np.float32)
    w = rng.normal(size=(256, 64)).astype(np.float32)
    xq_p = q.choose_qparams_symmetric(jnp.float32(np.abs(x).max()))
    xq = q.quantize(jnp.asarray(x), xq_p)
    wq, wscale = q.quantize_per_channel(jnp.asarray(w), axis=-1)
    got = quant_matmul(xq, wq, jnp.float32(xq_p.scale), wscale[0], interpret=True)
    err = np.abs(np.asarray(got) - x @ w)
    # K=256 accumulation of int8 quant noise on N(0,1) operands
    assert err.mean() < 0.6, err.mean()


def test_flash_attention_ref_gqa_shapes():
    rng = np.random.default_rng(0)
    q_ = jnp.asarray(rng.normal(size=(2, 8, 16, 32)).astype(np.float32))
    k_ = jnp.asarray(rng.normal(size=(2, 2, 16, 32)).astype(np.float32))
    v_ = jnp.asarray(rng.normal(size=(2, 2, 16, 32)).astype(np.float32))
    out = ref.flash_attention_ref(q_, k_, v_, causal=True)
    assert out.shape == (2, 8, 16, 32)
    assert bool(jnp.all(jnp.isfinite(out)))


# ---------------------------------------------------------------------------
# byte-packed plane format (8 planes per uint8, unpacked in-kernel)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_bits", [1, 2, 4, 8])
def test_byte_packed_roundtrip(n_bits):
    rng = np.random.default_rng(n_bits)
    lo, hi = -(1 << (n_bits - 1)), (1 << (n_bits - 1)) - 1
    w = jnp.asarray(rng.integers(lo, hi + 1, size=(64, 32)).astype(np.int8))
    packed = ref.pack_bitplanes_bytes(w, n_bits)
    assert packed.shape == (64, 32) and packed.dtype == jnp.uint8
    np.testing.assert_array_equal(
        np.asarray(ref.unpack_bitplanes_bytes(packed, n_bits)),
        np.asarray(ref.pack_bitplanes(w, n_bits)))


@pytest.mark.parametrize("m,k,n", [(4, 16, 32), (100, 130, 60), (128, 256, 256)])
def test_bitserial_matmul_byte_packed_matches_unpacked(m, k, n):
    """The kernel must produce identical results from the byte-packed
    [K, N] uint8 format (8x less VMEM traffic) and the legacy plane stack."""
    rng = np.random.default_rng(m + k * 31 + n)
    x, w, xs, ws = _rand_q(rng, m, k, n)
    planes = ref.pack_bitplanes(w, 8)
    packed = ref.pack_bitplanes_bytes(w, 8)
    a = bitserial_matmul(x, planes, xs, ws, interpret=True)
    b = bitserial_matmul(x, packed, xs, ws, n_bits=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    want = ref.quant_matmul_ref(x, w, xs, ws)
    np.testing.assert_allclose(np.asarray(b), np.asarray(want), rtol=1e-6,
                               atol=1e-5)


@pytest.mark.parametrize("n_bits", [2, 4, 6])
def test_byte_packed_sub8_sign_exact(n_bits):
    """MSB plane carries -2^(n-1): negative sub-8-bit weights must survive
    the byte-packed round trip through the kernel."""
    rng = np.random.default_rng(40 + n_bits)
    lo, hi = -(1 << (n_bits - 1)), (1 << (n_bits - 1)) - 1
    w = rng.integers(lo, hi + 1, size=(32, 16)).astype(np.int8)
    x = rng.integers(-128, 128, size=(8, 32)).astype(np.int8)
    packed = ref.pack_bitplanes_bytes(jnp.asarray(w), n_bits)
    got = bitserial_matmul(jnp.asarray(x), packed, jnp.float32(1.0),
                           jnp.ones(16, jnp.float32), n_bits=n_bits,
                           interpret=True)
    want = jnp.dot(jnp.asarray(x, jnp.int32), jnp.asarray(w, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got, np.int64), np.asarray(want))


# ---------------------------------------------------------------------------
# W4A4: byte-packed *activations* (2 elements/byte, 2 MXU passes per plane)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,k,n", [(8, 32, 16), (100, 130, 60), (16, 13, 8)])
def test_a4_packed_activations_exact(m, k, n):
    """Nibble-packed activations must be bit-exact with the int GEMM,
    including odd K (dangling nibble padded with zero)."""
    rng = np.random.default_rng(m + k + n)
    x = rng.integers(-8, 8, size=(m, k)).astype(np.int8)
    w = rng.integers(-8, 8, size=(k, n)).astype(np.int8)
    xp = ref.pack_activation_nibbles(jnp.asarray(x))
    assert xp.shape == (m, (k + 1) // 2) and xp.dtype == jnp.uint8
    np.testing.assert_array_equal(
        np.asarray(ref.unpack_activation_nibbles(xp, k)), x)
    from repro.kernels.bitserial_matmul import bitserial_matmul_a4
    got = bitserial_matmul_a4(xp, ref.pack_bitplanes_bytes(jnp.asarray(w), 4),
                              jnp.float32(1.0), jnp.ones(n, jnp.float32),
                              interpret=True)
    want = x.astype(np.int64) @ w.astype(np.int64)
    np.testing.assert_array_equal(np.asarray(got, np.int64), want)


def test_a4_matches_w8a8_dequant():
    """Same dequant epilogue semantics as the W8A8 kernel."""
    rng = np.random.default_rng(77)
    x = rng.integers(-8, 8, size=(32, 64)).astype(np.int8)
    w = rng.integers(-8, 8, size=(64, 24)).astype(np.int8)
    xs = np.float32(0.031)
    ws = rng.uniform(0.001, 0.1, size=(24,)).astype(np.float32)
    from repro.kernels.bitserial_matmul import bitserial_matmul_a4
    got = bitserial_matmul_a4(
        ref.pack_activation_nibbles(jnp.asarray(x)),
        ref.pack_bitplanes_bytes(jnp.asarray(w), 4),
        xs, jnp.asarray(ws), interpret=True)
    want = ref.quant_matmul_ref(jnp.asarray(x), jnp.asarray(w), xs,
                                jnp.asarray(ws))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-5)


def test_a4_hlo_flops_scale_with_planes():
    """Packing activations must not break precision-proportional FLOPs:
    the W4A4 kernel lowers to ~half the MXU work of the 8-plane kernel
    (2 half-K passes x 4 planes vs 1 full-K pass x 8 planes)."""
    from repro.distributed.hlo_analysis import xla_cost_analysis
    from repro.kernels.bitserial_matmul import (bitserial_matmul,
                                                bitserial_matmul_a4)
    rng = np.random.default_rng(5)
    M, K, N = 128, 256, 128
    x8 = jnp.asarray(rng.integers(-128, 128, size=(M, K)).astype(np.int8))
    w8 = ref.pack_bitplanes_bytes(
        jnp.asarray(rng.integers(-128, 128, size=(K, N)).astype(np.int8)), 8)
    f8 = jax.jit(lambda a, p: bitserial_matmul(a, p, 1.0, jnp.ones(N),
                                               n_bits=8, interpret=True))
    fl8 = xla_cost_analysis(f8.lower(x8, w8).compile()).get("flops", 0)
    x4 = ref.pack_activation_nibbles(
        jnp.asarray(rng.integers(-8, 8, size=(M, K)).astype(np.int8)))
    w4 = ref.pack_bitplanes_bytes(
        jnp.asarray(rng.integers(-8, 8, size=(K, N)).astype(np.int8)), 4)
    f4 = jax.jit(lambda a, p: bitserial_matmul_a4(a, p, 1.0, jnp.ones(N),
                                                  n_bits=4, interpret=True))
    fl4 = xla_cost_analysis(f4.lower(x4, w4).compile()).get("flops", 0)
    assert fl8 > 0 and fl4 > 0
    assert 0.35 < fl4 / fl8 < 0.65, (fl4, fl8)


def test_a4_ops_wrapper_fallback_matches_kernel():
    """ops.bitserial_matmul_a4's XLA fallback equals the Pallas kernel."""
    from repro.kernels import ops as K
    rng = np.random.default_rng(8)
    x = rng.integers(-8, 8, size=(16, 40)).astype(np.int8)
    w = rng.integers(-8, 8, size=(40, 12)).astype(np.int8)
    xp = K.pack_activations(jnp.asarray(x))
    wp = K.pack_weights(jnp.asarray(w, jnp.int32), 4)
    a = K.bitserial_matmul_a4(xp, wp, jnp.float32(1.0),
                              jnp.ones(12, jnp.float32), k=40)
    b = K.bitserial_matmul_a4(xp, wp, jnp.float32(1.0),
                              jnp.ones(12, jnp.float32), k=40,
                              prefer_pallas=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
