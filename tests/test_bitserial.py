"""Bit-exactness + cycle-formula tests for the in-SRAM arithmetic emulation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bitserial as bs

jax.config.update("jax_enable_x64", True)


def _rand(rng, n_bits, shape):
    return rng.integers(0, 1 << n_bits, size=shape, dtype=np.uint32)


# ---------------------------------------------------------------------------
# pack / unpack roundtrip
# ---------------------------------------------------------------------------
@given(
    n_bits=st.integers(1, 16),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=25, deadline=None)
def test_pack_unpack_roundtrip(n_bits, seed):
    rng = np.random.default_rng(seed)
    x = _rand(rng, n_bits, (17,))
    planes = bs.bitplane_pack(jnp.asarray(x), n_bits)
    assert planes.shape == (n_bits, 17)
    back = np.asarray(bs.bitplane_unpack(planes))
    np.testing.assert_array_equal(back, x)


def test_pack_unpack_signed():
    x = jnp.asarray([-128, -1, 0, 1, 127], jnp.int32)
    planes = bs.bitplane_pack(x.astype(jnp.uint32) & 0xFF, 8)
    back = np.asarray(bs.bitplane_unpack(planes, signed=True))
    np.testing.assert_array_equal(back, np.asarray(x))


# ---------------------------------------------------------------------------
# addition (§III-B): bit-exact, n+1 cycles
# ---------------------------------------------------------------------------
@given(
    n_bits=st.integers(1, 12),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=25, deadline=None)
def test_add_exact(n_bits, seed):
    rng = np.random.default_rng(seed)
    a, b = _rand(rng, n_bits, (64,)), _rand(rng, n_bits, (64,))
    pa, pb = bs.bitplane_pack(jnp.asarray(a), n_bits), bs.bitplane_pack(jnp.asarray(b), n_bits)
    out, cycles = bs.bitserial_add(pa, pb)
    assert cycles == n_bits + 1
    assert out.shape[0] == n_bits + 1
    np.testing.assert_array_equal(np.asarray(bs.bitplane_unpack(out)), a.astype(np.uint64) + b)


def test_add_mixed_width():
    pa = bs.bitplane_pack(jnp.asarray([250, 3], jnp.uint32), 8)
    pb = bs.bitplane_pack(jnp.asarray([7, 1], jnp.uint32), 3)
    out, cycles = bs.bitserial_add(pa, pb)
    np.testing.assert_array_equal(np.asarray(bs.bitplane_unpack(out)), [257, 4])
    assert cycles == 9


# ---------------------------------------------------------------------------
# subtraction: two's complement, sign plane correct
# ---------------------------------------------------------------------------
@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_sub_exact(seed):
    rng = np.random.default_rng(seed)
    a, b = _rand(rng, 8, (64,)), _rand(rng, 8, (64,))
    pa, pb = bs.bitplane_pack(jnp.asarray(a), 8), bs.bitplane_pack(jnp.asarray(b), 8)
    out, cycles = bs.bitserial_sub(pa, pb)
    got = np.asarray(bs.bitplane_unpack(out, signed=True))
    np.testing.assert_array_equal(got, a.astype(np.int64) - b.astype(np.int64))
    assert cycles == 9


# ---------------------------------------------------------------------------
# multiplication (§III-C): bit-exact, n^2+5n-2 cycles
# ---------------------------------------------------------------------------
@given(
    n_bits=st.integers(1, 8),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=25, deadline=None)
def test_mul_exact(n_bits, seed):
    rng = np.random.default_rng(seed)
    a, b = _rand(rng, n_bits, (32,)), _rand(rng, n_bits, (32,))
    pa, pb = bs.bitplane_pack(jnp.asarray(a), n_bits), bs.bitplane_pack(jnp.asarray(b), n_bits)
    out, cycles = bs.bitserial_multiply(pa, pb)
    assert cycles == n_bits * n_bits + 5 * n_bits - 2
    assert out.shape[0] == 2 * n_bits
    np.testing.assert_array_equal(
        np.asarray(bs.bitplane_unpack(out)), a.astype(np.uint64) * b.astype(np.uint64)
    )


def test_mul_paper_example_cycles():
    # §III-C: 8-bit multiply = 102 cycles; §VI-A quotes 236 cycles per 8-bit MAC
    assert bs.mul_cycles(8) == 102
    card = bs.OpCycles()
    assert card.mac_floor == 102 + 25
    assert card.mac8 == 236
    assert card.mac_overhead == 236 - 127


# ---------------------------------------------------------------------------
# MAC: acc += a*b with fixed accumulator width
# ---------------------------------------------------------------------------
@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_mac_exact(seed):
    rng = np.random.default_rng(seed)
    a, b = _rand(rng, 8, (16,)), _rand(rng, 8, (16,))
    acc0 = _rand(rng, 20, (16,))
    acc = bs.bitplane_pack(jnp.asarray(acc0), 24)
    pa, pb = bs.bitplane_pack(jnp.asarray(a), 8), bs.bitplane_pack(jnp.asarray(b), 8)
    out, _ = bs.bitserial_mac(acc, pa, pb)
    want = (acc0.astype(np.uint64) + a.astype(np.uint64) * b) % (1 << 24)
    np.testing.assert_array_equal(np.asarray(bs.bitplane_unpack(out)), want)


# ---------------------------------------------------------------------------
# reduction (§III-D): log-tree, exact sum, widening widths
# ---------------------------------------------------------------------------
@given(
    k=st.integers(2, 64),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=25, deadline=None)
def test_reduce_exact(k, seed):
    rng = np.random.default_rng(seed)
    x = _rand(rng, 8, (k,))
    planes = bs.bitplane_pack(jnp.asarray(x), 8)
    out, cycles = bs.bitserial_reduce(planes)
    assert out.shape[-1] == 1
    got = int(np.asarray(bs.bitplane_unpack(out))[0])
    assert got == int(x.astype(np.uint64).sum())
    assert cycles == bs.reduce_cycles(k, 8)


def test_reduce_cycles_growth():
    # each of the log2(k) steps costs (move w) + (add w+1) with w growing by 1
    assert bs.reduce_cycles(2, 8) == 8 + 9
    assert bs.reduce_cycles(4, 8) == (8 + 9) + (9 + 10)
    assert bs.reduce_cycles(32, 8) == sum((8 + i) + (9 + i) for i in range(5))


# ---------------------------------------------------------------------------
# predicated ops: ReLU / max (§IV-D)
# ---------------------------------------------------------------------------
def test_relu():
    vals = jnp.asarray([-120, -1, 0, 5, 127], jnp.int32)
    planes = bs.bitplane_pack(vals.astype(jnp.uint32) & 0xFF, 8)
    out, _ = bs.bitserial_relu(planes)
    got = np.asarray(bs.bitplane_unpack(out, signed=True))
    np.testing.assert_array_equal(got, np.maximum(np.asarray(vals), 0))


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_max(seed):
    rng = np.random.default_rng(seed)
    a, b = _rand(rng, 8, (33,)), _rand(rng, 8, (33,))
    pa, pb = bs.bitplane_pack(jnp.asarray(a), 8), bs.bitplane_pack(jnp.asarray(b), 8)
    out, _ = bs.bitserial_max(pa, pb)
    np.testing.assert_array_equal(np.asarray(bs.bitplane_unpack(out))[: len(a)], np.maximum(a, b))


# ---------------------------------------------------------------------------
# end-to-end dot product through the array
# ---------------------------------------------------------------------------
@given(k=st.sampled_from([4, 9, 16, 32]), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_dot(k, seed):
    rng = np.random.default_rng(seed)
    x = _rand(rng, 8, (k,))
    w = _rand(rng, 8, (k,))
    got, cycles = bs.bitserial_dot(jnp.asarray(x), jnp.asarray(w))
    assert int(got) == int((x.astype(np.uint64) * w).sum())
    assert cycles > 0


# ---------------------------------------------------------------------------
# packed bit-lane layout (32 lanes per uint32 word)
# ---------------------------------------------------------------------------
@given(n_bits=st.integers(1, 16), lanes=st.sampled_from([1, 7, 31, 32, 33, 64, 100]),
       seed=st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_pack_lanes_roundtrip(n_bits, lanes, seed):
    """pack_lanes <-> unpack_lanes round-trips any lane count, including
    non-multiples of 32 (zero-padded into the last word)."""
    rng = np.random.default_rng(seed)
    planes = (rng.integers(0, 2, size=(n_bits, lanes))).astype(np.uint8)
    pp = bs.pack_lanes(planes)
    assert pp.n_planes == n_bits
    assert pp.lane_shape == (lanes,)
    assert pp.n_words == -(-lanes // 32)
    np.testing.assert_array_equal(np.asarray(bs.unpack_lanes(pp)), planes)


def test_pack_lanes_multidim_roundtrip():
    rng = np.random.default_rng(0)
    planes = rng.integers(0, 2, size=(9, 3, 5, 7)).astype(np.uint8)  # 105 lanes
    pp = bs.pack_lanes(planes)
    assert pp.lane_shape == (3, 5, 7)
    assert pp.n_words == 4  # 105 lanes -> 4 words, 23 pad lanes
    np.testing.assert_array_equal(np.asarray(bs.unpack_lanes(pp)), planes)


@given(lanes=st.sampled_from([1, 5, 31, 33, 63, 97]),
       seed=st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_packed_matches_unpacked_ops(lanes, seed):
    """Ops fed PackedPlanes must agree bit-for-bit with the raw-plane path,
    at every lane count (padding lanes must never leak)."""
    rng = np.random.default_rng(seed)
    a = _rand(rng, 8, (lanes,))
    b = _rand(rng, 8, (lanes,))
    pa, pb = bs.bitplane_pack(jnp.asarray(a), 8), bs.bitplane_pack(jnp.asarray(b), 8)
    qa, qb = bs.pack_lanes(pa), bs.pack_lanes(pb)

    for op in (bs.bitserial_add, bs.bitserial_sub, bs.bitserial_multiply,
               bs.bitserial_max):
        raw, c_raw = op(pa, pb)
        packed, c_packed = op(qa, qb)
        assert isinstance(packed, bs.PackedPlanes)
        assert c_raw == c_packed
        np.testing.assert_array_equal(np.asarray(bs.unpack_lanes(packed)),
                                      np.asarray(raw))

    raw, c_raw = bs.bitserial_relu(pa)
    packed, c_packed = bs.bitserial_relu(qa)
    assert c_raw == c_packed
    np.testing.assert_array_equal(np.asarray(bs.unpack_lanes(packed)),
                                  np.asarray(raw))

    raw, c_raw = bs.bitserial_reduce(pa)
    packed, c_packed = bs.bitserial_reduce(qa)
    assert c_raw == c_packed
    np.testing.assert_array_equal(np.asarray(bs.unpack_lanes(packed)),
                                  np.asarray(raw))


def test_packed_ops_under_jit():
    """The scan-based traced path (inside jax.jit) matches the host path."""
    rng = np.random.default_rng(11)
    a = _rand(rng, 8, (45,))
    b = _rand(rng, 8, (45,))

    @jax.jit
    def pipeline(av, bv):
        pa = bs.bitplane_pack(av, 8)
        pb = bs.bitplane_pack(bv, 8)
        s, _ = bs.bitserial_add(pa, pb)
        p, _ = bs.bitserial_multiply(pa, pb)
        r, _ = bs.bitserial_reduce(p)
        return s, p, r

    s, p, r = pipeline(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(bs.bitplane_unpack(s)),
                                  a.astype(np.uint64) + b)
    np.testing.assert_array_equal(np.asarray(bs.bitplane_unpack(p)),
                                  a.astype(np.uint64) * b)
    assert int(np.asarray(bs.bitplane_unpack(r))[0]) == int(
        (a.astype(np.uint64) * b).sum())


def test_selective_copy_packed_mask():
    rng = np.random.default_rng(4)
    dst = bs.bitplane_pack(jnp.asarray(_rand(rng, 8, (40,))), 8)
    src = bs.bitplane_pack(jnp.asarray(_rand(rng, 8, (40,))), 8)
    mask = rng.integers(0, 2, size=(40,)).astype(np.uint8)
    out, cyc = bs.selective_copy(dst, src, mask)
    want = np.where(mask[None, :].astype(bool), np.asarray(src), np.asarray(dst))
    np.testing.assert_array_equal(np.asarray(out), want)
    assert cyc == 9


# ---------------------------------------------------------------------------
# Packed-resident format: direct value packing, the in-packed lane shuffle,
# row-aligned ops, and the fused dot engine (PR 2)
# ---------------------------------------------------------------------------
@given(n_bits=st.integers(1, 16), k=st.sampled_from([1, 5, 9, 32, 72, 100]),
       seed=st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_pack_values_roundtrip(n_bits, k, seed):
    """pack_values/unpack_values round-trip both layouts without ever
    materializing raw planes."""
    rng = np.random.default_rng(seed)
    x = _rand(rng, n_bits, (7, k))
    for row_align in (False, True):
        pp = bs.pack_values(x, n_bits, row_align=row_align)
        assert (pp.row_lanes > 0) == row_align
        np.testing.assert_array_equal(np.asarray(bs.unpack_values(pp)), x)
        # matches the plane-tensor path bit for bit
        np.testing.assert_array_equal(
            np.asarray(bs.unpack_lanes(pp)), bs.bitplane_pack(x, n_bits))


@given(k=st.sampled_from([1, 4, 9, 31, 32, 72]), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_lane_shuffle_roundtrip(k, seed):
    """shuffle_to_rows/shuffle_to_flat convert layouts in packed space."""
    rng = np.random.default_rng(seed)
    x = _rand(rng, 8, (6, k))
    flat = bs.pack_values(x, 8)
    rows = bs.shuffle_to_rows(flat)
    assert rows.row_lanes == bs._row_layout(k)[0]
    np.testing.assert_array_equal(rows.words,
                                  bs.pack_values(x, 8, row_align=True).words)
    back = bs.shuffle_to_flat(rows)
    assert back.row_lanes == 0
    np.testing.assert_array_equal(back.words, flat.words)


def test_row_aligned_ops_match_flat():
    """Element-wise ops agree bit-for-bit across layouts, and mixed-layout
    operands are aligned via the packed-space shuffle."""
    rng = np.random.default_rng(21)
    a = _rand(rng, 8, (5, 9))
    b = _rand(rng, 8, (5, 9))
    fa, fb = bs.pack_values(a, 8), bs.pack_values(b, 8)
    ra, rb = (bs.pack_values(v, 8, row_align=True) for v in (a, b))
    for op in (bs.bitserial_add, bs.bitserial_sub, bs.bitserial_multiply,
               bs.bitserial_max):
        flat_out, c1 = op(fa, fb)
        rows_out, c2 = op(ra, rb)
        mixed_out, c3 = op(ra, fb)  # flat operand shuffled to rows
        assert c1 == c2 == c3
        np.testing.assert_array_equal(np.asarray(bs.unpack_lanes(rows_out)),
                                      np.asarray(bs.unpack_lanes(flat_out)))
        np.testing.assert_array_equal(np.asarray(bs.unpack_lanes(mixed_out)),
                                      np.asarray(bs.unpack_lanes(flat_out)))


def test_reduce_stays_packed():
    """A packed MAC -> reduce chain never leaves word space and returns a
    flat-packed result with the unchanged cycle formula."""
    rng = np.random.default_rng(22)
    a = _rand(rng, 8, (5, 72))
    b = _rand(rng, 8, (5, 72))
    ra, rb = (bs.pack_values(v, 8, row_align=True) for v in (a, b))
    prod, c_mul = bs.bitserial_multiply(ra, rb)
    assert isinstance(prod, bs.PackedPlanes) and prod.row_lanes == 128
    red, c_red = bs.bitserial_reduce(prod)
    assert isinstance(red, bs.PackedPlanes) and red.row_lanes == 0
    assert red.lane_shape == (5, 1)
    want = (a.astype(np.uint64) * b).sum(axis=1)
    np.testing.assert_array_equal(
        np.asarray(bs.unpack_values(red))[:, 0], want)
    assert c_red == bs.reduce_cycles(72, 16)


@given(k=st.sampled_from([3, 9, 32, 72]), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=8, deadline=None)
def test_packed_dot_words_exact(k, seed):
    from repro.core.nc_layers import nc_dot
    rng = np.random.default_rng(seed)
    x = _rand(rng, 8, (6, k))
    w = _rand(rng, 8, (6, k))
    got, cyc = nc_dot(x, w, acc_bits=32)
    want = (x.astype(np.int64) * w).sum(axis=-1)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert cyc == bs.dot_cycles(k, 8, 32)


def test_engine_cache_buckets():
    """The bucketed jit engine compiles once per (planes, acc, K) key."""
    rng = np.random.default_rng(23)
    bs.engine_cache_clear()
    k = 40
    for rows in (8, 8, 8):  # same bucket -> one compile
        x = _rand(rng, 8, (rows, k))
        w = _rand(rng, 8, (rows, k))
        xw = bs.pack_values(x, 8, row_align=True).words.reshape(8, -1, 2)
        ww = bs.pack_values(w, 8, row_align=True).words.reshape(8, -1, 2)
        vals, _ = bs.packed_dot_words(xw, ww, K=k, acc_bits=32, engine="jit")
        np.testing.assert_array_equal(
            np.asarray(vals), (x.astype(np.int64) * w).sum(axis=-1))
    info = bs.engine_cache_info()
    assert info["entries"] == 1
    if info["compiled"]:  # executable count is best-effort (private JAX API)
        assert info["compiled"] == 1


def test_zero_skip_stats_account_and_preserve_results():
    """Host multiply elides all-zero-operand words; results and cycles are
    untouched, the elision is visible in SKIP_STATS."""
    rng = np.random.default_rng(24)
    a = _rand(rng, 8, (200,))
    b = np.zeros((200,), np.uint32)
    b[:3] = rng.integers(1, 256, 3)
    pa = bs.pack_values(a, 8)
    pb = bs.pack_values(b, 8)
    bs.SKIP_STATS.reset()
    out, cyc = bs.bitserial_multiply(pa, pb)
    np.testing.assert_array_equal(
        np.asarray(bs.unpack_values(out)), a.astype(np.int64) * b)
    assert cyc == bs.mul_cycles(8)  # modeled cycles unchanged by skipping
    snap = bs.SKIP_STATS.snapshot()
    assert snap["words_total"] == 7  # 200 lanes -> 7 words
    assert snap["words_skipped"] == 6  # only the first word has live pairs
    assert snap["lanes_zero"] >= 197
