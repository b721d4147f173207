"""Hypothesis property tests on system invariants (quantization, data,
loop-aware analysis, cycle formulas)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.quantize import (choose_qparams, dequantize, quantize,
                                 quantize_per_channel)
from repro.data import SyntheticLMDataset

jax.config.update("jax_enable_x64", True)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.floats(-1e3, 1e3, allow_nan=False), min_size=2,
                max_size=64),
       st.booleans())
def test_quantize_roundtrip_error_bounded(vals, signed):
    """|dequant(quant(x)) - x| <= scale elementwise (affine, 8-bit).

    The bound is `scale`, not `scale/2`: zero-point rounding can shift the
    whole grid by up to half a step on top of value rounding.
    """
    x = jnp.asarray(vals, jnp.float32)
    qp = choose_qparams(jnp.min(x), jnp.max(x), bits=8, signed=signed)
    err = jnp.abs(dequantize(quantize(x, qp), qp) - x)
    assert float(jnp.max(err)) <= float(qp.scale) * (1 + 1e-5) + 1e-6


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 6), st.integers(2, 8), st.integers(2, 8))
def test_per_channel_quant_scales_per_column(bits, k, n):
    w = jnp.asarray(np.random.default_rng(0).normal(size=(k, n)), jnp.float32)
    q, scale = quantize_per_channel(w, bits=bits)
    qmax = (1 << (bits - 1)) - 1
    assert int(jnp.max(jnp.abs(q.astype(jnp.int32)))) <= qmax + 1
    recon = q.astype(jnp.float32) * scale
    assert float(jnp.max(jnp.abs(recon - w))) <= float(jnp.max(scale)) * 0.51


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 1000), st.sampled_from([1, 2, 4]), st.integers(0, 3))
def test_data_same_index_same_batch_any_host_split(idx, num_hosts, host_sel):
    """Global batch content is independent of the host partitioning."""
    ds = SyntheticLMDataset(vocab_size=64, seq_len=16, global_batch=4,
                            seed=9)
    host = min(host_sel, num_hosts - 1)
    b = ds.host_batch(idx, host, num_hosts)
    assert b["tokens"].shape == (4 // num_hosts, 16)
    # deterministic per (index, host)
    b2 = ds.host_batch(idx, host, num_hosts)
    np.testing.assert_array_equal(b["tokens"], b2["tokens"])
    # tokens stay inside the vocab
    assert b["tokens"].min() >= 1 and b["tokens"].max() < 64


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 24))
def test_loop_analyzer_linear_in_trip_count(L):
    from repro.distributed.hlo_loop_analysis import analyze_hlo
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def f(a, b):
        def body(c, _):
            return jnp.tanh(c @ b), None
        y, _ = jax.lax.scan(body, a, None, length=L)
        return y

    c = jax.jit(f).lower(x, x).compile()
    got = analyze_hlo(c.as_text()).flops
    want = L * (2 * 64 ** 3 + 64 * 64)  # dot + tanh per step
    # the loop-counter increment adds O(1) flops per iteration
    assert abs(got - want) <= 4 * L, (got, want)
