"""Mamba-2 SSD: chunked == recurrent == per-step decode (equivalence suite)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config, reduced_config
from repro.models import mamba2 as M


def _rand_ssd(rng, B, T, nh, P, N):
    x = jnp.asarray(rng.normal(size=(B, T, nh, P)).astype(np.float32))
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(B, T, nh)).astype(np.float32))
    A = jnp.asarray(-rng.uniform(0.5, 4.0, size=(nh,)).astype(np.float32))
    Bm = jnp.asarray(rng.normal(size=(B, T, N)).astype(np.float32))
    Cm = jnp.asarray(rng.normal(size=(B, T, N)).astype(np.float32))
    D = jnp.asarray(rng.normal(size=(nh,)).astype(np.float32))
    return x, dt, A, Bm, Cm, D


@pytest.mark.parametrize("T,chunk", [(32, 8), (33, 8), (64, 16), (7, 16), (128, 32)])
def test_chunked_matches_recurrent(T, chunk):
    rng = np.random.default_rng(T * chunk)
    x, dt, A, Bm, Cm, D = _rand_ssd(rng, 2, T, 3, 4, 8)
    y_ref, h_ref = M.ssd_recurrent(x, dt, A, Bm, Cm, D)
    y, h = M.ssd_chunked(x, dt, A, Bm, Cm, D, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=2e-4, atol=2e-4)


@given(seed=st.integers(0, 2**31 - 1), chunk=st.sampled_from([4, 8, 16]))
@settings(max_examples=10, deadline=None)
def test_chunked_matches_recurrent_property(seed, chunk):
    rng = np.random.default_rng(seed)
    x, dt, A, Bm, Cm, D = _rand_ssd(rng, 1, 24, 2, 4, 4)
    y_ref, _ = M.ssd_recurrent(x, dt, A, Bm, Cm, D)
    y, _ = M.ssd_chunked(x, dt, A, Bm, Cm, D, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=3e-4, atol=3e-4)


def test_initial_state_carries():
    rng = np.random.default_rng(0)
    x, dt, A, Bm, Cm, D = _rand_ssd(rng, 1, 32, 2, 4, 4)
    # run 32 steps in one shot vs two halves with state handoff
    y_full, h_full = M.ssd_chunked(x, dt, A, Bm, Cm, D, 8)
    y1, h1 = M.ssd_chunked(x[:, :16], dt[:, :16], A, Bm[:, :16], Cm[:, :16], D, 8)
    y2, h2 = M.ssd_chunked(x[:, 16:], dt[:, 16:], A, Bm[:, 16:], Cm[:, 16:], D, 8, h0=h1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full), rtol=2e-4, atol=2e-4)


def test_mixer_prefill_then_step():
    """Full mixer: prefill cache then step-decode must equal one-shot apply."""
    cfg = reduced_config(get_config("mamba2-2.7b"))
    p = M.mamba_init(cfg, jax.random.PRNGKey(0))
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 33, cfg.d_model), jnp.float32)
    y_full, _ = M.mamba_apply(cfg, p, u)
    y_pre, cache = M.mamba_apply(cfg, p, u[:, :32], cache=M.mamba_cache_init(cfg, 2))
    y_step, _ = M.mamba_step(cfg, p, u[:, 32:33], cache)
    np.testing.assert_allclose(np.asarray(y_step[:, 0]), np.asarray(y_full[:, 32]),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y_pre), np.asarray(y_full[:, :32]),
                               rtol=1e-3, atol=1e-4)


def test_state_is_constant_size():
    """The long_500k enabler: decode state independent of context length."""
    cfg = get_config("mamba2-2.7b")
    c = M.mamba_cache_init(cfg, batch=1)
    state_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree.leaves(c))
    assert state_bytes < 4 * (1 << 20)  # a few MB regardless of 500k context
