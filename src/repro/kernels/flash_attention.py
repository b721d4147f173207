"""Flash attention Pallas kernel (GQA, causal) — TPU target.

VMEM-tiled online-softmax attention: grid (B*H, Tq/bq, Tk/bk) with the KV
axis innermost ("arbitrary" = sequential), so the (bq, bk) score tile, the
running max/sum and the output accumulator all live in VMEM scratch and the
O(T^2) score matrix never exists in HBM — the same "keep partials next to
the compute" discipline the paper applies to SRAM bit lines.

GQA is handled in the index maps: query head h reads KV head h // G, so KV
tiles are fetched once per group from HBM (the MXU sees the dense [bq, bk]
tiles regardless).

Tile defaults: bq=bk=256, D<=256 keeps the working set
(q 256xD + k/v 2x256xD + scores 256x256x4 + acc 256xDx4) under ~1 MB —
far inside the ~16 MB/core VMEM, dims aligned to the 128-lane MXU.

Validated against ref.flash_attention_ref with interpret=True in
tests/test_kernels_flash.py (shape/dtype sweeps).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 256
DEFAULT_BK = 256
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, n_k: int, bq: int, bk: int, causal: bool, scale: float):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # [bq, D]
    k = k_ref[0].astype(jnp.float32)  # [bk, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    if causal:
        i = pl.program_id(1)
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    c = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * c + jnp.sum(p, axis=-1)
    m_ref[...] = m_new
    v = v_ref[0].astype(jnp.float32)
    acc_ref[...] = (acc_ref[...] * c[:, None]
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32))

    @pl.when(j == n_k - 1)
    def _epilogue():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(
    q: jax.Array,  # [B, H, Tq, D]
    k: jax.Array,  # [B, Hkv, Tk, D]
    v: jax.Array,
    *,
    interpret: bool,
    causal: bool = True,
    bq: int = DEFAULT_BQ,
    bk: int = DEFAULT_BK,
) -> jax.Array:
    B, H, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    G = H // Hkv
    bq = min(bq, Tq)
    bk = min(bk, Tk)
    assert Tq % bq == 0 and Tk % bk == 0, (Tq, bq, Tk, bk)
    n_q, n_k = Tq // bq, Tk // bk
    scale = 1.0 / math.sqrt(D)

    qf = q.reshape(B * H, Tq, D)
    kf = k.reshape(B * Hkv, Tk, D)
    vf = v.reshape(B * Hkv, Tk, D)

    def kv_index(bh, i, j):
        return (bh // H) * Hkv + (bh % H) // G, j, 0

    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, bq=bq, bk=bk, causal=causal,
                          scale=scale),
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, D), kv_index),
            pl.BlockSpec((1, bk, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Tq, D)
