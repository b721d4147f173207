"""Fused W8A8 GEMM Pallas kernel — the production path of the paper's
quantized pipeline on TPU.

The Neural-Cache insight "never move operands out of the array between
multiply, accumulate and requantize" maps to: int8 x int8 -> int32 MACs on
the MXU with the dequant/bias epilogue fused in VMEM, so the accumulator
never round-trips HBM.

Grid: (M/bm, N/bn, K/bk), K innermost; int32 accumulator lives in a VMEM
scratch tile, epilogue fires on the last K step.  Tile defaults keep the
working set (x 128x512 + w 512x128 + acc 128x128x4B = 192 KB) well inside
the ~16 MB/core VMEM while aligning both MXU dims to 128.  Operands are
zero-padded to the TPU tiling exactly as in kernels/bitserial_matmul.py;
``interpret`` has no default (``kernels/ops.py`` decides it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitserial_matmul import _blocks, _pad2, _scales

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512


def _kernel(x_ref, w_ref, xs_ref, ws_ref, bias_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 -> int32 is the MXU's native integer form
    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        out = acc_ref[...].astype(jnp.float32)
        out = out * xs_ref[0, 0] * ws_ref[...]
        out = out + bias_ref[...]
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype", "interpret")
)
def quant_matmul(
    x_q: jax.Array,  # [M, K] int8
    w_q: jax.Array,  # [K, N] int8
    x_scale: jax.Array,  # scalar f32
    w_scale: jax.Array,  # [N] f32 (per-channel)
    bias: jax.Array | None = None,  # [N] f32
    *,
    interpret: bool,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    out_dtype=jnp.float32,
) -> jax.Array:
    M, K = x_q.shape
    K2, N = w_q.shape
    assert K == K2, (x_q.shape, w_q.shape)
    (bm, bn, bk), (Mp, Np, Kp) = _blocks(M, N, K, bm, bn, bk)
    x_q = _pad2(x_q.astype(jnp.int8), Mp, Kp)
    w_q = _pad2(w_q.astype(jnp.int8), Kp, Np)
    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    bias = _pad2(jnp.asarray(bias, jnp.float32).reshape(1, N), 1, Np)
    xs, ws = _scales(x_scale, w_scale, N, Np)
    n_k = Kp // bk
    grid = (Mp // bm, Np // bn, n_k)

    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_q, w_q, xs, ws, bias)
    return out[:M, :N]
