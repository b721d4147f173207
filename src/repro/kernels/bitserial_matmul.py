"""Bit-serial GEMM Pallas kernel — the paper's compute model on the MXU.

The SRAM array processes one *bit* of every lane per cycle; the TPU analogue
processes one *bit-plane* of the weight tensor per MXU pass:

    out = sum_b  w_b * (x @ plane_b),   w_b = 2^b (MSB plane: -2^(n-1))

Properties carried over from the paper:
  * latency proportional to weight precision (planes are a static unroll:
    4-bit weights cost half the MXU passes of 8-bit),
  * transposed layout: planes are packed once at weight-load time
    (ref.pack_bitplanes_bytes == the TMU gateway).  Storage is
    **byte-packed**: one uint8 carries all n_bits planes of an element
    (bit b == plane b), so a (bk, bn) tile moves 8x less VMEM traffic
    than the unpacked [n_bits, bk, bn] layout; each MXU pass recovers its
    plane in-kernel with a shift+mask (a VPU-cheap op on the int32 tile),
  * beyond-paper: *zero-plane skipping* — a per-(plane, K-block, N-block)
    occupancy mask predicates all-zero plane-blocks off with @pl.when,
    exploiting bit-level sparsity the SRAM substrate cannot (it must clock
    every bit-slice).  The mask rides in SMEM as a scalar-prefetch operand
    indexed by the grid position.  Pass ``plane_mask`` precomputed at
    weight-load time (plane_block_mask over the unpacked planes, at the
    padded block grid); otherwise it is derived from the byte-packed
    tensor on every call.

MXU operands: the signed path feeds int8 activations and int8 {0,1}
planes (int32 accumulation).  The unsigned exact path (activations
0..255, the packed word engine's convention) feeds bf16 operands with f32
accumulation, exact per K block while ``bk * 255 < 2**24``; each block's
partial product converts to the int32 accumulator exactly.

Every operand is zero-padded to the TPU tiling (rows to 32, K and N to
128 lanes) so each block satisfies the (32, 128) int8 tile; padding
contributes zeros and is sliced off.

Grid: (M/bm, N/bn, K/bk) with K innermost; planes of one (bk, bn) tile are
looped inside the kernel body (static python loop -> fully unrolled MXU
passes over the VMEM-resident byte tile).  ``interpret`` has no default:
``kernels/ops.py`` decides it from the platform.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import pack_bitplanes_bytes, unpack_bitplanes_bytes

# W4A4 (bitserial_matmul_a4): byte-packing extends to the *activation*
# operand — two 4-bit elements per byte (ref.pack_activation_nibbles), so
# both operand tiles move half the VMEM bytes.  Each weight plane then
# costs two MXU passes over half-K (even nibbles @ even plane rows + odd @
# odd): identical MAC count per plane, so total HLO FLOPs still scale with
# the plane count (4-bit ~ 0.5x of the 8-bit kernel; asserted in
# tests/test_kernels.py).

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 256

_SUBLANE = 32  # int8/uint8 second-minor tile
_LANE = 128
_BF16_EXACT = 1 << 24  # f32 accumulation is exact below this magnitude


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _blocks(M: int, N: int, K: int, bm: int, bn: int, bk: int):
    """Tile-aligned block sizes and the padded extents they divide."""
    bm = min(bm, _round_up(M, _SUBLANE))
    bn = min(bn, _round_up(N, _LANE))
    bk = min(bk, _round_up(K, _LANE))
    return (bm, bn, bk), (_round_up(M, bm), _round_up(N, bn),
                          _round_up(K, bk))


def _pad2(a: jax.Array, rows: int, cols: int) -> jax.Array:
    pr, pc = rows - a.shape[0], cols - a.shape[1]
    return jnp.pad(a, ((0, pr), (0, pc))) if pr or pc else a


def _plane_w(n_bits: int, signed: bool) -> list[int]:
    """Per-plane scales as static ints: two's complement (MSB negative)
    for the signed quantized-GEMM convention, plain powers of two for the
    UNSIGNED operands of the packed word engine (core/backends.py)."""
    pw = [1 << b for b in range(n_bits)]
    if signed:
        pw[-1] = -pw[-1]
    return pw


def _scales(x_scale, w_scale, N: int, Np: int):
    """x_scale as a (1, 1) SMEM scalar, w_scale as a (1, Np) lane row."""
    ws = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32), (N,))
    ws = _pad2(ws.reshape(1, N), 1, Np)
    return jnp.reshape(jnp.asarray(x_scale, jnp.float32), (1, 1)), ws


def _store_out(o_ref, acc_ref, xs_ref, ws_ref):
    """Scale/dequant epilogue.  Integer out dtypes take the EXACT int32
    accumulator (scales must be 1 — the backend-registry conformance
    path, where a float32 round-trip would lose bits above 2^24)."""
    if jnp.issubdtype(o_ref.dtype, jnp.integer):
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)
    else:
        out = acc_ref[...].astype(jnp.float32)
        out = out * xs_ref[0, 0] * ws_ref[...]
        o_ref[...] = out.astype(o_ref.dtype)


def _mask_live(mask_ref, b: int, n_k: int, n_n: int):
    """Zero-plane skip predicate: the flat [n_bits, n_k, n_n] SMEM mask
    at (plane b, this grid step's K and N block)."""
    k, n = pl.program_id(2), pl.program_id(1)
    return mask_ref[(b * n_k + k) * n_n + n] != 0


def _kernel(mask_ref, x_ref, p_ref, xs_ref, ws_ref, o_ref, acc_ref, *,
            n_k: int, n_n: int, n_bits: int, signed: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pw = _plane_w(n_bits, signed)
    if signed:  # int8 activations straight onto the MXU
        x, op_t, acc_t = x_ref[...], jnp.int8, jnp.int32
    else:  # 0..255 activations: exact as bf16 (8 significant bits)
        x = x_ref[...].astype(jnp.int32).astype(jnp.float32).astype(
            jnp.bfloat16)
        op_t, acc_t = jnp.bfloat16, jnp.float32
    packed = p_ref[...].astype(jnp.int32)  # (bk, bn) bytes: all planes
    for b in range(n_bits):  # bit-serial: one plane per MXU pass
        @pl.when(_mask_live(mask_ref, b, n_k, n_n))  # zero-plane skip
        def _plane(b=b):
            plane = (packed >> b) & 1  # in-kernel unpack: shift+mask
            if signed:
                plane = plane.astype(op_t)
            else:
                plane = plane.astype(jnp.float32).astype(op_t)
            part = jnp.dot(x, plane, preferred_element_type=acc_t)
            acc_ref[...] += pw[b] * part.astype(jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        _store_out(o_ref, acc_ref, xs_ref, ws_ref)


def _kernel_a4(mask_ref, x_ref, we_ref, wo_ref, xs_ref, ws_ref, o_ref,
               acc_ref, *, n_k: int, n_n: int, n_bits: int, signed: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pw = _plane_w(n_bits, signed)
    xb = x_ref[...].astype(jnp.int32)  # (bm, bk2) bytes: 2 elements each
    if signed:
        xe = ((xb & 0xF) ^ 8) - 8  # in-kernel unpack + 4-bit sign extend
        xo = ((xb >> 4) ^ 8) - 8
    else:
        xe = xb & 0xF  # unsigned nibbles: plain shift+mask unpack
        xo = xb >> 4
    xe, xo = xe.astype(jnp.int8), xo.astype(jnp.int8)  # both fit int8
    we = we_ref[...].astype(jnp.int32)  # even K rows pair with low nibbles
    wo = wo_ref[...].astype(jnp.int32)
    for b in range(n_bits):  # bit-serial: two half-K MXU passes per plane
        @pl.when(_mask_live(mask_ref, b, n_k, n_n))  # zero-plane skip
        def _plane(b=b):
            part = jnp.dot(xe, ((we >> b) & 1).astype(jnp.int8),
                           preferred_element_type=jnp.int32)
            part += jnp.dot(xo, ((wo >> b) & 1).astype(jnp.int8),
                            preferred_element_type=jnp.int32)
            acc_ref[...] += pw[b] * part

    @pl.when(k == n_k - 1)
    def _epilogue():
        _store_out(o_ref, acc_ref, xs_ref, ws_ref)


def plane_block_mask(planes: jax.Array, bk: int, bn: int) -> jax.Array:
    """[n_bits, K/bk, N/bn] int8 occupancy of each plane tile — compute
    once at weight-load time and pass as ``plane_mask``.

    ``planes`` is the unpacked [n_bits, K, N] {0,1} layout (use
    ref.unpack_bitplanes_bytes first when starting from byte-packed)."""
    n_bits, K, N = planes.shape
    p = planes.reshape(n_bits, K // bk, bk, N // bn, bn)
    return (p.sum(axis=(2, 4)) > 0).astype(jnp.int8)


def _grid_call(kernel, *, grid, in_blocks, bm, bn, out_shape, interpret):
    """pallas_call over (m, n, k) with the flat plane mask scalar-
    prefetched into SMEM and the scales as (1, 1) SMEM / (1, bn) rows."""
    in_specs = [pl.BlockSpec(shape, imap) for shape, imap in in_blocks]
    in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM),
                 pl.BlockSpec((1, bn), lambda m, n, k, mask: (0, n))]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda m, n, k, mask: (m, n)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )


def _flat_mask(plane_mask, packed, n_bits: int, bk: int, bn: int,
               expect: tuple) -> jax.Array:
    if plane_mask is None:
        plane_mask = plane_block_mask(unpack_bitplanes_bytes(packed, n_bits),
                                      bk, bn)
    assert plane_mask.shape == expect, (plane_mask.shape, expect)
    return plane_mask.astype(jnp.int32).reshape(-1)


@functools.partial(
    jax.jit, static_argnames=("n_bits", "bm", "bn", "bk", "out_dtype",
                              "interpret", "signed")
)
def bitserial_matmul(
    x_q: jax.Array,  # [M, K] int8 (signed) or uint8 (unsigned) activations
    planes: jax.Array,  # [K, N] uint8 byte-packed, or [n_bits, K, N] {0,1}
    x_scale: jax.Array,  # scalar f32
    w_scale: jax.Array,  # [N] f32
    plane_mask: jax.Array | None = None,  # [n_bits, Kp/bk, Np/bn] int8
    *,
    interpret: bool,
    n_bits: int | None = None,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    out_dtype=jnp.float32,
    signed: bool = True,  # False: unsigned planes (MSB weight +2^(n-1))
) -> jax.Array:
    if planes.ndim == 3:  # legacy unpacked planes: re-pack to bytes
        n_bits = planes.shape[0]
        packed = pack_bitplanes_bytes(
            jnp.sum(planes.astype(jnp.int32)
                    << jnp.arange(n_bits, dtype=jnp.int32)[:, None, None],
                    axis=0), n_bits)
    else:
        n_bits = 8 if n_bits is None else n_bits
        packed = planes.astype(jnp.uint8)
    K, N = packed.shape
    M = x_q.shape[0]
    assert x_q.shape[1] == K
    (bm, bn, bk), (Mp, Np, Kp) = _blocks(M, N, K, bm, bn, bk)
    assert signed or bk * 255 < _BF16_EXACT, bk
    x_q = _pad2(x_q.astype(jnp.int8 if signed else jnp.uint8), Mp, Kp)
    packed = _pad2(packed, Kp, Np)
    xs, ws = _scales(x_scale, w_scale, N, Np)
    n_k, n_n = Kp // bk, Np // bn
    mask = _flat_mask(plane_mask, packed, n_bits, bk, bn, (n_bits, n_k, n_n))

    out = _grid_call(
        functools.partial(_kernel, n_k=n_k, n_n=n_n, n_bits=n_bits,
                          signed=signed),
        grid=(Mp // bm, n_n, n_k),
        in_blocks=[((bm, bk), lambda m, n, k, mask: (m, k)),
                   ((bk, bn), lambda m, n, k, mask: (k, n))],
        bm=bm, bn=bn, out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        interpret=interpret,
    )(mask, x_q, packed, xs, ws)
    return out[:M, :N]


@functools.partial(
    jax.jit, static_argnames=("n_bits", "bm", "bn", "bk2", "out_dtype",
                              "interpret", "signed")
)
def bitserial_matmul_a4(
    x_packed: jax.Array,  # [M, ceil(K/2)] uint8 nibble-packed activations
    planes: jax.Array,  # [K, N] uint8 byte-packed weight planes
    x_scale: jax.Array,  # scalar f32
    w_scale: jax.Array,  # [N] f32
    plane_mask: jax.Array | None = None,  # [n_bits, K2p/bk2, Np/bn] int8
    *,
    interpret: bool,
    n_bits: int = 4,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk2: int = DEFAULT_BK // 2,
    out_dtype=jnp.float32,
    signed: bool = True,  # False: unsigned nibbles + unsigned plane weights
) -> jax.Array:
    """W4A4 bit-serial GEMM with byte-packed *activations* and weights.

    ``x_packed`` comes from ref.pack_activation_nibbles (2 elements/byte);
    ``planes`` from ref.pack_bitplanes_bytes.  Each of the ``n_bits`` weight
    planes costs two MXU passes over half of K (even/odd nibble streams),
    so FLOPs scale with the plane count while both operand tiles move half
    the VMEM bytes of the W8A8 byte-packed kernel.  The even/odd weight
    rows are split outside the kernel (a strided in-kernel slice does not
    lower on the TPU), so each stream is its own (bk2, bn) block.
    """
    M, K2 = x_packed.shape
    K, N = planes.shape
    if K < 2 * K2:  # odd-K weights: pad the dangling row (nibble is zero)
        planes = jnp.pad(planes, ((0, 2 * K2 - K), (0, 0)))
    (bm, bn, bk2), (Mp, Np, K2p) = _blocks(M, N, K2, bm, bn, bk2)
    x_packed = _pad2(x_packed, Mp, K2p)
    planes = _pad2(planes.astype(jnp.uint8), 2 * K2p, Np)
    xs, ws = _scales(x_scale, w_scale, N, Np)
    n_k, n_n = K2p // bk2, Np // bn
    mask = _flat_mask(plane_mask, planes, n_bits, 2 * bk2, bn,
                      (n_bits, n_k, n_n))

    out = _grid_call(
        functools.partial(_kernel_a4, n_k=n_k, n_n=n_n, n_bits=n_bits,
                          signed=signed),
        grid=(Mp // bm, n_n, n_k),
        in_blocks=[((bm, bk2), lambda m, n, k, mask: (m, k)),
                   ((bk2, bn), lambda m, n, k, mask: (k, n)),
                   ((bk2, bn), lambda m, n, k, mask: (k, n))],
        bm=bm, bn=bn, out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        interpret=interpret,
    )(mask, x_packed, planes[0::2], planes[1::2], xs, ws)
    return out[:M, :N]
