"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each ``*_ref`` is the mathematical definition the kernel must match —
tests/test_kernels.py sweeps shapes/dtypes and asserts allclose (exact for
integer paths).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "quant_matmul_ref",
    "pack_bitplanes",
    "pack_bitplanes_bytes",
    "unpack_bitplanes_bytes",
    "pack_activation_nibbles",
    "unpack_activation_nibbles",
    "bitserial_matmul_ref",
    "flash_attention_ref",
]


def quant_matmul_ref(
    x_q: jax.Array,  # [M, K] int8
    w_q: jax.Array,  # [K, N] int8
    x_scale: jax.Array | float = 1.0,  # scalar
    w_scale: jax.Array | None = None,  # [N] or scalar
    bias: jax.Array | None = None,  # [N] f32
) -> jax.Array:
    """W8A8 GEMM: int32 accumulate, per-channel dequant epilogue -> f32."""
    acc = jnp.dot(
        x_q.astype(jnp.int32), w_q.astype(jnp.int32),
        preferred_element_type=jnp.int32,
    )
    out = acc.astype(jnp.float32) * jnp.asarray(x_scale, jnp.float32)
    if w_scale is not None:
        out = out * jnp.asarray(w_scale, jnp.float32)[None, :]
    if bias is not None:
        out = out + bias[None, :]
    return out


def pack_bitplanes(w_q: jax.Array, n_bits: int = 8) -> jax.Array:
    """int8 weights -> [n_bits, K, N] {0,1} planes, two's complement
    (MSB plane carries weight -2^(n-1)).  The TPU analogue of the paper's
    transposed (bit-line) layout: serial over planes, parallel over the tile.
    """
    w = w_q.astype(jnp.int32) & ((1 << n_bits) - 1)
    shifts = jnp.arange(n_bits, dtype=jnp.int32).reshape((n_bits,) + (1,) * w_q.ndim)
    return ((w[None] >> shifts) & 1).astype(jnp.int8)


def pack_bitplanes_bytes(w_q: jax.Array, n_bits: int = 8) -> jax.Array:
    """int8 weights -> [K, N] uint8 *byte-packed* planes: bit ``b`` of each
    byte is plane ``b`` (two's complement over ``n_bits``).

    This is the dense storage format for the bit-serial Pallas kernel: one
    byte carries all (up to 8) planes of an element, so the kernel streams
    8x less VMEM traffic than the unpacked [n_bits, K, N] int8 layout and
    unpacks planes with a shift+mask per MXU pass, in-kernel.
    """
    assert 1 <= n_bits <= 8, n_bits
    return (w_q.astype(jnp.int32) & ((1 << n_bits) - 1)).astype(jnp.uint8)


def unpack_bitplanes_bytes(packed: jax.Array, n_bits: int = 8) -> jax.Array:
    """[K, N] uint8 byte-packed -> [n_bits, K, N] {0,1} int8 planes
    (inverse of :func:`pack_bitplanes_bytes`; oracle/XLA-path format)."""
    return pack_bitplanes(packed.astype(jnp.int32), n_bits)


def pack_activation_nibbles(x_q: jax.Array) -> jax.Array:
    """int8 4-bit activations [M, K] -> [M, ceil(K/2)] uint8: two elements
    per byte, even element in the low nibble (two's complement over 4 bits).

    Byte-packing extended to the *activation* operand (W4A4): the kernel
    streams half the activation bytes and recovers each element in-kernel
    with a shift/mask + sign-extend, paying two half-K MXU passes per
    weight plane — same MACs, half the VMEM traffic on both operands.
    """
    if x_q.shape[-1] % 2:
        x_q = jnp.pad(x_q, ((0, 0), (0, 1)))
    lo = x_q[:, 0::2].astype(jnp.int32) & 0xF
    hi = x_q[:, 1::2].astype(jnp.int32) & 0xF
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_activation_nibbles(packed: jax.Array, K: int) -> jax.Array:
    """Inverse of :func:`pack_activation_nibbles` (oracle path): [M, K2]
    uint8 -> [M, K] int8 with 4-bit sign extension."""
    b = packed.astype(jnp.int32)
    even = ((b & 0xF) ^ 8) - 8
    odd = ((b >> 4) ^ 8) - 8
    full = jnp.stack([even, odd], axis=-1).reshape(b.shape[0], -1)
    return full[:, :K].astype(jnp.int8)


def plane_weights(n_bits: int) -> jax.Array:
    """Per-plane scale: [1, 2, 4, ..., -2^(n-1)] (two's complement)."""
    w = [1 << b for b in range(n_bits - 1)] + [-(1 << (n_bits - 1))]
    return jnp.asarray(w, jnp.int32)


def bitserial_matmul_ref(
    x_q: jax.Array,  # [M, K] int8 activations
    planes: jax.Array,  # [n_bits, K, N] {0,1} int8
    x_scale: jax.Array | float = 1.0,
    w_scale: jax.Array | None = None,  # [N] or scalar
) -> jax.Array:
    """Bit-serial GEMM: out = sum_b weight_b * (x @ plane_b), dequantized.

    Bit-exact with quant_matmul_ref when planes = pack_bitplanes(w_q).
    """
    n_bits = planes.shape[0]
    pw = plane_weights(n_bits)
    acc = jnp.zeros((x_q.shape[0], planes.shape[2]), jnp.int32)
    for b in range(n_bits):
        part = jnp.dot(
            x_q.astype(jnp.int32), planes[b].astype(jnp.int32),
            preferred_element_type=jnp.int32,
        )
        acc = acc + pw[b] * part
    out = acc.astype(jnp.float32) * jnp.asarray(x_scale, jnp.float32)
    if w_scale is not None:
        out = out * jnp.asarray(w_scale, jnp.float32)[None, :]
    return out


def flash_attention_ref(
    q: jax.Array,  # [B, H, Tq, D]
    k: jax.Array,  # [B, Hkv, Tk, D]
    v: jax.Array,  # [B, Hkv, Tk, D]
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
) -> jax.Array:
    """GQA attention oracle (naive, materializes scores)."""
    B, H, Tq, D = q.shape
    Hkv = k.shape[1]
    groups = H // Hkv
    qg = q.reshape(B, Hkv, groups, Tq, D)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k) / jnp.sqrt(D).astype(q.dtype)
    qpos = jnp.arange(Tq)[:, None] + q_offset
    kpos = jnp.arange(k.shape[2])[None, :]
    mask = jnp.ones((Tq, k.shape[2]), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v)
    return out.reshape(B, H, Tq, D)
