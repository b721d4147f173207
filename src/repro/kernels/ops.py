"""Public jit'd wrappers around the Pallas kernels.

Platform policy, decided here and nowhere else: on a TPU the kernels run
compiled; the Pallas interpreter runs only where the platform is the CPU
(:func:`interpret_mode`).  Off the TPU, callers that are on the hot path
(models, serving) use the XLA fallback (`*_xla`) which lowers to plain
dot — numerically identical, fast on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.bitserial_matmul import bitserial_matmul as _bitserial_pallas
from repro.kernels.bitserial_matmul import bitserial_matmul_a4 as _bitserial_a4_pallas
from repro.kernels.quant_matmul import quant_matmul as _quant_pallas

__all__ = [
    "on_tpu",
    "interpret_mode",
    "quant_matmul",
    "bitserial_matmul",
    "bitserial_matmul_a4",
    "bitserial_matmul_exact",
    "pack_weights",
    "pack_activations",
    "quant_matmul_xla",
    "flash_attention",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Whether Pallas kernels run through the interpreter: only on CPU."""
    return jax.default_backend() == "cpu"


def pack_weights(w_q: jax.Array, n_bits: int = 8) -> jax.Array:
    """Weight-load-time transpose to bit-plane layout (the TMU step).

    Returns the dense **byte-packed** format ([K, N] uint8, bit b == plane
    b): 8x smaller than the unpacked [n_bits, K, N] plane stack, unpacked
    per tile in-kernel.  Pass the same ``n_bits`` to
    :func:`bitserial_matmul` (the MSB plane carries the -2^(n-1) weight)."""
    return ref.pack_bitplanes_bytes(w_q, n_bits)


@functools.partial(jax.jit, static_argnames=("prefer_pallas",))
def quant_matmul(x_q, w_q, x_scale, w_scale, bias=None, *, prefer_pallas: bool = False):
    """W8A8 GEMM with fused dequant epilogue."""
    if prefer_pallas or on_tpu():
        return _quant_pallas(x_q, w_q, x_scale, w_scale, bias,
                             interpret=interpret_mode())
    return ref.quant_matmul_ref(x_q, w_q, x_scale, w_scale, bias)


quant_matmul_xla = jax.jit(ref.quant_matmul_ref)


@functools.partial(jax.jit, static_argnames=("causal", "prefer_pallas"))
def flash_attention(q, k, v, *, causal: bool = True,
                    prefer_pallas: bool = False):
    """Tiled attention: Pallas kernel on TPU (VMEM online softmax), the
    naive oracle elsewhere (models/layers.py keeps its own scan-based
    fallback for the banded/cached paths)."""
    from repro.kernels.flash_attention import flash_attention as _fa
    if prefer_pallas or on_tpu():
        return _fa(q, k, v, causal=causal, interpret=interpret_mode())
    return ref.flash_attention_ref(q, k, v, causal=causal)


def pack_activations(x_q: jax.Array) -> jax.Array:
    """Nibble-pack 4-bit activations (2 elements/byte) for the W4A4 kernel
    — the activation-side counterpart of :func:`pack_weights`."""
    return ref.pack_activation_nibbles(x_q)


@functools.partial(jax.jit, static_argnames=("k", "prefer_pallas"))
def bitserial_matmul_a4(x_packed, planes, x_scale, w_scale, *, k: int,
                        prefer_pallas: bool = False):
    """W4A4 GEMM: nibble-packed activations x byte-packed 4-bit weight
    planes; 2 MXU passes per plane (half-K each), half the operand bytes.
    ``k`` is the unpacked inner dimension."""
    if prefer_pallas or on_tpu():
        return _bitserial_a4_pallas(x_packed, planes, x_scale, w_scale,
                                    n_bits=4, interpret=interpret_mode())
    x_q = ref.unpack_activation_nibbles(x_packed, k)
    return ref.bitserial_matmul_ref(
        x_q, ref.unpack_bitplanes_bytes(planes, 4), x_scale, w_scale)


def bitserial_matmul_exact(x_q, planes, *, n_bits: int,
                           w4a4: bool = False):
    """Exact unsigned-integer bit-serial GEMM through the Pallas kernel —
    the backend-registry entry point (``core/backends.py`` ``pallas``).

    Unsigned plane weights (MSB carries +2^(n-1), matching the packed
    word engine's operand convention), no dequant epilogue: the int32
    accumulator comes back verbatim (``out_dtype=int32`` skips the lossy
    float32 round-trip), so results are byte-comparable against the host
    reference.  ``x_q`` holds unsigned activations below 256.
    ``w4a4=True`` takes nibble-packed activations (:func:`pack_activations`)
    through the half-K W4A4 kernel.  The compiled kernel on TPU, the
    interpreter on CPU (:func:`interpret_mode`)."""
    interp = interpret_mode()
    if w4a4:
        return _bitserial_a4_pallas(x_q, planes, 1.0, 1.0, n_bits=n_bits,
                                    out_dtype=jnp.int32, signed=False,
                                    interpret=interp)
    return _bitserial_pallas(x_q, planes, 1.0, 1.0, n_bits=n_bits,
                             out_dtype=jnp.int32, signed=False,
                             interpret=interp)


@functools.partial(jax.jit, static_argnames=("n_bits", "prefer_pallas"))
def bitserial_matmul(x_q, planes, x_scale, w_scale, *, n_bits: int | None = None,
                     prefer_pallas: bool = False):
    """Bit-serial (plane-decomposed) GEMM; cost scales with the plane count.

    ``planes`` is either the byte-packed [K, N] uint8 format from
    :func:`pack_weights` (pass its ``n_bits``) or the legacy unpacked
    [n_bits, K, N] {0,1} stack (``n_bits`` inferred)."""
    if planes.ndim == 3:
        n_bits = planes.shape[0]
        unpacked = planes
    else:
        n_bits = 8 if n_bits is None else n_bits
        unpacked = ref.unpack_bitplanes_bytes(planes, n_bits)
    if prefer_pallas or on_tpu():
        return _bitserial_pallas(x_q, planes, x_scale, w_scale, n_bits=n_bits,
                                 interpret=interpret_mode())
    return ref.bitserial_matmul_ref(x_q, unpacked, x_scale, w_scale)
