"""Backend registry for the packed bit-serial hot path (PR 10).

Three execution bodies exist for the packed MAC+reduce that
``bitserial.packed_dot_words`` exposes: the exact numpy host walk, the
bucketed-jit decoded-lane kernel, and the Pallas bit-serial GEMM
(``kernels/bitserial_matmul.py`` — previously only reachable as a
standalone matmul).  This module makes the choice explicit: ONE registry
of :class:`Backend` entries, looked up by name everywhere an
``engine=`` string used to be interpreted ad hoc.

Contract
--------

* **Backends re-time execution, never the model.**  A backend's
  ``dot_words`` returns VALUES only; modeled cycles are charged by
  ``bitserial.packed_dot_words`` from the unchanged §III formula
  (``bitserial.dot_cycles``) before dispatch, so cycle counts are
  bit-identical across backends *by construction*.
* **Byte-identity.**  Every registered backend must reproduce the host
  reference exactly (tests/test_backends.py runs the differential
  conformance harness over the full operating envelope).  A backend may
  delegate inputs outside its native envelope (capability flags below)
  to the host body on CPU — delegation is counted in
  :func:`dispatch_stats` so tests can assert the native path actually
  ran.  The same counters hold the bytes each backend hands to the
  device and copies back (``pallas``: the padded word grids and the
  padded int32 result).
* **Selection is configuration.**  Precedence at every call site:
  explicit ``engine=`` argument > the plan's ``backend`` field
  (``schedule.plan_layer(backend=...)`` — the same plan-decision idiom
  as sparsity/overlap/integrity/compression) > the ``NC_BACKEND``
  environment variable > ``pallas`` where the platform is a TPU > the
  caller's default (host, or jit for batched Inception forwards).  An
  explicit engine that *contradicts* a backend-carrying plan raises
  (ambiguous).

Registered backends
-------------------

``host``
    The exact numpy bit-serial walk (``bitserial._dot_words_impl``) —
    the reference every other backend is checked against.  Handles any
    plane width, accumulator width and row layout; zero-operand word
    skipping (``bitserial.ZERO_SKIP``) lives here.
``jit``
    Bucketed compiled decoded-lane kernel: one XLA executable per
    (x planes, w planes, acc, K) bucket (``bitserial.engine_cache_info``
    reports the cache).  Falls back to host when the int32 decode could
    overflow.
``pallas``
    The byte-packed Pallas bit-serial GEMM (in-kernel shift+mask plane
    unpack, zero-plane-block skip; the W4A4 nibble kernel when both
    operands fit 4 planes).  The word grids decode to integer rows on
    the device inside one jitted program with the kernel; rows pad to
    :func:`~repro.core.bitserial.bucket_words` so repeated shapes share
    one executable.  It declares ``layer_calls``: ``nc_conv2d`` hands it
    a layer's whole pass list, so a conv layer's packed operands go to
    the device once, not once per plan tile.  Compiled on a TPU, run through
    the Pallas interpreter on CPU (``kernels/ops.py`` decides).  On CPU,
    inputs outside its native envelope (traced operands, rows sharing
    words — ``K <= 16`` —, > 8 planes, int32-overflow risk,
    non-separable broadcast grids, oversized tiles) delegate to host,
    exactly; on a TPU they raise instead — the device path never
    answers from the host.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import bitserial as bs
from repro.kernels import ops
from repro.kernels import ref as kref

__all__ = [
    "Backend",
    "ENV_VAR",
    "register_backend",
    "registered_backends",
    "get_backend",
    "env_backend",
    "default_backend",
    "resolve_backend",
    "dispatch_stats",
    "dispatch_stats_clear",
]

ENV_VAR = "NC_BACKEND"


@dataclasses.dataclass(frozen=True)
class Backend:
    """One registered execution body for the packed bit-serial dot.

    The capability flags describe the *native* envelope; inputs outside
    it are delegated to the host body on CPU (still byte-exact — see the
    module contract) and raise on a TPU.
    ``dot_words(xw, ww, *, K, acc_bits, materialize, passes)`` returns
    the integer row values only; cycles are charged by the
    caller (``bitserial.packed_dot_words``) so backends cannot perturb
    the cycle model.  ``passes`` is the number of plan passes the call
    serves, for the dispatch counters.

    ``layer_calls`` declares that one call may carry a whole layer's
    pass list: ``nc_conv2d``'s unchecked loop then gathers every row
    tile's packed windows and every filter tile's packed columns and
    dispatches once per layer (more calls only where an operand grid
    would pass ``max_lane_words``), instead of once per plan tile.

    ``emulates_walks`` declares that the backend steps the bit-level
    walks outside the dot (the pools' and the §IV-D min/max tree) in
    numpy, as ``host`` does for the byte-exact reference; on the others
    ``nc_maxpool2d`` and ``nc_avgpool2d`` run as one compiled
    reduce-window call and ``nc_minmax`` as one exact integer reduction,
    and each charges its walk's closed-form cycles."""

    name: str
    # accumulator widths executed natively (None = any)
    acc_bits: tuple[int, ...] | None
    w4a4: bool  # dedicated nibble-packed path for <=4-plane operands
    compressed_planes: bool  # consumes CSR-reconstructed filter tiles
    integrity: bool  # safe under the ABFT checked/fault-injected path
    # cap on one operand's word-grid size (None = unbounded)
    max_lane_words: int | None
    layer_calls: bool  # takes a layer's whole pass list in one call
    emulates_walks: bool  # pools and min/max step their bit-level walks
    dot_words: Callable[..., np.ndarray]

    def supports_acc(self, acc_bits: int) -> bool:
        return self.acc_bits is None or acc_bits in self.acc_bits


_REGISTRY: dict[str, Backend] = {}
# per-backend dispatch counters, in the order of _DISPATCH_KEYS
_DISPATCH_KEYS = ("native", "fallback", "bytes_to_device",
                  "bytes_from_device", "passes")
_DISPATCH: dict[str, list[int]] = {}


def register_backend(backend: Backend) -> Backend:
    _REGISTRY[backend.name] = backend
    _DISPATCH.setdefault(backend.name, [0] * len(_DISPATCH_KEYS))
    return backend


def registered_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_backend(name: str, source: str = "engine") -> Backend:
    """Look up a backend by name; unknown names raise a :class:`ValueError`
    that names every registered backend (the one error surfaced for a bad
    ``engine=`` string and a bad ``NC_BACKEND`` alike)."""
    backend = _REGISTRY.get(name)
    if backend is None:
        raise ValueError(
            f"unknown backend {name!r} (from {source}); registered "
            f"backends: {', '.join(registered_backends())}")
    return backend


def env_backend() -> str | None:
    """The ``NC_BACKEND`` environment selection, validated, or None when
    unset/empty."""
    name = os.environ.get(ENV_VAR)
    if not name:
        return None
    return get_backend(name, source=f"{ENV_VAR} environment variable").name


def default_backend() -> str:
    """``NC_BACKEND`` when set (validated), else ``pallas`` on a TPU,
    else the host reference."""
    return resolve_backend()


def resolve_backend(explicit: str | None = None,
                    plan_backend: str | None = None,
                    default: str | None = None) -> str:
    """Resolve the backend name by the standing precedence: explicit
    ``engine=`` > plan's ``backend`` field > ``NC_BACKEND`` > ``pallas``
    on a TPU > ``default`` (the host reference when no default is
    given).  Callers raise on the ambiguous explicit-vs-plan combination
    *before* resolving; here an explicit name simply wins (they are
    checked equal upstream)."""
    if explicit is not None:
        return get_backend(explicit).name
    if plan_backend is not None:
        return get_backend(plan_backend, source="plan backend").name
    return (env_backend() or ("pallas" if ops.on_tpu() else None)
            or default or "host")


def dispatch_stats() -> dict[str, dict[str, int]]:
    """Per-backend dispatch counters since the last clear:
    ``{name: {"native": n, "fallback": m, "bytes_to_device": a,
    "bytes_from_device": b, "passes": p}}`` — ``fallback`` counts calls
    delegated to the host body (inputs outside the native envelope); the
    byte counts are the operands handed to the device and the results
    copied back, padding included (``pallas`` only: the other bodies run
    on the host or keep their transfers inside XLA); ``passes`` counts
    the plan passes that native calls served, so ``passes / native`` is
    passes per call (1 per tile; a layer's tiles on a ``layer_calls``
    backend)."""
    return {name: dict(zip(_DISPATCH_KEYS, c))
            for name, c in _DISPATCH.items()}


def dispatch_stats_clear() -> None:
    for c in _DISPATCH.values():
        c[:] = [0] * len(c)


def _note(name: str, native: bool, to_device: int = 0,
          from_device: int = 0, passes: int = 1) -> None:
    c = _DISPATCH[name]
    c[0 if native else 1] += 1
    c[2] += to_device
    c[3] += from_device
    if native:
        c[4] += passes


# ---------------------------------------------------------------------------
# host — the exact reference body
# ---------------------------------------------------------------------------
def _host_dot_words(xw, ww, *, K: int, acc_bits: int,
                    materialize: bool = True, passes: int = 1):
    _note("host", native=True, passes=passes)
    return bs._dot_words_impl(xw, ww, K=K, acc_bits=acc_bits)


# ---------------------------------------------------------------------------
# jit — bucketed compiled decoded-lane kernel (cache lives in bitserial so
# engine_cache_info/engine_cache_clear keep reporting it)
# ---------------------------------------------------------------------------
def _jit_dot_words(xw, ww, *, K: int, acc_bits: int, materialize: bool = True,
                   passes: int = 1):
    if bs._is_traced(xw, ww):
        _note("jit", native=False)
        return bs._dot_words_impl(xw, ww, K=K, acc_bits=acc_bits)
    max_sum = K * ((1 << xw.shape[0]) - 1) * ((1 << ww.shape[0]) - 1)
    if max_sum >= (1 << 31) and not jax.config.jax_enable_x64:
        # the traced decode saturates at int32 — stay exact on host
        _note("jit", native=False)
        return bs._dot_words_impl(xw, ww, K=K, acc_bits=acc_bits)
    key = (int(xw.shape[0]), int(ww.shape[0]), acc_bits, K)
    fn = bs._ENGINE_CACHE.get(key)
    if fn is None:
        fn = jax.jit(functools.partial(bs._dot_words_decoded, K=K,
                                       acc_bits=acc_bits))
        bs._ENGINE_CACHE[key] = fn
    _note("jit", native=True, passes=passes)
    out = fn(jnp.asarray(xw), jnp.asarray(ww))
    return np.asarray(out) if materialize else out


# ---------------------------------------------------------------------------
# pallas — the byte-packed Pallas GEMM as a word-grid adapter
# ---------------------------------------------------------------------------
def _pallas_fallback_reason(xw, ww, *, K: int, acc_bits: int,
                            backend: Backend) -> str | None:
    if bs._is_traced(xw, ww):
        return "traced operands"
    nx, nw = int(xw.shape[0]), int(ww.shape[0])
    if nx > 8 or nw > 8:
        return "more than 8 bit planes"
    if not backend.supports_acc(acc_bits):
        return f"acc_bits={acc_bits} outside {backend.acc_bits}"
    P, _, r = bs._row_layout(K)
    if r != 1:
        return "rows share words (K <= 16)"
    max_sum = K * ((1 << nx) - 1) * ((1 << nw) - 1)
    if max_sum >= (1 << 31) and not jax.config.jax_enable_x64:
        return "int32 accumulator overflow"
    cap = backend.max_lane_words
    if cap is not None and max(xw.size, ww.size) > cap:
        return "operand grid exceeds max_lane_words"
    gx, gw = xw.shape[1:-1], ww.shape[1:-1]
    if len(gx) != len(gw):
        return "grid ranks differ"
    if any(a > 1 and b > 1 for a, b in zip(gx, gw)):
        return "non-separable broadcast grids"
    return None


def _decode_rows_dev(words, K: int):
    """Row-aligned word grid ``(n, R, wpr)`` uint32 -> ``(R, K)`` int32
    lane values, on the device (P >= 32 layouts: one row per grid row)."""
    n, R, wpr = words.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & 1  # (n, R, wpr, 32)
    bits = bits.reshape(n, R, wpr * 32)[..., :K].astype(jnp.int32)
    weights = (jnp.int32(1) << jnp.arange(n, dtype=jnp.int32))[:, None, None]
    return (bits * weights).sum(axis=0)


def _pallas_exact_impl(xf, wf, *, K: int, w4a4: bool):
    """Decode both operands' word grids and run the exact unsigned Pallas
    GEMM: ``(Rx, K) @ (K, Rw)`` int32, one jitted program per shape."""
    X = _decode_rows_dev(xf, K)
    planes = _decode_rows_dev(wf, K).T.astype(jnp.uint8)  # byte == planes
    n_w = int(wf.shape[0])
    if w4a4:
        x_nib = kref.pack_activation_nibbles(X.astype(jnp.int8))
        return ops.bitserial_matmul_exact(x_nib, planes, n_bits=n_w,
                                          w4a4=True)
    return ops.bitserial_matmul_exact(X.astype(jnp.uint8), planes,
                                      n_bits=n_w)


_pallas_exact = jax.jit(_pallas_exact_impl, static_argnames=("K", "w4a4"))


def _pad_rows(words: np.ndarray, rows: int) -> np.ndarray:
    pad = rows - words.shape[1]
    return np.pad(words, ((0, 0), (0, pad), (0, 0))) if pad else words


def _pallas_dot_words(xw, ww, *, K: int, acc_bits: int,
                      materialize: bool = True, passes: int = 1):
    """Adapter: flatten the two row-aligned word grids, pad their rows to
    the bucketed-jit engine's buckets, decode and run the byte-packed
    Pallas kernel in one device program (the W4A4 nibble kernel when
    both operands fit 4 planes), and scatter the exact int32 accumulator
    back into the broadcast grid.

    Profiler spans, one each per call: ``nc.pallas.launch`` (envelope
    check, flatten, pad, enqueue), ``nc.pallas.wait`` (the host blocked
    on the device run and the copy back) and ``nc.pallas.scatter``
    (slice, widen to int64, scatter)."""
    backend = _REGISTRY["pallas"]
    with TraceAnnotation("nc.pallas.launch"):
        reason = _pallas_fallback_reason(xw, ww, K=K, acc_bits=acc_bits,
                                         backend=backend)
        if reason is None:
            nx, nw = int(xw.shape[0]), int(ww.shape[0])
            gx, gw = xw.shape[1:-1], ww.shape[1:-1]
            xf = np.asarray(xw, np.uint32).reshape(nx, -1, xw.shape[-1])
            wf = np.asarray(ww, np.uint32).reshape(nw, -1, ww.shape[-1])
            Rx, Rw = xf.shape[1], wf.shape[1]
            w4a4 = backend.w4a4 and nx <= 4 and nw <= 4 and K >= 2
            xf = _pad_rows(xf, bs.bucket_words(Rx))
            wf = _pad_rows(wf, bs.bucket_words(Rw))
            out = _pallas_exact(xf, wf, K=K, w4a4=w4a4)
    if reason is not None:
        if ops.on_tpu():
            raise ValueError(f"pallas backend: input outside the native "
                             f"envelope ({reason}); the TPU path has no "
                             f"host fallback")
        _note("pallas", native=False)
        return bs._dot_words_impl(xw, ww, K=K, acc_bits=acc_bits)
    _note("pallas", native=True, to_device=xf.nbytes + wf.nbytes,
          from_device=out.nbytes, passes=passes)
    with TraceAnnotation("nc.pallas.wait"):
        O = np.asarray(out)  # exact int32 accumulator, padded

    # scatter back into the broadcast grid: each grid axis is owned by at
    # most one operand (separability checked above), so interleaving the
    # (gx_i, gw_i) axis pairs and merging each pair (one side is 1)
    # reproduces np.broadcast_shapes(gx, gw)
    with TraceAnnotation("nc.pallas.scatter"):
        n_axes = len(gx)
        O = O[:Rx, :Rw].astype(np.int64).reshape(tuple(gx) + tuple(gw))
        O = O.transpose([a for i in range(n_axes) for a in (i, n_axes + i)])
        return O.reshape(np.broadcast_shapes(gx, gw))


register_backend(Backend(
    name="host", acc_bits=None, w4a4=True, compressed_planes=True,
    integrity=True, max_lane_words=None, layer_calls=False,
    emulates_walks=True, dot_words=_host_dot_words))
register_backend(Backend(
    name="jit", acc_bits=None, w4a4=True, compressed_planes=True,
    integrity=True, max_lane_words=None, layer_calls=False,
    emulates_walks=False, dot_words=_jit_dot_words))
register_backend(Backend(
    name="pallas", acc_bits=(24, 32), w4a4=True,
    compressed_planes=True, integrity=True, max_lane_words=1 << 22,
    layer_calls=True, emulates_walks=False, dot_words=_pallas_dot_words))
