"""Bit-serial in-SRAM arithmetic — functional, bit-exact emulation.

This module is the paper's §III (Neural Cache Arithmetic) as executable JAX.
Data lives in the *transposed* layout: an unsigned n-bit tensor becomes n
binary *planes* (LSB first).  Plane axis == word-line axis; every other axis
is a bit line.  All element lanes advance in lockstep, exactly like the
SRAM array: one bit-slice per cycle, carry/tag held in per-bit-line latches.

Packed bit-lane engine
----------------------
Every operation runs on a **word-packed** representation
(:class:`PackedPlanes`): 32 element lanes are packed into one ``uint32``
word, so a single bitwise AND/XOR/OR advances 32 lanes at once — the
software analogue of the SRAM array clocking thousands of bit lines per
cycle (and of Xcel-RAM's word-parallel bitwise reorganization).

``PackedPlanes`` resident-format contract
-----------------------------------------
``PackedPlanes`` is the *resident* format of the whole layer pipeline:
``bitserial_mac -> bitserial_reduce -> requantize`` chains stay in packed
word space end to end and never round-trip through
:func:`bitplane_unpack`/:func:`bitplane_pack`.  Two lane layouts share the
``words[(n_planes, n_words)]`` container, selected by ``row_lanes``:

* **flat** (``row_lanes == 0``)::

      words[p, w]  bit l  ==  plane p of lane (w * 32 + l)

  lanes flattened C-order from ``lane_shape``, zero-padded up to a
  multiple of 32.  This is the element-wise layout.

* **row-aligned** (``row_lanes == P > 0``): the last ``lane_shape`` axis
  (length K, the reduce axis) is padded to ``P = next_pow2(K)`` bit
  positions so the §III-D log-tree reduction is a pure word-slice
  (``P >= 32``: ``P/32`` dedicated words per row) or an in-word shift
  (``P < 32``: ``32/P`` rows share one word, each owning a P-bit
  segment).  Rows are the remaining lane axes, flattened C-order.

:func:`shuffle_to_rows` / :func:`shuffle_to_flat` convert between the two
(the software analogue of an in-array lane move) so a MAC result can feed
the reducer without reconstructing integer values: the shuffle is a
C-speed bit-grid gather below the value-plane API, not a
``bitplane_unpack``/``bitplane_pack`` round-trip.  Producers that know
their reduce axis pack row-aligned up front with
``pack_values(x, n, row_align=True)`` and skip even that; the row layout
also makes operand *broadcast* free at word granularity (a window row
packs once and is reused by every filter — see core/nc_layers.py).

Because the full adder, tag predication and selective copy are pure
bitwise ops, lanes never interact across bit positions: carries propagate
across *planes* (held in a packed carry word), never across lanes, so
padding lanes stay zero and results are bit-exact with the per-lane
reference in either layout.

Engine dispatch and the bucketed jit cache
------------------------------------------
The engine has two dispatch modes for the same packed algorithm:

* **concrete operands** (the emulation/test/bench path) run the
  bit-position loops directly on host ``numpy`` words — thousands of
  32-lane bitwise ops cost microseconds and nothing is ever compiled;
* **traced operands** (inside ``jax.jit``) run the same loops under
  ``lax.scan``, so traces stay O(1) in both lane count and bit width and
  the ops compile cleanly into larger jitted pipelines.

For repeated tile work (the conv tiler in core/nc_layers.py), a third
path amortizes compilation: :func:`packed_dot_words` with
``engine="jit"`` looks up a jitted kernel in a **small compilation
cache** keyed by ``(plane counts, acc width, K)`` — the *bucket*.  Word
counts are padded to power-of-two buckets (:func:`bucket_words`) before
entering the jitted kernel, so every tile of a layer (including the
ragged last one) replays the same compiled executable instead of
recompiling per lane shape.  ``engine_cache_info()`` reports the cache
contents.

The ``engine=`` string names an entry in the explicit backend registry
(core/backends.py — ``host``, ``jit``, ``pallas``; an unknown
name raises listing the registered set, ``None`` resolves through the
``NC_BACKEND`` environment variable, then the platform).  Backends return values only;
:func:`packed_dot_words` charges :func:`dot_cycles` before dispatch, so
modeled cycles are bit-identical across backends by construction.

Beyond-paper zero-operand skipping (EIE-style): the host multiply drops
word columns whose 32 lanes all have a zero operand (the product lanes
are provably zero, exactly what the tag latch would predicate off);
``SKIP_STATS`` accounts skipped lanes/words for the cycle notes.  Modeled
cycles are *never* changed by skipping — the SRAM clocks every bit-slice.

Cycle-model invariants (unchanged by packing — the packed engine models
the *same* hardware, it is only a faster emulation):

    add        : n + 1                     (§III-B)
    multiply   : n^2 + 5n - 2              (§III-C)
    divide     : 1.5 n^2 + 5.5 n           (§III-C)
    reduction  : log2(k) x (move + widening add)   (§III-D)

Every operation still returns ``(result_planes, cycles)`` with these
formulas, and :func:`bitserial_reduce` keeps asserting its step-summed
cycles against the closed form.  The public API is unchanged: ops accept
either raw ``{0,1}`` plane tensors (``(n_bits, *lanes)`` uint8) or
:class:`PackedPlanes`, and return the representation they were given.

The emulation is *bit-exact* against integer arithmetic
(tests/test_bitserial.py sweeps this); the cycle counts feed
core/simulator.py.
"""
from __future__ import annotations

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "PackedPlanes",
    "pack_lanes",
    "unpack_lanes",
    "pack_values",
    "unpack_values",
    "shuffle_to_rows",
    "shuffle_to_flat",
    "bitplane_pack",
    "bitplane_unpack",
    "add_cycles",
    "mul_cycles",
    "div_cycles",
    "reduce_cycles",
    "minmax_cycles",
    "dot_cycles",
    "abft_checksums",
    "checksum_cycles",
    "bitserial_add",
    "bitserial_sub",
    "bitserial_multiply",
    "bitserial_mac",
    "bitserial_reduce",
    "bitserial_minmax",
    "selective_copy",
    "bitserial_relu",
    "bitserial_max",
    "packed_dot_words",
    "bucket_words",
    "engine_cache_info",
    "engine_cache_clear",
    "filter_occupancy",
    "CompressedPlanes",
    "SKIP_STATS",
]

_PLANE_DTYPE = jnp.uint8
_WORD = 32
_FULL_WORD = np.uint32(0xFFFFFFFF)
_LITTLE = sys.byteorder == "little"


def _is_traced(*xs) -> bool:
    return any(isinstance(x, jax.core.Tracer) for x in xs)


def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())


def _row_layout(K: int) -> tuple[int, int, int]:
    """Reduce-axis layout: (P, words_per_row, rows_per_word) for K lanes."""
    P = _next_pow2(max(K, 1))
    if P >= _WORD:
        return P, P // _WORD, 1
    return P, 1, _WORD // P


# ---------------------------------------------------------------------------
# Word <-> bit helpers (host side uses C-speed packbits on little-endian).
# ---------------------------------------------------------------------------
def _pack_bits32_np(bits: np.ndarray) -> np.ndarray:
    """(..., 32) {0,1} -> (...,) uint32."""
    bits = np.ascontiguousarray(bits, np.uint8)
    if _LITTLE:
        packed = np.packbits(bits, axis=-1, bitorder="little")
        return packed.view(np.uint32)[..., 0]
    shifts = np.arange(_WORD, dtype=np.uint32)
    return np.bitwise_or.reduce(bits.astype(np.uint32) << shifts, axis=-1)


def _unpack_bits32_np(words: np.ndarray) -> np.ndarray:
    """(...,) uint32 -> (..., 32) uint8."""
    words = np.ascontiguousarray(words, np.uint32)
    if _LITTLE:
        return np.unpackbits(words[..., None].view(np.uint8), axis=-1,
                             bitorder="little")
    shifts = np.arange(_WORD, dtype=np.uint32)
    return ((words[..., None] >> shifts) & 1).astype(np.uint8)


def _pack_bits32_jnp(bits) -> jax.Array:
    shifts = jnp.arange(_WORD, dtype=jnp.uint32)
    return (bits.astype(jnp.uint32) << shifts).sum(axis=-1).astype(jnp.uint32)


def _unpack_bits32_jnp(words) -> jax.Array:
    shifts = jnp.arange(_WORD, dtype=jnp.uint32)
    return ((words[..., None] >> shifts) & jnp.uint32(1)).astype(_PLANE_DTYPE)


def _popcount(w: np.ndarray) -> int:
    if hasattr(np, "bitwise_count"):
        return int(np.bitwise_count(w).sum())
    return int(np.unpackbits(np.ascontiguousarray(w).view(np.uint8)).sum())


# ---------------------------------------------------------------------------
# Transposed (bit-plane) layout — the software analogue of the paper's TMU.
# ---------------------------------------------------------------------------
def bitplane_pack(x, n_bits: int):
    """Pack an unsigned integer tensor into ``n_bits`` binary planes (LSB first).

    Returns shape ``(n_bits, *x.shape)`` with values in {0, 1}.  This is the
    paper's transpose layout: plane index == word line, remaining axes == bit
    lines.
    """
    if _is_traced(x):
        x = x.astype(jnp.uint32)
        shifts = jnp.arange(n_bits, dtype=jnp.uint32)
        planes = (x[None, ...] >> shifts.reshape((n_bits,) + (1,) * x.ndim)) & 1
        return planes.astype(_PLANE_DTYPE)
    x = np.asarray(x).astype(np.uint32)
    shifts = np.arange(n_bits, dtype=np.uint32).reshape((n_bits,) + (1,) * x.ndim)
    return ((x[None, ...] >> shifts) & 1).astype(np.uint8)


def bitplane_unpack(planes, signed: bool = False):
    """Inverse of :func:`bitplane_pack`.  ``signed`` interprets the planes as
    two's complement of width ``planes.shape[0]``."""
    if isinstance(planes, PackedPlanes):
        return unpack_values(planes, signed=signed)
    n = planes.shape[0]
    if _is_traced(planes):
        weights = (jnp.uint32(1) << jnp.arange(n, dtype=jnp.uint32)).reshape(
            (n,) + (1,) * (planes.ndim - 1)
        )
        val = jnp.sum(planes.astype(jnp.uint32) * weights, axis=0).astype(jnp.int64)
        if signed:
            val = jnp.where(planes[-1].astype(bool), val - (1 << n), val)
        return val
    p = np.asarray(planes, np.uint64)
    weights = (np.uint64(1) << np.arange(n, dtype=np.uint64)).reshape(
        (n,) + (1,) * (p.ndim - 1)
    )
    val = (p * weights).sum(axis=0).astype(np.int64)
    if signed:
        val = np.where(p[-1].astype(bool), val - (1 << n), val)
    return val


# ---------------------------------------------------------------------------
# Packed bit-lane container: 32 lanes per uint32 word.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PackedPlanes:
    """Word-packed bit planes (see module docstring for the layout contract).

    ``row_lanes == 0``: flat — ``words[p, w]`` bit ``l`` is plane ``p`` of
    lane ``w * 32 + l`` (lanes flattened C-order from ``lane_shape``,
    zero-padded to a multiple of 32).

    ``row_lanes == P``: row-aligned — the last ``lane_shape`` axis is padded
    to ``P`` (a power of two) bit positions per row; ``P >= 32`` gives
    ``P/32`` words per row, ``P < 32`` packs ``32/P`` rows per word."""

    words: jax.Array  # (n_planes, n_words) uint32
    lane_shape: tuple[int, ...]
    row_lanes: int = 0

    @property
    def n_planes(self) -> int:
        return self.words.shape[0]

    @property
    def n_lanes(self) -> int:
        return int(np.prod(self.lane_shape)) if self.lane_shape else 1

    @property
    def n_words(self) -> int:
        return self.words.shape[1]

    @property
    def n_rows(self) -> int:
        """Row count of the row-aligned layout (reduce groups)."""
        if not self.row_lanes:
            raise ValueError("flat-packed planes have no row structure")
        shape = self.lane_shape[:-1]
        return int(np.prod(shape)) if shape else 1

    def __getitem__(self, idx) -> "PackedPlanes":
        """Plane-axis slicing (lane layout is preserved)."""
        if not isinstance(idx, slice):
            raise TypeError("PackedPlanes supports plane-axis slices only")
        return PackedPlanes(self.words[idx], self.lane_shape, self.row_lanes)


jax.tree_util.register_dataclass(
    PackedPlanes, data_fields=["words"], meta_fields=["lane_shape", "row_lanes"]
)


@dataclasses.dataclass(frozen=True)
class CompressedPlanes:
    """CSR-style per-bit-plane filter store (PR 8, EIE-inspired).

    The sibling of :class:`PackedPlanes` for RESIDENT filters: instead of
    a dense ``(n_planes, n_columns, ...)`` word grid (one column per
    filter), each bit plane keeps only its LIVE columns — the filters
    with at least one set bit in that plane — as a sorted column index
    plus their packed words.  Planes with no set bit anywhere store
    nothing at all; a pruned (all-zero-plane) filter column appears in no
    plane's index.  :meth:`dense` reconstructs the original grid
    byte-identically (round trip asserted by tests/test_sparsity.py), so
    the packed MAC+reduce consumes exactly the words it would have seen
    uncompressed.

    The modeled residency of this store is
    ``mapper.compressed_filter_bytes`` (live-plane payload + per-plane
    live-column bitmap); :attr:`index_bytes` mirrors the bitmap term."""

    column_index: tuple[np.ndarray, ...]  # per plane: sorted int32 live cols
    columns: tuple[np.ndarray, ...]  # per plane: (n_live, *tail) uint32 words
    n_columns: int  # dense column (filter) count
    tail_shape: tuple[int, ...]  # per-column word shape of the dense grid

    @property
    def n_planes(self) -> int:
        return len(self.column_index)

    @property
    def live_planes(self) -> int:
        """Planes with at least one live column (the only ones stored)."""
        return sum(1 for idx in self.column_index if idx.size)

    @property
    def payload_bytes(self) -> int:
        """Bytes of packed words actually stored (live columns only)."""
        return sum(int(c.nbytes) for c in self.columns)

    @property
    def index_bytes(self) -> int:
        """Per-plane live-column bitmap bytes (one bit per filter column,
        byte-rounded, live planes only) — the CSR index overhead."""
        return self.live_planes * (-(-self.n_columns // 8))

    @property
    def nbytes(self) -> int:
        return self.payload_bytes + self.index_bytes

    @classmethod
    def compress(cls, words) -> "CompressedPlanes":
        """Compress a dense per-plane filter word grid ``(n_planes,
        n_columns, ...)`` uint32 (e.g. the packed filter block the engine
        feeds ``packed_dot_words``) into CSR-per-plane form."""
        grid = np.asarray(words, np.uint32)
        if grid.ndim < 2:
            raise ValueError(
                f"expected (n_planes, n_columns, ...) words, got {grid.shape}")
        flat = grid.reshape(grid.shape[0], grid.shape[1], -1)
        live = flat.any(axis=2)  # (n_planes, n_columns)
        index = tuple(np.flatnonzero(live[p]).astype(np.int32)
                      for p in range(grid.shape[0]))
        cols = tuple(np.ascontiguousarray(grid[p, index[p]])
                     for p in range(grid.shape[0]))
        return cls(column_index=index, columns=cols,
                   n_columns=int(grid.shape[1]),
                   tail_shape=tuple(grid.shape[2:]))

    def dense(self) -> np.ndarray:
        """Reconstruct the dense ``(n_planes, n_columns, *tail_shape)``
        word grid, byte-identical to what :meth:`compress` consumed —
        dead columns and dead planes come back as zero words (a zero
        word is the multiply's identity, so consumers are unchanged)."""
        return self.dense_columns(0, self.n_columns)

    def dense_columns(self, start: int, stop: int) -> np.ndarray:
        """Reconstruct columns ``[start, stop)`` of the dense grid — the
        per-tile filter slice the packed engine consumes — without
        materializing the rest (the CSR index is sorted, so the slice is
        two binary searches per plane)."""
        if not (0 <= start <= stop <= self.n_columns):
            raise ValueError(
                f"columns [{start}, {stop}) out of range for "
                f"{self.n_columns}")
        grid = np.zeros((self.n_planes, stop - start) + self.tail_shape,
                        np.uint32)
        for p, (idx, cols) in enumerate(zip(self.column_index, self.columns)):
            if idx.size:
                lo = int(np.searchsorted(idx, start))
                hi = int(np.searchsorted(idx, stop))
                if lo < hi:
                    grid[p, idx[lo:hi] - start] = cols[lo:hi]
        return grid


def _grid_bits_np(flat: np.ndarray, lane_shape: tuple[int, ...],
                  row_align: bool) -> np.ndarray:
    """Arrange per-lane values (any int dtype, all planes at once:
    ``(n, n_lanes)``) into the ``(n, n_words, 32)`` bit-position grid of
    the requested layout (padding positions zero)."""
    n, n_lanes = flat.shape
    if not row_align:
        n_words = max(-(-n_lanes // _WORD), 1)
        grid = np.zeros((n, n_words * _WORD), flat.dtype)
        grid[:, :n_lanes] = flat
        return grid.reshape(n, n_words, _WORD)
    K = lane_shape[-1] if lane_shape else 1
    B = max(n_lanes // max(K, 1), 1)
    P, wpr, r = _row_layout(K)
    if r == 1:
        grid = np.zeros((n, B, wpr * _WORD), flat.dtype)
        grid[:, :, :K] = flat.reshape(n, B, K)
        return grid.reshape(n, B * wpr, _WORD)
    Bp = -(-B // r) * r
    grid = np.zeros((n, Bp, P), flat.dtype)
    grid[:, :B, :K] = flat.reshape(n, B, K)
    return grid.reshape(n, Bp // r, _WORD)


def _ungrid_np(grid: np.ndarray, lane_shape: tuple[int, ...],
               row_lanes: int) -> np.ndarray:
    """Inverse of :func:`_grid_bits_np`: (n, n_words, 32) grid -> (n, lanes)."""
    n = grid.shape[0]
    n_lanes = int(np.prod(lane_shape)) if lane_shape else 1
    if not row_lanes:
        return grid.reshape(n, -1)[:, :n_lanes]
    K = lane_shape[-1] if lane_shape else 1
    B = max(n_lanes // max(K, 1), 1)
    P, wpr, r = _row_layout(K)
    if r == 1:
        return grid.reshape(n, B, wpr * _WORD)[:, :, :K].reshape(n, -1)
    return grid.reshape(n, -1, P)[:, :B, :K].reshape(n, -1)


def pack_lanes(planes, row_align: bool = False) -> PackedPlanes:
    """Raw ``{0,1}`` planes ``(n, *lanes)`` -> :class:`PackedPlanes`.

    ``row_align=True`` packs the last lane axis row-aligned (the reduce
    layout; see the class docstring)."""
    n = planes.shape[0]
    lane_shape = tuple(planes.shape[1:])
    if _is_traced(planes):
        flat = planes.reshape(n, -1)
        return PackedPlanes(
            _pack_bits32_jnp(_grid_bits_jnp(flat, lane_shape, row_align)),
            lane_shape,
            _row_layout(lane_shape[-1] if lane_shape else 1)[0] if row_align else 0,
        )
    flat = np.asarray(planes, np.uint8).reshape(n, -1)
    words = _pack_bits32_np(_grid_bits_np(flat, lane_shape, row_align))
    rl = _row_layout(lane_shape[-1] if lane_shape else 1)[0] if row_align else 0
    return PackedPlanes(words, lane_shape, rl)


def _grid_bits_jnp(flat, lane_shape: tuple[int, ...], row_align: bool):
    """Traced analogue of :func:`_grid_bits_np` (operates on all planes at
    once: flat is (n, n_lanes) -> (n, n_words, 32))."""
    n, n_lanes = flat.shape
    if not row_align:
        n_words = max(-(-n_lanes // _WORD), 1)
        pad = n_words * _WORD - n_lanes
        if pad:
            flat = jnp.pad(flat, ((0, 0), (0, pad)))
        return flat.reshape(n, n_words, _WORD)
    K = lane_shape[-1] if lane_shape else 1
    B = max(n_lanes // max(K, 1), 1)
    P, wpr, r = _row_layout(K)
    x = flat.reshape(n, B, K)
    if r == 1:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, wpr * _WORD - K)))
        return x.reshape(n, B * wpr, _WORD)
    Bp = -(-B // r) * r
    x = jnp.pad(x, ((0, 0), (0, Bp - B), (0, P - K)))
    return x.reshape(n, Bp // r, _WORD)


def unpack_lanes(pp: PackedPlanes):
    """:class:`PackedPlanes` -> raw ``{0,1}`` planes ``(n, *lanes)`` uint8."""
    n = pp.n_planes
    if _is_traced(pp.words):
        bits = _unpack_bits32_jnp(pp.words)  # (n, n_words, 32)
        flat = _ungrid_jnp(bits, pp.lane_shape, pp.row_lanes)
        return flat.reshape((n,) + pp.lane_shape).astype(_PLANE_DTYPE)
    bits = _unpack_bits32_np(np.asarray(pp.words))
    flat = _ungrid_np(bits, pp.lane_shape, pp.row_lanes)
    return flat.reshape((n,) + pp.lane_shape).astype(np.uint8)


def _ungrid_jnp(bits, lane_shape: tuple[int, ...], row_lanes: int):
    n = bits.shape[0]
    n_lanes = int(np.prod(lane_shape)) if lane_shape else 1
    if not row_lanes:
        return bits.reshape(n, -1)[:, :n_lanes]
    K = lane_shape[-1] if lane_shape else 1
    B = max(n_lanes // max(K, 1), 1)
    P, wpr, r = _row_layout(K)
    if r == 1:
        return bits.reshape(n, B, wpr * _WORD)[:, :, :K].reshape(n, -1)
    return bits.reshape(n, -1, P)[:, :B, :K].reshape(n, -1)


def pack_values(x, n_bits: int, row_align: bool = False) -> PackedPlanes:
    """Integer tensor -> :class:`PackedPlanes` directly, without ever
    materializing the raw ``(n_bits, *lanes)`` plane tensor.

    This is the packed-resident producer: layers pack their quantized
    operands straight into word space (``row_align=True`` when the last
    axis is the reduce axis)."""
    lane_shape = tuple(np.shape(x))
    if _is_traced(x):
        flat = x.astype(jnp.uint32).reshape(-1)
        shifts = jnp.arange(n_bits, dtype=jnp.uint32)
        planes = ((flat[None, :] >> shifts[:, None]) & 1).astype(jnp.uint32)
        grids = _grid_bits_jnp(planes, lane_shape, row_align)
        rl = _row_layout(lane_shape[-1] if lane_shape else 1)[0] if row_align else 0
        return PackedPlanes(_pack_bits32_jnp(grids), lane_shape, rl)
    flat = np.asarray(x).astype(np.uint64).reshape(1, -1)
    grid = _grid_bits_np(flat, lane_shape, row_align)[0]  # (n_words, 32) values
    words = np.empty((n_bits, grid.shape[0]), np.uint32)
    for p in range(n_bits):
        words[p] = _pack_bits32_np(((grid >> np.uint64(p)) & 1).astype(np.uint8))
    rl = _row_layout(lane_shape[-1] if lane_shape else 1)[0] if row_align else 0
    return PackedPlanes(words, lane_shape, rl)


def unpack_values(pp: PackedPlanes, signed: bool = False):
    """:class:`PackedPlanes` -> integer tensor of ``lane_shape`` (int64),
    without materializing raw planes (the packed-resident consumer)."""
    n = pp.n_planes
    if _is_traced(pp.words):
        bits = _unpack_bits32_jnp(pp.words).astype(jnp.int64)
        flat = _ungrid_jnp(bits, pp.lane_shape, pp.row_lanes).astype(jnp.int64)
        weights = (jnp.int64(1) << jnp.arange(n, dtype=jnp.int64))[:, None]
        val = (flat * weights).sum(axis=0)
        if signed:
            val = jnp.where(flat[-1].astype(bool), val - (1 << n), val)
        return val.reshape(pp.lane_shape)
    words = np.asarray(pp.words)
    acc = np.zeros((words.shape[1], _WORD), np.int64)
    for p in range(n):
        acc += _unpack_bits32_np(words[p]).astype(np.int64) << p
    val = _ungrid_np(acc[None], pp.lane_shape, pp.row_lanes)[0]
    if signed:
        sign = _ungrid_np(_unpack_bits32_np(words[n - 1])[None],
                          pp.lane_shape, pp.row_lanes)[0]
        val = np.where(sign.astype(bool), val - (1 << n), val)
    return val.reshape(pp.lane_shape)


# ---------------------------------------------------------------------------
# In-packed lane shuffle: flat <-> row-aligned without leaving word space.
# ---------------------------------------------------------------------------
def shuffle_to_rows(pp: PackedPlanes) -> PackedPlanes:
    """Flat-packed -> row-aligned (reduce layout) lane shuffle.

    The software analogue of the in-array move that lines the reduce axis
    up row-wise (§III-D).  Implementation note: the gather transiently
    expands the words to a {0,1} bit grid (C-speed packbits/unpackbits)
    and repacks — it stays below the value-plane API (no
    ``bitplane_unpack`` integer reconstruction), but it is NOT free;
    producers that know their reduce axis should pack row-aligned up
    front (``pack_values(..., row_align=True)``) and skip it, as the conv
    tiler does."""
    if pp.row_lanes:
        return pp
    K = pp.lane_shape[-1] if pp.lane_shape else 1
    n = pp.n_planes
    if _is_traced(pp.words):
        bits = _ungrid_jnp(_unpack_bits32_jnp(pp.words), pp.lane_shape, 0)
        grids = _grid_bits_jnp(bits, pp.lane_shape, True)
        return PackedPlanes(_pack_bits32_jnp(grids), pp.lane_shape,
                            _row_layout(K)[0])
    bits = _unpack_bits32_np(np.asarray(pp.words)).reshape(n, -1)[:, :pp.n_lanes]
    grids = _grid_bits_np(bits, pp.lane_shape, True)
    return PackedPlanes(_pack_bits32_np(grids), pp.lane_shape, _row_layout(K)[0])


def shuffle_to_flat(pp: PackedPlanes) -> PackedPlanes:
    """Row-aligned -> flat-packed, in packed space (inverse shuffle)."""
    if not pp.row_lanes:
        return pp
    n = pp.n_planes
    if _is_traced(pp.words):
        bits = _ungrid_jnp(_unpack_bits32_jnp(pp.words), pp.lane_shape,
                           pp.row_lanes)
        grids = _grid_bits_jnp(bits, pp.lane_shape, False)
        return PackedPlanes(_pack_bits32_jnp(grids), pp.lane_shape, 0)
    bits = _unpack_bits32_np(np.asarray(pp.words))
    flat = _ungrid_np(bits, pp.lane_shape, pp.row_lanes)
    grids = _grid_bits_np(flat, pp.lane_shape, False)
    return PackedPlanes(_pack_bits32_np(grids), pp.lane_shape, 0)


def _coerce(x) -> tuple[PackedPlanes, bool]:
    if isinstance(x, PackedPlanes):
        return x, True
    return pack_lanes(x), False


def _align_pair(pa: PackedPlanes, pb: PackedPlanes):
    """Bring two operands to a common lane layout (packed-space shuffle)."""
    if pa.row_lanes == pb.row_lanes:
        return pa, pb
    if pa.row_lanes and not pb.row_lanes:
        return pa, shuffle_to_rows(pb)
    if pb.row_lanes and not pa.row_lanes:
        return shuffle_to_rows(pa), pb
    raise ValueError(
        f"incompatible row layouts: {pa.row_lanes} vs {pb.row_lanes}")


def _emit(words, lane_shape: tuple[int, ...], packed: bool, row_lanes: int = 0):
    pp = PackedPlanes(words, lane_shape, row_lanes)
    return pp if packed else unpack_lanes(pp)


def _pack_mask(mask, like: PackedPlanes | None = None):
    """Per-lane predicate -> packed tag word row (n_words,) uint32, in the
    same lane layout as ``like`` (flat when omitted)."""
    if isinstance(mask, PackedPlanes):
        return mask.words[0]
    row = bool(like is not None and like.row_lanes)
    if _is_traced(mask):
        return pack_lanes(mask.astype(_PLANE_DTYPE)[None], row_align=row).words[0]
    return pack_lanes(np.asarray(mask, np.uint8)[None], row_align=row).words[0]


# ---------------------------------------------------------------------------
# Cycle formulas (paper §III).
# ---------------------------------------------------------------------------
def add_cycles(n: int) -> int:
    return n + 1


def mul_cycles(n: int) -> int:
    return n * n + 5 * n - 2


def div_cycles(n: int) -> float:
    return 1.5 * n * n + 5.5 * n


def move_cycles(n: int) -> int:
    # Word-line move: read + write-back per bit; sense-amp cycling folds this
    # to ~1 cycle/bit in column-multiplexed arrays (§III-D, [18]).
    return n


def reduce_cycles(k: int, width: int) -> int:
    """Cycles to reduce ``k`` elements of ``width`` bits to one sum in-array."""
    cyc = 0
    w = width
    steps = int(np.ceil(np.log2(max(k, 1))))
    for _ in range(steps):
        cyc += move_cycles(w) + add_cycles(w)
        w += 1
    return cyc


def minmax_cycles(k: int, width: int) -> int:
    """Cycles for the §IV-D in-cache min/max log tree over ``k`` lanes of
    ``width``-bit values.

    Each halving step is one subtract (whose sign drives the tag latch),
    one tag-masked selective copy, and a tag load — min and max candidate
    lanes are separate bit-line groups advancing in lockstep, so a single
    pass serves both trees (like the §IV-D max-pool sequence)."""
    steps = int(np.ceil(np.log2(max(k, 1))))
    return steps * (add_cycles(width) + (width + 1) + 1)


def dot_cycles(k: int, n_bits: int, acc_bits: int) -> int:
    """Per-lane-group dot cycles: one n-bit MAC into an ``acc_bits`` partial
    sum, then the §III-D log tree over ``k`` lanes (the conv inner loop)."""
    return (mul_cycles(n_bits) + add_cycles(max(acc_bits, 2 * n_bits))
            + reduce_cycles(k, acc_bits))


# ---------------------------------------------------------------------------
# ABFT integrity layer (PR 7): checksum columns over one pass's operands.
# ---------------------------------------------------------------------------
def abft_checksums(x_rows, w_rows):
    """ABFT reference sums for one pass over CLEAN unsigned operands.

    The pass computes ``v[m, t] = w_m . x_t``.  Two checksum vectors bound
    every entry:

    * column reference ``col[t] = x_t . sum_m(w_m)`` — one extra "filter"
      (the column checksum appended to the packed filter block at load
      time) dotted against every window row; a corrupted filter word or a
      corrupted pass output shifts some per-row filter sum,
    * row reference ``row[m] = sum_t(x_t) . w_m`` — one extra "window row"
      dotted against every filter; a corrupted activation word shifts some
      per-filter row sum.

    Because operands are unsigned, a monotone stuck-at-1 corruption can
    only *raise* sums, and any single-bit flip at a live lane (a lane where
    the opposing checksum vector is nonzero) shifts exactly one reference
    — so a verification pass over (col, row) detects every output-changing
    fault the injector covers; mismatch-free means output-identical.

    Returns ``(col, row)`` as exact int64 vectors."""
    xr = np.asarray(x_rows, dtype=np.int64)
    wr = np.asarray(w_rows, dtype=np.int64)
    return xr @ wr.sum(axis=0), wr @ xr.sum(axis=0)


def checksum_cycles(k: int, n_bits: int, acc_bits: int, rows: int,
                    filters: int) -> int:
    """Cycles to verify one pass of ``rows`` window rows x ``filters``
    filter columns: the column checksum is one extra filter lane-group
    dotted per row, the row checksum one extra window row dotted per
    filter — each priced at the same per-lane-group :func:`dot_cycles` as
    the real work (the checksum columns ride the §III-D reduce tree)."""
    return dot_cycles(k, n_bits, acc_bits) * (max(rows, 0) + max(filters, 0))


# ---------------------------------------------------------------------------
# EIE-style zero-operand lane skipping (beyond-paper, host path only).
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class SkipStats:
    """Accounting for zero-operand lane skipping (does NOT change modeled
    cycles — the SRAM clocks every bit-slice; this is emulation-side work
    elision plus the note the cycle reports print).

    ``planes_*`` count multiplier bit-plane steps: a plane whose tag word
    carries no set bit makes the tag-predicated shifted-add an identity, so
    the host engine elides the whole step (value-sparsity at bit-plane
    granularity — the per-plane half of the sparsity-aware scheduling; the
    per-filter half lives in core/schedule.py, where it DOES earn modeled
    skipped-pass credits)."""

    lanes_total: int = 0
    lanes_zero: int = 0  # lanes with a provably-zero operand (tag-skippable)
    words_total: int = 0
    words_skipped: int = 0  # whole 32-lane words elided by the host engine
    planes_total: int = 0  # multiplier bit-plane steps seen by the host engine
    planes_skipped: int = 0  # all-zero tag planes elided (step is an identity)

    def reset(self) -> None:
        self.lanes_total = self.lanes_zero = 0
        self.words_total = self.words_skipped = 0
        self.planes_total = self.planes_skipped = 0

    def snapshot(self) -> dict:
        return dataclasses.asdict(self)


SKIP_STATS = SkipStats()
ZERO_SKIP = True  # module switch for the host multiply's word/plane elision


def filter_occupancy(rows, n_bits: int, zero: int = 0):
    """Pack-time operand occupancy scan for sparsity-aware scheduling.

    ``rows``: integer filter rows ``(M, K)`` (one row per filter, reduce
    lanes last — the grid :func:`pack_values` consumes).  Returns
    ``(zero_mask, plane_live)``:

    * ``zero_mask`` ``(M,)`` bool — filters whose every weight equals
      ``zero`` (the quantized zero point): their dot contribution is the
      analytically-known ``zero * sum(x)``, so the scheduler can drop their
      serialized passes entirely (core/schedule.py turns this into
      skipped-pass cycle credits),
    * ``plane_live`` ``(n_bits,)`` bool — bit planes carrying at least one
      set bit across the *live* filters; dead planes make the multiplier's
      shifted-add step an identity (see :func:`_mul_words_dense`).

    Pure metadata: results/cycles of any individual op are never changed by
    this scan — it only feeds the plan."""
    rows = np.asarray(rows)
    if rows.ndim != 2:
        rows = rows.reshape(rows.shape[0], -1)
    zero_mask = (rows == zero).all(axis=1)
    live = rows[~zero_mask].astype(np.uint64)
    plane_live = np.array(
        [bool(((live >> np.uint64(p)) & 1).any()) for p in range(n_bits)])
    return zero_mask, plane_live


# ---------------------------------------------------------------------------
# The column peripheral, word-packed: full adder + carry latch + tag latch,
# one bit-slice per cycle.  One uint32 word advances 32 lanes per bitwise op.
# Concrete operands run numpy loops (microseconds, nothing compiled); traced
# operands run the identical recurrence under lax.scan (O(1) trace size).
# Word arrays broadcast over their lane axes, so row-aligned operands can be
# thin views (a window row packed once serves every filter).
# ---------------------------------------------------------------------------
def _word_full_adder(a, b, c):
    s = a ^ b ^ c
    carry = (a & b) | ((a ^ b) & c)
    return s, carry


def _zext_np(w: np.ndarray, n: int) -> np.ndarray:
    if w.shape[0] == n:
        return w
    if w.shape[0] > n:
        return w[:n]
    out = np.zeros((n,) + w.shape[1:], np.uint32)
    out[: w.shape[0]] = w
    return out


def _zext_jnp(w, n: int):
    if w.shape[0] == n:
        return w
    if w.shape[0] > n:
        return w[:n]
    pad = [(0, n - w.shape[0])] + [(0, 0)] * (w.ndim - 1)
    return jnp.pad(w, pad)


def _add_words(aw, bw, *, out_bits: int, invert_b: bool = False,
               carry_one: bool = False):
    """Packed ripple add over ``out_bits`` planes (operands broadcast).

    ``invert_b``/``carry_one`` give two's-complement subtraction for free —
    complement planes come from BLB, carry latch preset to 1 (§III-B).
    """
    if _is_traced(aw, bw):
        a = _zext_jnp(jnp.asarray(aw), out_bits)
        b = _zext_jnp(jnp.asarray(bw), out_bits)
        if invert_b:
            b = ~b
        shape = jnp.broadcast_shapes(a.shape[1:], b.shape[1:])
        init = jnp.full(shape, _FULL_WORD if carry_one else 0, jnp.uint32)

        def step(carry, planes):
            s, carry = _word_full_adder(planes[0], planes[1], carry)
            return carry, s

        _, out = jax.lax.scan(step, init, (a, b))
        return out
    a = _zext_np(np.asarray(aw), out_bits)
    b = _zext_np(np.asarray(bw), out_bits)
    if invert_b:
        b = ~b
    shape = np.broadcast_shapes(a.shape[1:], b.shape[1:])
    carry = np.full(shape, _FULL_WORD if carry_one else 0, np.uint32)
    out = np.empty((out_bits,) + shape, np.uint32)
    for i in range(out_bits):
        out[i], carry = _word_full_adder(a[i], b[i], carry)
    return out


def _nonzero_word(w) -> np.ndarray:
    """OR over planes: bit l set iff lane l has any live bit."""
    return np.bitwise_or.reduce(np.asarray(w), axis=0)


def _mul_words_dense(apad, bw, shape):
    """Tag-predicated shifted-add multiply on (broadcastable) word arrays.

    A multiplier plane whose tag word has no set bit makes every lane's
    predicated write a no-op, so the whole shifted-add step is elided on the
    host path (``SKIP_STATS.planes_skipped``) — the per-plane face of value
    sparsity (a pruned filter's dead bit planes never clock the adder).
    Results are bit-identical; modeled cycles are charged by the caller's
    unchanged formula."""
    total, nb = apad.shape[0], bw.shape[0]
    prod = np.zeros((total,) + shape, np.uint32)
    SKIP_STATS.planes_total += nb
    for j in range(nb):
        tag = bw[j]
        if ZERO_SKIP and not tag.any():
            SKIP_STATS.planes_skipped += 1
            continue
        ntag = ~tag
        shifted = np.roll(apad, j, axis=0)
        carry = np.zeros(shape, np.uint32)
        for i in range(total):
            s, carry = _word_full_adder(prod[i], shifted[i], carry)
            prod[i] = (tag & s) | (ntag & prod[i])
    return prod


def _mul_words(aw, bw):
    """Packed tag-predicated shifted-add multiply (§III-C).

    One step per multiplier plane: full-add the (plane-shifted) multiplicand
    into the product under that plane's tag word.  On the host path, word
    columns whose 32 lanes all carry a zero operand are elided (EIE-style
    zero-operand skipping — their product lanes are exactly zero); the
    elision is accounted in ``SKIP_STATS`` and never alters results or the
    modeled cycle count.
    """
    na, nb = aw.shape[0], bw.shape[0]
    total = na + nb
    if _is_traced(aw, bw):
        apad = _zext_jnp(jnp.asarray(aw), total)
        bw = jnp.asarray(bw)
        shape = jnp.broadcast_shapes(apad.shape[1:], bw.shape[1:])
        # plane-shifted copies of the multiplicand: roll is exact because
        # the top nb planes of apad are zero.
        shifted = jnp.stack([jnp.roll(apad, j, axis=0) for j in range(nb)])

        def step(prod, tj):
            tag, sh = tj

            def astep(carry, planes):
                s, carry = _word_full_adder(planes[0], planes[1], carry)
                return carry, s

            _, summed = jax.lax.scan(astep, jnp.zeros(shape, jnp.uint32),
                                     (prod, sh))
            return (tag & summed) | (~tag & prod), None

        prod, _ = jax.lax.scan(step, jnp.zeros((total,) + shape, jnp.uint32),
                               (bw, shifted))
        return prod
    aw = np.asarray(aw)
    bw = np.asarray(bw)
    apad = _zext_np(aw, total)
    shape = np.broadcast_shapes(aw.shape[1:], bw.shape[1:])
    n_words = int(np.prod(shape)) if shape else 1
    if ZERO_SKIP and n_words > 1:
        active = np.broadcast_to(_nonzero_word(aw) & _nonzero_word(bw), shape)
        idx = np.flatnonzero(active.reshape(-1))
        SKIP_STATS.words_total += n_words
        SKIP_STATS.lanes_total += n_words * _WORD
        SKIP_STATS.lanes_zero += n_words * _WORD - _popcount(
            np.ascontiguousarray(active))
        if idx.size < n_words - n_words // 8:  # worth compressing
            # only count elision that actually happens — below the threshold
            # the dense path still clocks every word
            SKIP_STATS.words_skipped += n_words - idx.size
            a_c = np.broadcast_to(apad, (total,) + shape).reshape(total, -1)[:, idx]
            b_c = np.broadcast_to(bw, (nb,) + shape).reshape(nb, -1)[:, idx]
            prod_c = _mul_words_dense(a_c, b_c, (idx.size,))
            prod = np.zeros((total, n_words), np.uint32)
            prod[:, idx] = prod_c
            return prod.reshape((total,) + shape)
    return _mul_words_dense(apad, bw, shape)


def _select_words(dst, src, tag):
    """Tag-predicated copy: dst where tag bit is 0, src where it is 1."""
    if _is_traced(dst, src, tag):
        src = _zext_jnp(jnp.asarray(src), dst.shape[0])
        return (tag & src) | (~tag & dst)
    src = _zext_np(np.asarray(src), dst.shape[0])
    return (tag & src) | (~tag & np.asarray(dst))


def bitserial_add(a, b, out_bits: int | None = None):
    """Element-wise sum of two plane tensors.  Returns (planes, cycles)."""
    pa, packed_a = _coerce(a)
    pb, packed_b = _coerce(b)
    pa, pb = _align_pair(pa, pb)
    n = max(pa.n_planes, pb.n_planes)
    out_bits = out_bits if out_bits is not None else n + 1
    ow = _add_words(pa.words, pb.words, out_bits=out_bits)
    return _emit(ow, pa.lane_shape, packed_a or packed_b,
                 pa.row_lanes), add_cycles(n)


def bitserial_sub(a, b, out_bits: int | None = None):
    """a - b in two's complement (width = max width + 1 by default).

    Implemented the SRAM way: complement planes of ``b`` are read from BLB
    (free), carry latch preset to 1.  Returns (planes, cycles); MSB of the
    result is the sign — it drives the tag latch for max/ReLU predication.
    """
    pa, packed_a = _coerce(a)
    pb, packed_b = _coerce(b)
    pa, pb = _align_pair(pa, pb)
    n = max(pa.n_planes, pb.n_planes)
    out_bits = out_bits if out_bits is not None else n + 1
    ow = _add_words(pa.words, pb.words, out_bits=out_bits,
                    invert_b=True, carry_one=True)
    return _emit(ow, pa.lane_shape, packed_a or packed_b,
                 pa.row_lanes), add_cycles(n)


def bitserial_multiply(a, b):
    """Element-wise product via tag-predicated shifted adds (§III-C).

    ``a`` is the multiplicand, ``b`` the multiplier; product has
    ``a_bits + b_bits`` planes.  Cycle count is the paper's n^2+5n-2 with
    n = max(a_bits, b_bits).
    """
    pa, packed_a = _coerce(a)
    pb, packed_b = _coerce(b)
    pa, pb = _align_pair(pa, pb)
    ow = _mul_words(pa.words, pb.words)
    n = max(pa.n_planes, pb.n_planes)
    return _emit(ow, pa.lane_shape, packed_a or packed_b,
                 pa.row_lanes), mul_cycles(n)


def bitserial_mac(acc, a, b):
    """acc += a * b.  Returns (planes, cycles) with acc width preserved."""
    pacc, packed_acc = _coerce(acc)
    pa, _ = _coerce(a)
    pb, _ = _coerce(b)
    pa, pb = _align_pair(pa, pb)
    pacc, pa = _align_pair(pacc, pa)
    pacc, pb = _align_pair(pacc, pb)
    prod = _mul_words(pa.words, pb.words)
    n_mul = max(pa.n_planes, pb.n_planes)
    n_add = max(pacc.n_planes, prod.shape[0])
    out = _add_words(pacc.words, prod, out_bits=pacc.n_planes)
    cycles = mul_cycles(n_mul) + add_cycles(n_add)
    return _emit(out, pacc.lane_shape, packed_acc, pacc.row_lanes), cycles


# ---------------------------------------------------------------------------
# Reduction (§III-D): log-tree over the last lane axis, entirely in packed
# space.  Row-aligned operands reduce in place; flat operands are first
# lane-shuffled to the row layout (shuffle_to_rows — a packed-space gather,
# not a plane round-trip).  Each halving step is either a word-slice
# (half >= 32 lanes) or an in-word shift (half < 32) — the SWAR form of
# "move the top half of the lanes under the bottom half".
# ---------------------------------------------------------------------------
def _reduce_tree_words(words, width: int, K: int):
    """Run the log-tree on row-aligned words (width, ..., wpr).

    Returns (words (width+steps, ..., 1), cycles).  Lane positions within
    each P-bit row segment hold partial sums; after the tree each row's sum
    sits at its segment's bit 0."""
    P, wpr, r = _row_layout(K)
    traced = _is_traced(words)
    xp = jnp if traced else np
    cycles = 0
    w, m = width, P
    seg = P if P < _WORD else _WORD
    while m > 1:
        half = m // 2
        if half >= _WORD:
            hw = half // _WORD
            lo, hi = words[..., :hw], words[..., hw:]
        else:
            pat = (1 << half) - 1
            keep = 0
            for j in range(_WORD // seg):
                keep |= pat << (j * seg)
            keep = np.uint32(keep)
            lo = words & keep
            hi = (words >> xp.uint32(half)) & keep
        words = _add_words(lo, hi, out_bits=w + 1)
        cycles += move_cycles(w) + add_cycles(w)
        w += 1
        m = half
    return words, cycles


def _rows_result_bits(words, K: int):
    """Extract each row's post-tree result bit: (w, ..., 1) words -> (w, n_rows)
    {0,1} values (still word-space arithmetic, no plane tensors)."""
    P, wpr, r = _row_layout(K)
    traced = _is_traced(words)
    xp = jnp if traced else np
    t = words[..., 0]  # (w, n_row_words)
    if r == 1:
        return (t & 1).astype(xp.uint32)
    offs = (xp.arange(r, dtype=xp.uint32) * xp.uint32(P))
    bits = (t[..., None] >> offs) & 1  # (w, n_row_words, r)
    return bits.reshape(t.shape[:-1] + (-1,)).astype(xp.uint32)


def bitserial_reduce(planes, out_bits: int | None = None):
    """Sum across the *last* axis (bit lines) via the log-tree of §III-D.

    Each step moves the top half of the lanes under the bottom half and adds
    with one extra bit of width.  Returns (planes, cycles) with lane axis
    reduced to 1.  PackedPlanes stay packed: row-aligned inputs reduce on
    their words directly; flat inputs pay one :func:`shuffle_to_rows` lane
    shuffle first (a transient bit-grid gather — cheap, but row-aligned
    producers skip it entirely).  Integer value planes are never
    reconstructed mid-chain.
    """
    packed_in = isinstance(planes, PackedPlanes)
    if packed_in:
        pp = planes
    else:
        pp = pack_lanes(planes, row_align=True)
    k = pp.lane_shape[-1] if pp.lane_shape else 1
    width = pp.n_planes
    other = tuple(pp.lane_shape[:-1])
    out_shape = other + (1,)
    traced = _is_traced(pp.words)
    if k <= 1:
        # the K == 1 row layout degenerates to flat packing of the rows
        out = PackedPlanes(pp.words, out_shape, 0)
        cycles = 0
    else:
        rows = shuffle_to_rows(pp)
        tree, cycles = _reduce_tree_words(
            rows.words.reshape((width, -1, max(_row_layout(k)[1], 1))), width, k)
        bits = _rows_result_bits(tree, k)  # (w', n_rows_padded)
        n_rows = int(np.prod(other)) if other else 1
        bits = bits[:, :n_rows]
        out = pack_lanes(bits.astype(jnp.uint8 if traced else np.uint8).reshape(
            (bits.shape[0],) + out_shape))
    # sanity: cycle formula matches the closed form
    assert cycles == reduce_cycles(k, width), (cycles, reduce_cycles(k, width))
    if out_bits is not None:
        out = PackedPlanes(
            (_zext_jnp if traced else _zext_np)(out.words, out_bits),
            out.lane_shape, out.row_lanes)
    if packed_in:
        return out, cycles
    return unpack_lanes(out), cycles


# ---------------------------------------------------------------------------
# Min/max reduction (§IV-D): the dynamic-range scalars of the requantization
# step, computed inside the array.  Same row-aligned halving walk as the sum
# tree, but each step is subtract + tag-masked selective copy instead of a
# widening add, so the width never grows.
# ---------------------------------------------------------------------------
def _minmax_tree_words(words, width: int, K: int):
    """Run the min/max log tree on row-aligned words ``(width, ..., wpr)``.

    Returns ``(min_words, max_words, cycles)``; after the tree each row's
    min/max sits at its segment's lane 0.  The host keeps two word grids
    (min candidates, max candidates), but they model *disjoint bit-line
    groups advancing in lockstep*: the per-step charge is one subtract +
    one tag-masked copy + a tag load (see :func:`minmax_cycles`)."""
    P, wpr, r = _row_layout(K)
    traced = _is_traced(words)
    xp = jnp if traced else np
    seg = P if P < _WORD else _WORD

    def halves(w, half):
        if half >= _WORD:
            hw = half // _WORD
            return w[..., :hw], w[..., hw:]
        pat = (1 << half) - 1
        keep = 0
        for j in range(_WORD // seg):
            keep |= pat << (j * seg)
        keep = np.uint32(keep)
        return w & keep, (w >> xp.uint32(half)) & keep

    mn = mx = words
    cycles = 0
    m = P
    while m > 1:
        half = m // 2
        lo, hi = halves(mx, half)
        lo_lt = _add_words(lo, hi, out_bits=width + 1, invert_b=True,
                           carry_one=True)[-1]  # sign of lo - hi
        mx = _select_words(lo, hi, lo_lt)
        lo, hi = halves(mn, half)
        hi_lt = _add_words(hi, lo, out_bits=width + 1, invert_b=True,
                           carry_one=True)[-1]  # sign of hi - lo
        mn = _select_words(lo, hi, hi_lt)
        cycles += add_cycles(width) + (width + 1) + 1
        m = half
    return mn, mx, cycles


def bitserial_minmax(planes):
    """Per-row min AND max over the *last* lane axis (§IV-D dynamic range).

    The in-cache half of the quantization step: a log tree of subtract +
    tag-masked selective copies run entirely in packed word space, so only
    the two per-row scalars ever leave the array.  Accepts raw planes or
    :class:`PackedPlanes` (row-aligned inputs walk their words directly,
    flat inputs pay one :func:`shuffle_to_rows`).  Returns
    ``((min, max), cycles)`` with the lane axis reduced to 1; the
    step-summed cycles are asserted against :func:`minmax_cycles`.

    Padding caveat: zero-padded lanes (flat packing, or rows whose length
    is not the power-of-two row width) fold a 0 into the tree.  Callers
    needing exact minima over arbitrary data must pre-pad rows to the next
    power of two with copies of a real lane — core/nc_layers.nc_minmax
    does exactly that (and handles two's-complement sign biasing)."""
    packed_in = isinstance(planes, PackedPlanes)
    pp = planes if packed_in else pack_lanes(planes, row_align=True)
    k = pp.lane_shape[-1] if pp.lane_shape else 1
    width = pp.n_planes
    other = tuple(pp.lane_shape[:-1])
    out_shape = other + (1,)
    traced = _is_traced(pp.words)
    if k <= 1:
        # the K == 1 row layout degenerates to flat packing of the rows
        out_mn = PackedPlanes(pp.words, out_shape, 0)
        out_mx = out_mn
        cycles = 0
    else:
        rows = shuffle_to_rows(pp)
        wpr = max(_row_layout(k)[1], 1)
        mnw, mxw, cycles = _minmax_tree_words(
            rows.words.reshape((width, -1, wpr)), width, k)
        n_rows = int(np.prod(other)) if other else 1
        dt = jnp.uint8 if traced else np.uint8

        def emit(w):
            bits = _rows_result_bits(w, k)[:, :n_rows]
            return pack_lanes(bits.astype(dt).reshape((width,) + out_shape))

        out_mn, out_mx = emit(mnw), emit(mxw)
    assert cycles == minmax_cycles(k, width), (cycles, minmax_cycles(k, width))
    if packed_in:
        return (out_mn, out_mx), cycles
    return (unpack_lanes(out_mn), unpack_lanes(out_mx)), cycles


# ---------------------------------------------------------------------------
# Fused packed dot (MAC + log-tree) over row-aligned word grids — the layer
# tiler's engine entry.  Bucketed jit cache for repeated tile shapes.
# ---------------------------------------------------------------------------
def bucket_words(n: int, minimum: int = 8) -> int:
    """Pad a word/row count up to its power-of-two bucket so repeated tile
    shapes share one compiled engine executable."""
    return max(_next_pow2(max(n, 1)), minimum)


_ENGINE_CACHE: dict[tuple, object] = {}


def engine_cache_info() -> dict:
    """Bucketed-jit compilation cache: entries keyed by
    (n_bits_x, n_bits_w, acc_bits, K) with jit-internal shape caches.

    ``compiled`` counts executables via the jitted function's
    ``_cache_size``."""
    return {
        "entries": len(_ENGINE_CACHE),
        "keys": sorted(_ENGINE_CACHE),
        "compiled": sum(f._cache_size() for f in _ENGINE_CACHE.values()),
    }


def engine_cache_clear() -> None:
    _ENGINE_CACHE.clear()


def _dot_words_impl(xw, ww, *, K: int, acc_bits: int):
    """Shared host/traced packed-dot body (see :func:`packed_dot_words`)."""
    traced = _is_traced(xw, ww)
    nx, nw = xw.shape[0], ww.shape[0]
    prod = _mul_words(xw, ww)  # (nx+nw, *grid, wpr_or_rowwords)
    acc = (_zext_jnp if traced else _zext_np)(prod, acc_bits)
    P, wpr, r = _row_layout(K)
    # P >= 32: last axis is the words-per-row; P < 32: every axis is grid
    # (each word already holds 32/P whole rows).
    grid = acc.shape[1:-1] if r == 1 else acc.shape[1:]
    tree, _ = _reduce_tree_words(acc.reshape((acc_bits, -1, wpr)),
                                 acc_bits, K)
    bits = _rows_result_bits(tree, K)  # (w', flat_rows)
    w_out = bits.shape[0]
    xp = jnp if traced else np
    # NOTE: without jax_enable_x64 the traced decode saturates at int32 —
    # exact for any realistic row sum (uint8 operands need K > 33k to reach
    # 2^31); the host path is always exact int64.
    dt = np.int64
    if traced and not jax.config.jax_enable_x64:
        dt = jnp.int32
    weights = xp.ones((w_out,), dt) << xp.arange(w_out, dtype=dt)
    vals = (bits.astype(dt) * weights[:, None]).sum(axis=0)
    if r == 1:
        return vals.reshape(grid)
    return vals.reshape(grid[:-1] + (grid[-1] * r,))


def _dot_words_decoded(xw, ww, *, K: int, acc_bits: int):
    """Bucketed-jit engine body: decode the packed row grids to integer
    lanes and dot them with one fused multiply-sum.

    Bit-exact with the scanned bit-serial walk (:func:`_dot_words_impl`)
    — padding lanes decode to zero and contribute nothing — but lowers to
    vectorized integer XLA ops instead of a sequential scan, so one
    compiled executable per bucket actually amortizes on batch sweeps.
    The structural bit-serial emulation stays on the host path; modeled
    cycles are charged by the caller's unchanged formula either way."""
    P, wpr, r = _row_layout(K)

    def decode(w):
        n = w.shape[0]
        bits = _unpack_bits32_jnp(w)  # (n, *grid[, wpr], 32)
        weights = (jnp.int32(1) << jnp.arange(n, dtype=jnp.int32)).reshape(
            (n,) + (1,) * (bits.ndim - 1))
        return (bits.astype(jnp.int32) * weights).sum(axis=0)

    prod = decode(xw) * decode(ww)  # broadcast over the grid axes
    if r == 1:
        return prod.sum(axis=(-1, -2))  # (wpr, 32) lanes cover one row
    pr = prod.reshape(prod.shape[:-1] + (r, P))  # 32 = r rows x P lanes
    s = pr.sum(axis=-1)
    return s.reshape(prod.shape[:-2] + (prod.shape[-2] * r,))


def packed_dot_words(xw, ww, *, K: int, acc_bits: int,
                     engine: str | None = "host",
                     materialize: bool = True, passes: int = 1):
    """Fused row-aligned dot: ``sum_k x[row, k] * w[row, k]`` per row.

    ``xw``/``ww`` are word arrays of shape ``(n_planes, *grid, row_words)``
    whose grid axes broadcast against each other (so a window row packed
    once is shared by every filter, and vice versa).  ``row_words`` covers
    rows of ``K`` lanes padded to ``P = next_pow2(K)`` (``P < 32``: the
    last grid axis counts words of ``32/P`` rows each, and the result
    expands it back to rows).

    Returns ``(values int64, cycles_per_row)`` where cycles follow the
    unchanged per-dot formula :func:`dot_cycles` — one MAC into an
    ``acc_bits`` partial sum plus the §III-D log tree.  Cycles are
    charged HERE, before dispatch, so no backend can perturb the cycle
    model (they re-time execution only).

    ``engine`` names a registered backend (core/backends.py): ``"host"``
    is this module's exact numpy walk, ``"jit"`` the bucketed compiled
    decoded-lane kernel (one executable per (planes, acc, K) bucket —
    callers pad their tile grids to :func:`bucket_words` sizes so ragged
    tails replay the cached executable; :func:`engine_cache_info` reports
    the cache), ``"pallas"`` the byte-packed Pallas bit-serial
    GEMM.  ``engine=None`` resolves through the ``NC_BACKEND``
    environment variable (default ``pallas`` on a TPU, else host); an
    unknown name raises a
    :class:`ValueError` listing the registered backends.

    ``materialize=False`` skips the blocking device->host copy on the jit
    path and returns the dispatched device array instead: XLA's
    asynchronous dispatch lets the caller keep packing the NEXT tile's
    operands while this tile computes — the §IV-E double-buffered engine
    in core/nc_layers.py defers ``np.asarray`` by one tile.  Values are
    identical either way; synchronous backends only change WHEN the copy
    happens, never what it holds.

    ``passes`` is the number of plan passes the grids carry (a layer's
    whole pass list on a backend that declares ``layer_calls``); it feeds
    the backend's dispatch counters and nothing else.
    """
    from repro.core import backends as _backends

    if engine is None:
        engine = _backends.default_backend()
    backend = _backends.get_backend(engine)
    n_bits = max(xw.shape[0], ww.shape[0])
    cycles = dot_cycles(K, n_bits, acc_bits)
    vals = backend.dot_words(xw, ww, K=K, acc_bits=acc_bits,
                             materialize=materialize, passes=passes)
    return vals, cycles


def _resize_planes(planes, n: int):
    if planes.shape[0] == n:
        return planes
    if planes.shape[0] > n:
        return planes[:n]
    pad = [(0, n - planes.shape[0])] + [(0, 0)] * (planes.ndim - 1)
    return (jnp if _is_traced(planes) else np).pad(planes, pad)


# ---------------------------------------------------------------------------
# Predicated ops (tag-latch) — ReLU / max / selective copy (§IV-D).
# ---------------------------------------------------------------------------
def selective_copy(dst, src, mask):
    """Copy ``src`` planes over ``dst`` where ``mask`` (per bit line) is 1.

    Cycles: one per bit (tag-enabled write-back), plus 1 to load the tag.
    """
    pd, packed_d = _coerce(dst)
    ps, _ = _coerce(src)
    pd, ps = _align_pair(pd, ps)
    n = max(pd.n_planes, ps.n_planes)
    tag = _pack_mask(mask, like=pd)
    out = _select_words(pd.words, ps.words, tag)
    return _emit(out, pd.lane_shape, packed_d, pd.row_lanes), n + 1


def bitserial_relu(x):
    """Two's-complement ReLU: zero lanes whose sign plane is set (§IV-D)."""
    px, packed_x = _coerce(x)
    sign = px.words[-1]
    out = px.words & ~sign
    return _emit(out, px.lane_shape, packed_x, px.row_lanes), px.n_planes + 1


def bitserial_max(a, b):
    """Element-wise max of two unsigned plane tensors via subtract + masked
    copy (§IV-D max pooling)."""
    pa, packed_a = _coerce(a)
    pb, packed_b = _coerce(b)
    pa, pb = _align_pair(pa, pb)
    n = max(pa.n_planes, pb.n_planes)
    diff = _add_words(pa.words, pb.words, out_bits=n + 1,
                      invert_b=True, carry_one=True)
    a_lt_b = diff[-1]  # sign of a-b drives the tag latch
    out = _select_words(pa.words, pb.words, a_lt_b)
    return _emit(out, pa.lane_shape, packed_a or packed_b,
                 pa.row_lanes), add_cycles(n) + n + 1


# ---------------------------------------------------------------------------
# Convenience: quantized dot product exactly as an array column computes it.
# ---------------------------------------------------------------------------
def bitserial_dot(x, w, n_bits: int = 8, acc_bits: int = 24):
    """Per-lane dot product: lanes hold channels, reduce at the end.

    ``x``/``w``: unsigned integer tensors of shape [..., K].  Emulates the
    paper's conv inner loop: K tag-predicated MACs into a ``acc_bits``-wide
    partial sum per lane, then a log-tree reduction over lanes.
    Returns (value, cycles) — value is the exact integer dot product.
    """
    xp = bitplane_pack(x, n_bits)
    wp = bitplane_pack(w, n_bits)
    zeros = jnp.zeros if _is_traced(x, w) else np.zeros
    acc = zeros((acc_bits,) + tuple(x.shape), np.uint8)
    cycles = 0
    acc, c = bitserial_mac(acc, xp, wp)
    cycles += c
    red, c = bitserial_reduce(acc)
    cycles += c
    return bitplane_unpack(red)[..., 0], cycles


@dataclasses.dataclass
class OpCycles:
    """Cycle-cost card for one 8-bit MAC pipeline, used by the simulator.

    ``mac8`` is the paper's measured per-MAC constant (236 cycles for layer
    Conv2D_2b: includes multiply, accumulate into the 24-bit partial sum, tag
    loads and scratch moves).  First-principles floor is mul(8)+add(24) = 127;
    the remainder is per-MAC orchestration overhead, which we keep as a
    calibrated constant so the simulator reproduces the paper's tables.
    """

    bits: int = 8
    acc_bits: int = 24
    mac8: int = 236

    @property
    def mac_floor(self) -> int:
        return mul_cycles(self.bits) + add_cycles(self.acc_bits)

    @property
    def mac_overhead(self) -> int:
        return self.mac8 - self.mac_floor
