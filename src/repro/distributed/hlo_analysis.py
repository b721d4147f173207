"""Parse collective traffic out of compiled (post-SPMD) HLO text.

``compiled.cost_analysis()`` reports FLOPs and HBM bytes but not collective
bytes, so we scan the optimized HLO module for all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute ops and sum their operand
sizes.  The compiled module is the *per-device* program after SPMD
partitioning, so the sums are bytes-per-device; the roofline's collective
term divides total traffic by (chips x link_bw), which algebraically equals
per-device bytes / link_bw — see roofline.py.

Each collective kind has a wire-traffic multiplier under a bidirectional-
ring schedule on ``n`` participants (ICI is a torus; ring per dimension):

    all-gather       input is 1/n of the result: moves (n-1)/n of output bytes
    reduce-scatter   (n-1)/n of input bytes
    all-reduce       RS + AG = 2(n-1)/n of input bytes
    all-to-all       (n-1)/n of input bytes cross links
    collective-permute  1x operand bytes

The multiplier's group size is read from the op's replica_groups when
present.  We report both raw operand bytes (for audit) and wire bytes (for
the roofline term).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Iterable

__all__ = ["collective_bytes", "xla_cost_analysis", "CollectiveStats",
           "DTYPE_BYTES"]


def xla_cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` — one dict of XLA's cost counters."""
    return dict(compiled.cost_analysis() or {})


DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# e.g.  %ag = bf16[16,1024,512]{2,1,0} all-gather(...), replica_groups=...
_OP_RE = re.compile(
    r"=\s*(?:\([^)]*\)\s*)?([a-z0-9]+)\[([\d,]*)\][^ ]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)
_TUPLE_RE = re.compile(
    r"=\s*\(([^)]*)\)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_ALT_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str) -> int:
    m = _GROUPS_ALT_RE.search(line)  # replica_groups=[g,n]<=...
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return 2  # unknown -> conservative minimum group


def _wire_factor(kind: str, n: int) -> float:
    if n <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2.0 * (n - 1) / n
    if kind == "collective-permute":
        return 1.0
    return (n - 1) / n  # all-gather / reduce-scatter / all-to-all


@dataclasses.dataclass
class CollectiveStats:
    ops: dict[str, int] = dataclasses.field(default_factory=dict)
    operand_bytes: dict[str, int] = dataclasses.field(default_factory=dict)
    wire_bytes: dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def total_operand_bytes(self) -> int:
        return sum(self.operand_bytes.values())

    @property
    def total_wire_bytes(self) -> float:
        return sum(self.wire_bytes.values())

    def add(self, kind: str, nbytes: int, group: int) -> None:
        self.ops[kind] = self.ops.get(kind, 0) + 1
        self.operand_bytes[kind] = self.operand_bytes.get(kind, 0) + nbytes
        self.wire_bytes[kind] = (
            self.wire_bytes.get(kind, 0.0) + nbytes * _wire_factor(kind, group)
        )

    def as_dict(self) -> dict:
        return {
            "ops": dict(self.ops),
            "operand_bytes": dict(self.operand_bytes),
            "wire_bytes": {k: round(v) for k, v in self.wire_bytes.items()},
            "total_operand_bytes": self.total_operand_bytes,
            "total_wire_bytes": round(self.total_wire_bytes),
        }


def collective_bytes(hlo_text: str) -> CollectiveStats:
    """Sum collective result sizes (per device) from optimized HLO text.

    ``-start`` variants are counted; matching ``-done`` ops are skipped so
    async pairs are not double counted.
    """
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        if "-done(" in line or "-done." in line:
            continue
        stripped = line.lstrip()
        if not any(c in stripped for c in _COLLECTIVES):
            continue
        m = _OP_RE.search(stripped)
        if m:
            dtype, dims, kind = m.group(1), m.group(2), m.group(3)
            stats.add(kind, _shape_bytes(dtype, dims), _group_size(stripped))
            continue
        m = _TUPLE_RE.search(stripped)
        if m:
            inner, kind = m.group(1), m.group(2)
            nbytes = sum(
                _shape_bytes(d, s) for d, s in _SHAPE_RE.findall(inner)
            )
            # async-start tuples repeat operand+result; result is half
            if kind != "all-to-all":
                nbytes //= 2
            stats.add(kind, nbytes, _group_size(stripped))
    return stats
