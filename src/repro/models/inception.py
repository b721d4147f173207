"""Inception v3 — the paper's evaluation workload (Table I).

The topology is data (``STEM``, ``MIXED``; the op grammar is
core/nc_network.py's), and one description drives ALL OF:
  * ``inception_v3_specs()`` — the per-branch LayerSpec list consumed by the
    Neural Cache mapper/simulator (reproduces Table I's Conv / Filter-MB
    columns exactly; see tests/test_inception.py),
  * ``init_params`` / ``apply`` — a runnable JAX forward pass (float and
    dynamically-quantized uint8, the paper's §IV-D pipeline), and
  * ``nc_forward`` — the same network executed *through the bit-serial
    emulation* by the shared executor (core/nc_network.py): every
    conv/pool/fc runs on the packed word engine and the per-layer report
    pairs the emulation's arithmetic cycles with the analytic model's pass
    cycles (core/simulator.py), paper-style.

An :class:`InceptionConfig` scales the workload: ``FULL`` is the paper's
299x299 network; ``reduced_config()`` shrinks image size / channel widths /
class count (and optionally drops mixed stages) so the full forward pass is
emulation-tractable while still exercising every block type (3x3 stems,
1x1 packing, 5x5 splits, 7x1/1x7 factorizations, nested splits, pools).

BN is inference-folded into a per-channel scale/bias on every conv.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import nc_network
from repro.core import quantize as q
from repro.core.mapper import LayerSpec
from repro.core.nc_network import (  # noqa: F401 — the network's interface
    observed_occupancy, prepare_conv_weights, prune_wpack)

# ---------------------------------------------------------------------------
# Structure: op = ("conv", R, S, M, stride, pad) | ("maxpool"|"avgpool", R,
# stride, pad) | ("split", [ops], [ops]).  A stem entry is a single op; a
# mixed block is a list of branches (each a list of ops), concatenated.
# ---------------------------------------------------------------------------
STEM = [
    ("Conv2d_1a_3x3", ("conv", 3, 3, 32, 2, "VALID")),
    ("Conv2d_2a_3x3", ("conv", 3, 3, 32, 1, "VALID")),
    ("Conv2d_2b_3x3", ("conv", 3, 3, 64, 1, "SAME")),
    ("MaxPool_3a_3x3", ("maxpool", 3, 2, "VALID")),
    ("Conv2d_3b_1x1", ("conv", 1, 1, 80, 1, "VALID")),
    ("Conv2d_4a_3x3", ("conv", 3, 3, 192, 1, "VALID")),
    ("MaxPool_5a_3x3", ("maxpool", 3, 2, "VALID")),
]


def _inception_a(pool_proj: int):  # Mixed_5x (35x35)
    return [
        [("conv", 1, 1, 64, 1, "SAME")],
        [("conv", 1, 1, 48, 1, "SAME"), ("conv", 5, 5, 64, 1, "SAME")],
        [
            ("conv", 1, 1, 64, 1, "SAME"),
            ("conv", 3, 3, 96, 1, "SAME"),
            ("conv", 3, 3, 96, 1, "SAME"),
        ],
        [("avgpool", 3, 1, "SAME"), ("conv", 1, 1, pool_proj, 1, "SAME")],
    ]


def _reduction_a():  # Mixed_6a (35 -> 17)
    return [
        [("conv", 3, 3, 384, 2, "VALID")],
        [
            ("conv", 1, 1, 64, 1, "SAME"),
            ("conv", 3, 3, 96, 1, "SAME"),
            ("conv", 3, 3, 96, 2, "VALID"),
        ],
        [("maxpool", 3, 2, "VALID")],
    ]


def _inception_b(c7: int):  # Mixed_6b..6e (17x17)
    return [
        [("conv", 1, 1, 192, 1, "SAME")],
        [
            ("conv", 1, 1, c7, 1, "SAME"),
            ("conv", 1, 7, c7, 1, "SAME"),
            ("conv", 7, 1, 192, 1, "SAME"),
        ],
        [
            ("conv", 1, 1, c7, 1, "SAME"),
            ("conv", 7, 1, c7, 1, "SAME"),
            ("conv", 1, 7, c7, 1, "SAME"),
            ("conv", 7, 1, c7, 1, "SAME"),
            ("conv", 1, 7, 192, 1, "SAME"),
        ],
        [("avgpool", 3, 1, "SAME"), ("conv", 1, 1, 192, 1, "SAME")],
    ]


def _reduction_b():  # Mixed_7a (17 -> 8)
    return [
        [("conv", 1, 1, 192, 1, "SAME"), ("conv", 3, 3, 320, 2, "VALID")],
        [
            ("conv", 1, 1, 192, 1, "SAME"),
            ("conv", 1, 7, 192, 1, "SAME"),
            ("conv", 7, 1, 192, 1, "SAME"),
            ("conv", 3, 3, 192, 2, "VALID"),
        ],
        [("maxpool", 3, 2, "VALID")],
    ]


def _inception_c():  # Mixed_7b/7c (8x8); nested split branches flattened
    return [
        [("conv", 1, 1, 320, 1, "SAME")],
        [("conv", 1, 1, 384, 1, "SAME"), ("split", [("conv", 1, 3, 384, 1, "SAME")], [("conv", 3, 1, 384, 1, "SAME")])],
        [
            ("conv", 1, 1, 448, 1, "SAME"),
            ("conv", 3, 3, 384, 1, "SAME"),
            ("split", [("conv", 1, 3, 384, 1, "SAME")], [("conv", 3, 1, 384, 1, "SAME")]),
        ],
        [("avgpool", 3, 1, "SAME"), ("conv", 1, 1, 192, 1, "SAME")],
    ]


MIXED = [
    ("Mixed_5b", _inception_a(32)),
    ("Mixed_5c", _inception_a(64)),
    ("Mixed_5d", _inception_a(64)),
    ("Mixed_6a", _reduction_a()),
    ("Mixed_6b", _inception_b(128)),
    ("Mixed_6c", _inception_b(160)),
    ("Mixed_6d", _inception_b(160)),
    ("Mixed_6e", _inception_b(192)),
    ("Mixed_7a", _reduction_b()),
    ("Mixed_7b", _inception_c()),
    ("Mixed_7c", _inception_c()),
]

IMG = 299


# ---------------------------------------------------------------------------
# Workload configuration: the full paper network, or a reduced-but-complete
# miniature for emulation-scale end-to-end runs.
# ---------------------------------------------------------------------------
def _scale_op(op, div: int):
    if op[0] == "conv":
        _, r, s, m, stride, pad = op
        return ("conv", r, s, max(1, m // div), stride, pad)
    if op[0] == "split":
        return ("split",) + tuple(
            [_scale_op(o, div) for o in sub] for sub in op[1:])
    return op


def _scale_blocks(blocks, div: int):
    if div == 1:
        return blocks
    out = []
    for name, entry in blocks:
        if isinstance(entry, tuple):  # single op (stem)
            out.append((name, _scale_op(entry, div)))
        else:  # list of branches
            out.append((name, [[_scale_op(o, div) for o in br]
                               for br in entry]))
    return out


@dataclasses.dataclass(frozen=True)
class InceptionConfig:
    """Workload geometry: image size, channel-width divisor, classes, and
    the stem/mixed structure (pre-scaled by :func:`_scale_blocks`)."""

    img: int = IMG
    classes: int = 1001
    stem: tuple = tuple((n, op) for n, op in STEM)
    mixed: tuple = tuple((n, br) for n, br in MIXED)

    @property
    def name(self) -> str:
        return f"inception_v3_{self.img}px_{self.classes}cls"

    @property
    def stages(self) -> tuple:
        """The network description the executor runs: the stem ops, then
        each mixed block as one ``mixed`` op."""
        return self.stem + tuple((n, ("mixed", br)) for n, br in self.mixed)


FULL = InceptionConfig()

_STAGE_BLOCKS = {
    "a": ("Mixed_5b",),
    "ra": ("Mixed_6a",),
    "b": ("Mixed_6b",),
    "rb": ("Mixed_7a",),
    "c": ("Mixed_7b",),
}


def reduced_config(img: int = 79, width_div: int = 4, classes: int = 32,
                   stages: Sequence[str] = ("a", "ra", "b", "rb", "c"),
                   ) -> InceptionConfig:
    """A miniature Inception v3: same topology, ``width_div``-narrower
    channels, one mixed block per requested stage.

    The default (79px, /4 widths) keeps every block type and both spatial
    reductions (7x7 -> 3x3 -> 1x1 mixed grids) while staying tractable for
    the bit-serial emulation; ``stages=("a",)`` with a smaller image is the
    test-sized variant.  Note Mixed_6a/7a need a >=7px mixed grid."""
    keep = [b for s in stages for b in _STAGE_BLOCKS[s]]
    mixed = tuple((n, br) for n, br in MIXED if n in keep)
    return InceptionConfig(
        img=img, classes=classes,
        stem=tuple(_scale_blocks(STEM, width_div)),
        mixed=tuple(_scale_blocks(mixed, width_div)),
    )


REDUCED = reduced_config()


def inception_v3_specs(config: InceptionConfig = FULL) -> list[LayerSpec]:
    return nc_network.network_specs(config)


specs = inception_v3_specs


# ---------------------------------------------------------------------------
# Runnable JAX model (NHWC).  BN folded: per-channel scale/bias after conv.
# ---------------------------------------------------------------------------
def init_params(key: jax.Array, dtype=jnp.float32,
                config: InceptionConfig = FULL) -> dict:
    return nc_network.init_params(key, config, dtype)


def _conv(x, p, stride, pad):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y * p["scale"] + p["bias"]


def _pool(x, kind, r, stride, pad):
    if kind == "maxpool":
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, r, r, 1), (1, stride, stride, 1), pad
        )
    ones = jax.lax.reduce_window(
        jnp.ones_like(x), 0.0, jax.lax.add, (1, r, r, 1), (1, stride, stride, 1), pad
    )
    s = jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, r, r, 1), (1, stride, stride, 1), pad
    )
    return s / ones


def _apply_op(x, name, op, params, quant: bool):
    if op[0] == "conv":
        _, r, s, m, stride, pad = op
        p = params[name]
        if quant:
            x = q.fake_quant(x)  # dynamic uint8 activations (§IV-D)
            wq, wscale = q.quantize_per_channel(p["w"], axis=-1)
            p = dict(p, w=wq.astype(jnp.float32) * wscale)
        y = _conv(x, p, stride, pad)
        return jax.nn.relu(y)
    if op[0] in ("maxpool", "avgpool"):
        _, r, stride, pad = op
        return _pool(x, op[0], r, stride, pad)
    if op[0] == "split":
        outs = []
        for i, sub in enumerate(op[1:]):
            y = x
            for j, sop in enumerate(sub):
                y = _apply_op(y, f"{name}_s{i}_{j}", sop, params, quant)
            outs.append(y)
        return jnp.concatenate(outs, axis=-1)
    raise ValueError(op)


def apply(params: dict, x: jax.Array, quant: bool = False,
          config: InceptionConfig = FULL) -> jax.Array:
    """Forward pass.  x: [N, H, W, 3] float32 in [0,1].  Returns [N, classes]."""
    for name, op in config.stem:
        x = _apply_op(x, name, op, params, quant)
    for bname, branches in config.mixed:
        outs = []
        for bi, branch in enumerate(branches):
            y = x
            for oi, op in enumerate(branch):
                y = _apply_op(y, f"{bname}_b{bi}_{oi}", op, params, quant)
            outs.append(y)
        x = jnp.concatenate(outs, axis=-1)
    x = jnp.mean(x, axis=(1, 2))  # global average pool
    if quant:
        x = q.fake_quant(x)
    p = params["FullyConnected"]
    logits = x @ p["w"][0, 0] * p["scale"] + p["bias"]
    return logits


# ---------------------------------------------------------------------------
# Through the Neural Cache engine: the shared executor (core/nc_network.py)
# ---------------------------------------------------------------------------
def activation_sparsity_estimates(config: InceptionConfig = REDUCED) -> dict:
    """ReLU-chain activation-sparsity estimates (see
    ``nc_network.activation_sparsity_estimates``)."""
    return nc_network.activation_sparsity_estimates(config)


def network_occupancy(wpack: dict, config: InceptionConfig = REDUCED) -> dict:
    """Per-layer occupancy of the resident weights (see
    ``nc_network.network_occupancy``)."""
    return nc_network.network_occupancy(wpack, config)


def nc_forward(params: dict, x: jax.Array,
               config: InceptionConfig = REDUCED, **kwargs):
    """Quantized Inception forward pass through the bit-serial emulation:
    ``nc_network.nc_forward`` on this network, with its keywords."""
    return nc_network.nc_forward(params, x, config, **kwargs)
