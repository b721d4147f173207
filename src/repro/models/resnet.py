"""ResNet-50 v1.5 through the Neural Cache engine.

Architecture: He et al., "Deep Residual Learning for Image Recognition"
(arXiv:1512.03385), Table 1, the 50-layer column, in the v1.5 variant of
the MLPerf Inference image-classification benchmark: a 7x7/2 stem conv of
64 filters and a 3x3/2 max pool, then bottleneck units (1x1, 3x3, 1x1 at
expansion 4) in four stages of [3, 4, 6, 3] units at widths 64, 128, 256
and 512.  The first unit of stages 2 to 4 strides on its 3x3 conv (v1.5;
v1 strides on the first 1x1), and the first unit of every stage has a
1x1 projection shortcut, the others the identity.  The third conv of a
unit and the projection are linear; ReLU follows the join.  A global
average pool and a 2048 -> 1000 classifier close the network.

The topology is data (:attr:`ResNetConfig.stages`, in the op grammar of
core/nc_network.py), and the shared executor runs it: every conv and
the classifier on the packed bit-serial engine, each residual join as an
in-cache add (``nc_network._nc_residual``).

Departures from the paper:

* BatchNorm is folded into a per-filter scale and bias after each conv
  (inference form), as on every network this engine runs;
* the weights are random from a seed (He-normal), not trained;
* padding is ``SAME`` in TensorFlow's convention on every conv and the
  max pool (an odd total pads one more at the end), where the MLPerf
  reference pads strided convs by ``(k - 1) // 2`` on both sides; the
  output sizes are the same;
* the classifier has 1000 classes (MLPerf's TensorFlow checkpoint adds a
  background class, 1001).

:data:`FULL` is the published network at 224 px; :data:`REDUCED` keeps
every op kind (the strided stem and pool, a projection and an identity
shortcut, strided 3x3s, linear convs, the joins) at a CPU-test size.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import nc_network
from repro.core.mapper import LayerSpec
from repro.core.nc_network import (  # noqa: F401 — the network's interface
    network_occupancy, observed_occupancy, prepare_conv_weights)

IMG = 224
WIDTHS = (64, 128, 256, 512)  # bottleneck width per stage
UNITS = (3, 4, 6, 3)  # units per stage, 50 layers
EXPANSION = 4


def _unit(width: int, stride: int, project: bool):
    """One bottleneck unit: a body of three convs and a shortcut (a
    strided 1x1 projection, or the identity)."""
    out = EXPANSION * width
    body = [("conv1", ("conv", 1, 1, width, 1, "SAME")),
            ("conv2", ("conv", 3, 3, width, stride, "SAME")),
            ("conv3", ("conv", 1, 1, out, 1, "SAME", "linear"))]
    shortcut = ([("shortcut", ("conv", 1, 1, out, stride, "SAME", "linear"))]
                if project else [])
    return ("residual", body, shortcut)


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    """Image size, classes, a divisor of every width (1: published) and
    the units per stage."""

    img: int = IMG
    classes: int = 1000
    width_div: int = 1
    units: tuple = UNITS

    @property
    def name(self) -> str:
        return f"resnet50_v1_5_{self.img}px_{self.classes}cls"

    @property
    def stages(self) -> tuple:
        d = self.width_div
        stages = [("conv1", ("conv", 7, 7, max(1, 64 // d), 2, "SAME")),
                  ("pool1", ("maxpool", 3, 2, "SAME"))]
        for s, (width, n) in enumerate(zip(WIDTHS, self.units)):
            for u in range(n):
                stages.append((f"block{s + 1}_unit{u + 1}",
                               _unit(max(1, width // d),
                                     2 if s and not u else 1, u == 0)))
        return tuple(stages)


FULL = ResNetConfig()
REDUCED = ResNetConfig(img=64, classes=10, width_div=16, units=(2, 1, 1, 1))


def resnet50_specs(config: ResNetConfig = FULL) -> list[LayerSpec]:
    return nc_network.network_specs(config)


specs = resnet50_specs


def init_params(key: jax.Array, dtype=jnp.float32,
                config: ResNetConfig = FULL) -> dict:
    """He-normal filters with the folded BatchNorm at identity, drawn as
    Inception's are (``nc_network.init_params``)."""
    return nc_network.init_params(key, config, dtype)


# ---------------------------------------------------------------------------
# The plain reference: float32, jax.numpy, no kernels, cache or batching
# ---------------------------------------------------------------------------
def _conv(x, p, stride):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y * p["scale"] + p["bias"]


def apply(params: dict, x: jax.Array,
          config: ResNetConfig = FULL) -> jax.Array:
    """Float forward pass.  x: [N, H, W, 3] float32 in [0, 1].  Returns
    [N, classes].  Widths come from the parameters."""
    with jax.default_matmul_precision("highest"):
        x = jax.nn.relu(_conv(x, params["conv1"], 2))
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1), "SAME")
        for s, n in enumerate(config.units):
            for u in range(n):
                name = f"block{s + 1}_unit{u + 1}"
                stride = 2 if s and not u else 1
                y = jax.nn.relu(_conv(x, params[f"{name}_conv1"], 1))
                y = jax.nn.relu(_conv(y, params[f"{name}_conv2"], stride))
                y = _conv(y, params[f"{name}_conv3"], 1)
                short = (_conv(x, params[f"{name}_shortcut"], stride)
                         if u == 0 else x)
                x = jax.nn.relu(y + short)
        x = jnp.mean(x, axis=(1, 2))  # global average pool
        p = params["FullyConnected"]
        return x @ p["w"][0, 0] * p["scale"] + p["bias"]


def nc_forward(params: dict, x: jax.Array,
               config: ResNetConfig = REDUCED, **kwargs):
    """Quantized ResNet forward pass through the bit-serial emulation:
    ``nc_network.nc_forward`` on this network, with its keywords."""
    return nc_network.nc_forward(params, x, config, **kwargs)
