"""ResNet-50 v1.5 served through Neural Cache: weights from the seed, the
plain reference, the work per image, and the adapter to the system under
test.

Architecture: He et al. 2015, "Deep Residual Learning for Image
Recognition" (arXiv:1512.03385), Table 1, 50 layers, in the v1.5 variant
of the MLPerf Inference image-classification reference
(github.com/mlcommons/inference, vision/classification_and_detection):
224 px input, a 7x7/2 stem conv of 64 filters, a 3x3/2 max pool, four
stages of [3, 4, 6, 3] bottleneck units (1x1, 3x3, 1x1 at expansion 4)
at widths 64, 128, 256, 512, the stride of a stage's first unit on its
3x3 conv, a 1x1 projection shortcut on the first unit of each stage and
the identity elsewhere, ReLU after each join, a global average pool and
a 2048 -> 1000 classifier.  BatchNorm is folded into a per-filter scale
(multiplied into the filter before quantization) and a bias (added to
the int32 accumulator).  Padding is SAME in TensorFlow's convention.

The reference is the paper's 8-bit pipeline (Section IV-D) written out
plainly in numpy, with nothing taken from the program:

* input pixels quantize to uint8 with scale 1/255;
* each conv's filters quantize per tensor to asymmetric uint8 from their
  own min/max (zero always representable), the accumulator is the exact
  integer sum of (x - zx)(w - zw), the folded bias is added as an
  integer, ReLU clips at 0 except on the linear convs (a unit's third
  conv and its projection), and the output requantizes per image from
  the accumulator's own min/max: a fixed-point multiply with round half
  up, the multiplier being the float32 of the real ratio;
* the max pool works on the uint8 codes, padded with code 0 (the real 0
  of a ReLU output, so the max is unchanged);
* a residual join rescales both operands' codes (less their zero
  points) to the finer of their two scales by the same fixed-point
  multiply, adds them, clips at 0 (ReLU) and requantizes the sum from its
  own min/max, as a conv's output;
* the global average pool rounds the integer mean of the codes, and the
  classifier's accumulator is dequantized to float logits.

``bits`` below 8 computes the same pipeline at that precision; the
control of the correctness check is ``bits=4``.
"""
from __future__ import annotations

import math

import numpy as np

WIDTHS = (64, 128, 256, 512)
EXPANSION = 4


# ---------------------------------------------------------------------------
# The network a configuration describes
# ---------------------------------------------------------------------------
def network(cfg: dict):
    """``(stem, units)``: the stem conv's filters, and per unit its name,
    bottleneck width, stride and whether it projects its shortcut, at
    the widths divided by ``width_div`` (1: published)."""
    div = int(cfg["width_div"])
    units = []
    for s, (width, n) in enumerate(zip(WIDTHS, cfg["units"])):
        for u in range(int(n)):
            units.append((f"block{s + 1}_unit{u + 1}", max(1, width // div),
                          2 if s and not u else 1, u == 0))
    return max(1, 64 // div), units


def _out(h, stride):
    return math.ceil(h / stride)  # SAME


def conv_layers(cfg: dict) -> list[dict]:
    """Every conv and the classifier, in order, with their shapes and
    ``M_live`` (nothing is pruned)."""
    stem, units = network(cfg)
    out: list[dict] = []

    def conv(name, h, c, r, m, stride):
        e = _out(h, stride)
        out.append(dict(name=name, H=h, W=h, C=c, R=r, S=r, M=m, E=e, F=e,
                        M_live=m))
        return e

    h = conv("conv1", int(cfg["img"]), 3, 7, stem, 2)
    h, c = _out(h, 2), stem  # the max pool
    for name, width, stride, project in units:
        e = conv(f"{name}_conv1", h, c, 1, width, 1)
        e = conv(f"{name}_conv2", e, width, 3, width, stride)
        conv(f"{name}_conv3", e, width, 1, EXPANSION * width, 1)
        if project:
            conv(f"{name}_shortcut", h, c, 1, EXPANSION * width, stride)
        h, c = e, EXPANSION * width
    out.append(dict(name="FullyConnected", H=1, W=1, C=c, R=1, S=1,
                    M=int(cfg["classes"]), E=1, F=1,
                    M_live=int(cfg["classes"])))
    return out


# ---------------------------------------------------------------------------
# Weights from the seed, made on the device in one jitted call
# ---------------------------------------------------------------------------
def make_params(cfg: dict, seed: int):
    """He-normal filters, BatchNorm folded into a scale in [0.8, 1.2) and
    a bias of standard deviation 0.05.  float32, as the system under test
    takes them."""
    import jax
    import jax.numpy as jnp

    layers = conv_layers(cfg)
    sizes = [l["R"] * l["S"] * l["C"] * l["M"] for l in layers]
    n_filters = sum(l["M"] for l in layers)

    def build(key):
        # three draws for the whole network, sliced per layer
        kw, ks, kb = jax.random.split(key, 3)
        w_all = jax.random.normal(kw, (sum(sizes),), jnp.float32)
        s_all = jax.random.uniform(ks, (n_filters,), jnp.float32, 0.8, 1.2)
        b_all = 0.05 * jax.random.normal(kb, (n_filters,), jnp.float32)
        params, wo, mo = {}, 0, 0
        for l, size in zip(layers, sizes):
            shape = (l["R"], l["S"], l["C"], l["M"])
            w = w_all[wo:wo + size].reshape(shape) * math.sqrt(
                2.0 / (l["R"] * l["S"] * l["C"]))
            m = l["M"]
            params[l["name"]] = {"w": w, "scale": s_all[mo:mo + m],
                                 "bias": b_all[mo:mo + m]}
            wo, mo = wo + size, mo + m
        return params

    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return jax.jit(build)(key)


def image_stream(cfg: dict, seed: int, stream: int):
    """Endless images of stream ``stream`` (0: warm-up, 1: the window),
    uniform in [0, 1), float32, ``[img, img, 3]``: the same seed gives
    the same images in the same order."""
    img = int(cfg["img"])
    rng = np.random.default_rng([seed, stream])
    while True:
        yield rng.random((img, img, 3), dtype=np.float32)


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------
def _qparams(lo, hi, bits):
    """(scale, zero point) of an affine unsigned ``bits``-bit code whose
    range covers [lo, hi] and 0, in float32 as the paper's CPU step."""
    qmax = (1 << bits) - 1
    lo = np.float32(min(np.float32(lo), np.float32(0.0)))
    hi = np.float32(max(np.float32(hi), np.float32(0.0)))
    scale = np.float32(hi - lo) / np.float32(qmax)
    if scale <= 0:
        scale = np.float32(1.0)
    zp = int(np.clip(np.round(np.float32(0.0) - lo / scale), 0, qmax))
    return np.float32(scale), zp


def _fixed(acc, real):
    """round half up of ``acc * float32(real)`` (exact in float64 for
    |acc| < 2**29)."""
    return np.floor(acc.astype(np.float64) * np.float64(np.float32(real))
                    + 0.5)


def _requant(acc, real, zp, bits):
    """The fixed-point multiply plus the zero point, clipped to the code
    range."""
    return np.clip(_fixed(acc, real) + zp, 0, (1 << bits) - 1)


def _same_pad(h, r, stride):
    total = max((math.ceil(h / stride) - 1) * stride + r - h, 0)
    return total // 2, total - total // 2


class _Act:
    """One image's activation: uint8-range codes (float64) and qparams."""

    def __init__(self, q, scale, zp):
        self.q, self.scale, self.zp = q, np.float32(scale), int(zp)


def _quant_weights(p, bits):
    wf = np.asarray(p["w"], np.float32) * np.asarray(p["scale"], np.float32)
    scale, zp = _qparams(wf.min(), wf.max(), bits)
    wq = np.clip(np.round(wf / scale) + zp, 0, (1 << bits) - 1)
    return wq.astype(np.float64) - zp, scale


def _out_act(acc, scale_in, bits):
    """Requantize an integer result in units of ``scale_in`` from its own
    min/max."""
    scale, zp = _qparams(np.float32(acc.min() * scale_in),
                         np.float32(acc.max() * scale_in), bits)
    return _Act(_requant(acc, scale_in / np.float64(scale), zp, bits),
                scale, zp)


def _conv(a, p, stride, relu, bits):
    wc, w_scale = _quant_weights(p, bits)
    r, s, _, m = wc.shape
    x = a.q - a.zp
    x = np.pad(x, (_same_pad(x.shape[0], r, stride),
                   _same_pad(x.shape[1], s, stride), (0, 0)))
    v = np.lib.stride_tricks.sliding_window_view(x, (r, s), axis=(0, 1))
    v = v[::stride, ::stride]  # (E, F, C, r, s)
    rows = v.transpose(0, 1, 3, 4, 2).reshape(v.shape[0], v.shape[1], -1)
    acc = (rows.reshape(-1, rows.shape[-1]) @ wc.reshape(-1, m)).reshape(
        rows.shape[0], rows.shape[1], m)
    sxw = np.float64(np.float32(a.scale) * np.float32(w_scale))
    acc = acc + np.round(np.asarray(p["bias"], np.float32).astype(np.float64)
                         / sxw)
    if relu:
        acc = np.maximum(acc, 0.0)
    return _out_act(acc, sxw, bits)


def _maxpool(a):
    ph, pw = _same_pad(a.q.shape[0], 3, 2), _same_pad(a.q.shape[1], 3, 2)
    x = np.pad(a.q, (ph, pw, (0, 0)))  # code 0: the real 0 for zp 0
    win = np.lib.stride_tricks.sliding_window_view(x, (3, 3), axis=(0, 1))
    return _Act(win[::2, ::2].max(axis=(3, 4)), a.scale, a.zp)


def _join(a, b, bits):
    s_c = min(np.float64(a.scale), np.float64(b.scale))
    acc = sum(_fixed(x.q - x.zp, np.float64(x.scale) / s_c) for x in (a, b))
    return _out_act(np.maximum(acc, 0.0), s_c, bits)


def reference_logits(cfg: dict, params: dict, image: np.ndarray,
                     bits: int = 8) -> np.ndarray:
    """Logits ``[classes]`` of one ``[img, img, 3]`` image in [0, 1)."""
    qmax = (1 << bits) - 1
    x = np.clip(np.round(np.asarray(image, np.float32) * np.float32(qmax)),
                0, qmax).astype(np.float64)
    a = _Act(x, np.float32(1.0) / np.float32(qmax), 0)
    _, units = network(cfg)
    a = _maxpool(_conv(a, params["conv1"], 2, True, bits))
    for name, _, stride, project in units:
        y = _conv(a, params[f"{name}_conv1"], 1, True, bits)
        y = _conv(y, params[f"{name}_conv2"], stride, True, bits)
        y = _conv(y, params[f"{name}_conv3"], 1, False, bits)
        short = (_conv(a, params[f"{name}_shortcut"], stride, False, bits)
                 if project else a)
        a = _join(y, short, bits)
    # the global average pool: rounded integer mean of the codes
    n = a.q.shape[0] * a.q.shape[1]
    pooled = np.floor((a.q.sum(axis=(0, 1)) + n // 2) / n)
    p = params["FullyConnected"]
    wc, w_scale = _quant_weights(p, bits)
    acc = (pooled - a.zp) @ wc.reshape(wc.shape[-2], -1)
    sxw = np.float64(np.float32(a.scale) * np.float32(w_scale))
    return acc * sxw + np.asarray(p["bias"], np.float64)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------
def program_config(cfg: dict):
    """The program's own ``ResNetConfig`` for this configuration."""
    from repro.models import resnet

    return resnet.ResNetConfig(img=int(cfg["img"]),
                               classes=int(cfg["classes"]),
                               width_div=int(cfg["width_div"]),
                               units=tuple(int(n) for n in cfg["units"]))


def build_engine(cfg: dict, params, max_batch: int, **opts):
    """``NCServingEngine`` serving ``models.resnet`` with its serving
    defaults, the configuration's filter store (``"dense"`` or
    ``"compressed"``), the traffic's ``max_batch`` and its further engine
    options ``opts``."""
    from repro.launch.serve import NCServingEngine
    from repro.models import resnet

    return NCServingEngine(params, program_config(cfg), network=resnet,
                           max_batch=max_batch,
                           compressed=cfg["store"] == "compressed", **opts)


def request(rid: int, image: np.ndarray):
    from repro.launch.serve import NCRequest

    return NCRequest(rid=rid, image=image)
