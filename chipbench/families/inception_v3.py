"""Inception v3 served through Neural Cache: weights from the seed, the
plain reference, the work per image, and the adapter to the system under
test.

Architecture: Szegedy et al. 2015, "Rethinking the Inception Architecture
for Computer Vision" (arXiv:1512.00567), as in the Neural Cache paper's
Table I: 299 px input, the stem, Mixed_5b..Mixed_7c, a global average
pool and a 1001-way classifier.  BatchNorm is folded into a per-filter
scale (multiplied into the filter before quantization) and a bias (added
to the int32 accumulator), as the Neural Cache deployment folds it.

The reference is the paper's 8-bit pipeline (Section IV-D) written out
plainly in numpy, with nothing taken from the program:

* input pixels quantize to uint8 with scale 1/255;
* each conv's filters quantize per tensor to asymmetric uint8 from their
  own min/max (zero always representable), the accumulator is the exact
  integer sum of (x - zx)(w - zw), the folded bias is added as an
  integer, ReLU clips at 0, and the output requantizes per image from
  the accumulator's own min/max: a fixed-point multiply with round half
  up, the multiplier being the float32 of the real ratio;
* max and average pools work on the uint8 codes (average: rounded
  integer divide by the number of real inputs in the window);
* branch outputs requantize to the common scale of their widest range
  before they are concatenated;
* the classifier's accumulator is dequantized to float logits.

``bits`` below 8 computes the same pipeline at that precision; the
control of the correctness check is ``bits=4``.
"""
from __future__ import annotations

import math

import numpy as np

# Structure: ("conv", R, S, M, stride, pad) | ("maxpool"|"avgpool", R,
# stride, pad) | ("split", [ops], [ops]).  A mixed block is a list of
# branches, each a list of ops.
STEM = [
    ("Conv2d_1a_3x3", ("conv", 3, 3, 32, 2, "VALID")),
    ("Conv2d_2a_3x3", ("conv", 3, 3, 32, 1, "VALID")),
    ("Conv2d_2b_3x3", ("conv", 3, 3, 64, 1, "SAME")),
    ("MaxPool_3a_3x3", ("maxpool", 3, 2, "VALID")),
    ("Conv2d_3b_1x1", ("conv", 1, 1, 80, 1, "VALID")),
    ("Conv2d_4a_3x3", ("conv", 3, 3, 192, 1, "VALID")),
    ("MaxPool_5a_3x3", ("maxpool", 3, 2, "VALID")),
]


def _block_a(pool_proj):
    return [[("conv", 1, 1, 64, 1, "SAME")],
            [("conv", 1, 1, 48, 1, "SAME"), ("conv", 5, 5, 64, 1, "SAME")],
            [("conv", 1, 1, 64, 1, "SAME"), ("conv", 3, 3, 96, 1, "SAME"),
             ("conv", 3, 3, 96, 1, "SAME")],
            [("avgpool", 3, 1, "SAME"), ("conv", 1, 1, pool_proj, 1, "SAME")]]


def _block_ra():
    return [[("conv", 3, 3, 384, 2, "VALID")],
            [("conv", 1, 1, 64, 1, "SAME"), ("conv", 3, 3, 96, 1, "SAME"),
             ("conv", 3, 3, 96, 2, "VALID")],
            [("maxpool", 3, 2, "VALID")]]


def _block_b(c7):
    return [[("conv", 1, 1, 192, 1, "SAME")],
            [("conv", 1, 1, c7, 1, "SAME"), ("conv", 1, 7, c7, 1, "SAME"),
             ("conv", 7, 1, 192, 1, "SAME")],
            [("conv", 1, 1, c7, 1, "SAME"), ("conv", 7, 1, c7, 1, "SAME"),
             ("conv", 1, 7, c7, 1, "SAME"), ("conv", 7, 1, c7, 1, "SAME"),
             ("conv", 1, 7, 192, 1, "SAME")],
            [("avgpool", 3, 1, "SAME"), ("conv", 1, 1, 192, 1, "SAME")]]


def _block_rb():
    return [[("conv", 1, 1, 192, 1, "SAME"), ("conv", 3, 3, 320, 2, "VALID")],
            [("conv", 1, 1, 192, 1, "SAME"), ("conv", 1, 7, 192, 1, "SAME"),
             ("conv", 7, 1, 192, 1, "SAME"), ("conv", 3, 3, 192, 2, "VALID")],
            [("maxpool", 3, 2, "VALID")]]


def _block_c():
    split = ("split", [("conv", 1, 3, 384, 1, "SAME")],
             [("conv", 3, 1, 384, 1, "SAME")])
    return [[("conv", 1, 1, 320, 1, "SAME")],
            [("conv", 1, 1, 384, 1, "SAME"), split],
            [("conv", 1, 1, 448, 1, "SAME"), ("conv", 3, 3, 384, 1, "SAME"),
             split],
            [("avgpool", 3, 1, "SAME"), ("conv", 1, 1, 192, 1, "SAME")]]


MIXED = [("Mixed_5b", _block_a(32)), ("Mixed_5c", _block_a(64)),
         ("Mixed_5d", _block_a(64)), ("Mixed_6a", _block_ra()),
         ("Mixed_6b", _block_b(128)), ("Mixed_6c", _block_b(160)),
         ("Mixed_6d", _block_b(160)), ("Mixed_6e", _block_b(192)),
         ("Mixed_7a", _block_rb()), ("Mixed_7b", _block_c()),
         ("Mixed_7c", _block_c())]


# ---------------------------------------------------------------------------
# The network a configuration describes
# ---------------------------------------------------------------------------
def _scale(op, div):
    if op[0] == "conv":
        return op[:3] + (max(1, op[3] // div),) + op[4:]
    if op[0] == "split":
        return ("split",) + tuple([_scale(o, div) for o in sub]
                                  for sub in op[1:])
    return op


def network(cfg: dict):
    """``(stem, mixed)`` of the configuration: the published structure,
    its filter counts divided by ``width_div`` (1 at published widths),
    and the mixed blocks named in ``blocks``."""
    div = int(cfg["width_div"])
    stem = [(n, _scale(op, div)) for n, op in STEM]
    mixed = [(n, [[_scale(op, div) for op in br] for br in brs])
             for n, brs in MIXED if n in cfg["blocks"]]
    return stem, mixed


def _out(h, r, stride, pad):
    return math.ceil(h / stride) if pad == "SAME" else (h - r) // stride + 1


def _walk_op(name, op, h, c, out):
    if op[0] == "conv":
        _, r, s, m, stride, pad = op
        e = _out(h, max(r, s), stride, pad)
        out.append(dict(name=name, H=h, W=h, C=c, R=r, S=s, M=m, E=e, F=e))
        return e, m
    if op[0] in ("maxpool", "avgpool"):
        return _out(h, op[1], op[2], op[3]), c
    hs, cs = [], 0
    for i, sub in enumerate(op[1:]):
        hh, cc = h, c
        for j, sop in enumerate(sub):
            hh, cc = _walk_op(f"{name}_s{i}_{j}", sop, hh, cc, out)
        hs.append(hh)
        cs += cc
    return hs[-1], cs


def conv_layers(cfg: dict) -> list[dict]:
    """Every conv and the classifier, in order, with their shapes and
    ``M_live``, the filters the configuration does not prune."""
    stem, mixed = network(cfg)
    out: list[dict] = []
    h, c = int(cfg["img"]), 3
    for name, op in stem:
        h, c = _walk_op(name, op, h, c, out)
    for bname, branches in mixed:
        cs = 0
        for bi, br in enumerate(branches):
            hh, cc = h, c
            for oi, op in enumerate(br):
                hh, cc = _walk_op(f"{bname}_b{bi}_{oi}", op, hh, cc, out)
            cs += cc
        h, c = hh, cs
    out.append(dict(name="FullyConnected", H=1, W=1, C=c, R=1, S=1,
                    M=int(cfg["classes"]), E=1, F=1))
    frac = float(cfg["prune_fraction"])
    for l in out:
        pruned = 0 if l["name"] == "FullyConnected" else round(l["M"] * frac)
        l["M_live"] = l["M"] - pruned
    return out


# ---------------------------------------------------------------------------
# Weights from the seed, made on the device in one jitted call
# ---------------------------------------------------------------------------
def make_params(cfg: dict, seed: int):
    """He-normal filters, BatchNorm folded into a scale in [0.8, 1.2) and
    a bias of standard deviation 0.05; the last ``prune_fraction`` of each
    conv's filters (not the classifier's) set to zero.  float32, as the
    system under test takes them."""
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
            if l["M_live"] < l["M"]:
                w = w.at[..., l["M_live"]:].set(0.0)
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


def _requant(acc, real, zp, bits):
    """round half up of ``acc * float32(real)`` plus the zero point,
    clipped to the code range (exact in float64 for |acc| < 2**29)."""
    mult = np.float64(np.float32(real))
    out = np.floor(acc.astype(np.float64) * mult + 0.5) + zp
    return np.clip(out, 0, (1 << bits) - 1)


def _same_pad(h, r, stride):
    total = max((math.ceil(h / stride) - 1) * stride + r - h, 0)
    return total // 2, total - total // 2


def _windows(x, r, s, stride):
    """[H, W, C] -> [E, F, r*s*C] window rows (VALID)."""
    v = np.lib.stride_tricks.sliding_window_view(x, (r, s), axis=(0, 1))
    v = v[::stride, ::stride]  # (E, F, C, r, s)
    return v.transpose(0, 1, 3, 4, 2).reshape(v.shape[0], v.shape[1], -1)


class _Act:
    """One image's activation: uint8-range codes (float64) and qparams."""

    def __init__(self, q, scale, zp):
        self.q, self.scale, self.zp = q, np.float32(scale), int(zp)


def _quant_weights(p, bits):
    wf = np.asarray(p["w"], np.float32) * np.asarray(p["scale"], np.float32)
    scale, zp = _qparams(wf.min(), wf.max(), bits)
    wq = np.clip(np.round(wf / scale) + zp, 0, (1 << bits) - 1)
    return wq.astype(np.float64) - zp, scale


def _conv(a, op, p, bits):
    _, r, s, m, stride, pad = op
    wc, w_scale = _quant_weights(p, bits)
    x = a.q - a.zp
    if pad == "SAME":
        ph, pw = _same_pad(x.shape[0], r, stride), _same_pad(x.shape[1], s,
                                                             stride)
        x = np.pad(x, (ph, pw, (0, 0)))
    rows = _windows(x, r, s, stride)
    acc = rows.reshape(-1, rows.shape[-1]) @ wc.reshape(-1, m)
    acc = acc.reshape(rows.shape[0], rows.shape[1], m)
    sxw = np.float64(np.float32(a.scale) * np.float32(w_scale))
    bias_q = np.round(np.asarray(p["bias"], np.float32).astype(np.float64)
                      / sxw)
    acc = np.maximum(acc + bias_q, 0.0)
    scale, zp = _qparams(np.float32(acc.min() * sxw),
                         np.float32(acc.max() * sxw), bits)
    return _Act(_requant(acc, sxw / np.float64(scale), zp, bits), scale, zp)


def _pool(a, op):
    kind, r, stride, pad = op
    x = a.q
    ones = np.ones(x.shape[:2] + (1,))
    if pad == "SAME":
        ph, pw = _same_pad(x.shape[0], r, stride), _same_pad(x.shape[1], r,
                                                             stride)
        x = np.pad(x, (ph, pw, (0, 0)))  # uint8 0: the real 0 for zp 0
        ones = np.pad(ones, (ph, pw, (0, 0)))
    win = np.lib.stride_tricks.sliding_window_view(x, (r, r), axis=(0, 1))
    win = win[::stride, ::stride]  # (E, F, C, r, r)
    if kind == "maxpool":
        return _Act(win.max(axis=(3, 4)), a.scale, a.zp)
    cnt = np.lib.stride_tricks.sliding_window_view(ones, (r, r), axis=(0, 1))
    cnt = cnt[::stride, ::stride].sum(axis=(3, 4))  # (E, F, 1)
    sums = win.sum(axis=(3, 4))
    return _Act(np.floor((sums + np.floor(cnt / 2)) / cnt), a.scale, a.zp)


def _concat(acts, bits):
    qmax = (1 << bits) - 1
    lo = min(np.float32((0 - a.zp) * a.scale) for a in acts)
    hi = max(np.float32((qmax - a.zp) * a.scale) for a in acts)
    scale, zp = _qparams(lo, hi, bits)
    parts = [_requant(a.q - a.zp, np.float64(a.scale) / np.float64(scale),
                      zp, bits) for a in acts]
    return _Act(np.concatenate(parts, axis=-1), scale, zp)


def _apply(a, name, op, params, bits):
    if op[0] == "conv":
        return _conv(a, op, params[name], bits)
    if op[0] in ("maxpool", "avgpool"):
        return _pool(a, op)
    outs = []
    for i, sub in enumerate(op[1:]):
        y = a
        for j, sop in enumerate(sub):
            y = _apply(y, f"{name}_s{i}_{j}", sop, params, bits)
        outs.append(y)
    return _concat(outs, bits)


def reference_logits(cfg: dict, params: dict, image: np.ndarray,
                     bits: int = 8) -> np.ndarray:
    """Logits ``[classes]`` of one ``[img, img, 3]`` image in [0, 1)."""
    qmax = (1 << bits) - 1
    x = np.clip(np.round(np.asarray(image, np.float32) * np.float32(qmax)),
                0, qmax).astype(np.float64)
    a = _Act(x, np.float32(1.0) / np.float32(qmax), 0)
    stem, mixed = network(cfg)
    for name, op in stem:
        a = _apply(a, name, op, params, bits)
    for bname, branches in mixed:
        outs = []
        for bi, br in enumerate(branches):
            y = a
            for oi, op in enumerate(br):
                y = _apply(y, f"{bname}_b{bi}_{oi}", op, params, bits)
            outs.append(y)
        a = _concat(outs, bits)
    h = a.q.shape[0]
    a = _pool(a, ("avgpool", h, 1, "VALID"))
    p = params["FullyConnected"]
    wc, w_scale = _quant_weights(p, bits)
    acc = (a.q.reshape(-1) - a.zp) @ wc.reshape(wc.shape[-2], -1)
    sxw = np.float64(np.float32(a.scale) * np.float32(w_scale))
    return acc * sxw + np.asarray(p["bias"], np.float64)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------
def program_config(cfg: dict):
    """The program's own ``InceptionConfig`` for this configuration."""
    from repro.models import inception

    div = int(cfg["width_div"])
    return inception.InceptionConfig(
        img=int(cfg["img"]), classes=int(cfg["classes"]),
        stem=tuple(inception._scale_blocks(inception.STEM, div)),
        mixed=tuple(inception._scale_blocks(
            [(n, b) for n, b in inception.MIXED if n in cfg["blocks"]], div)))


def build_engine(cfg: dict, params, max_batch: int, **opts):
    """``NCServingEngine`` with its serving defaults, the configuration's
    filter store (``"dense"`` or ``"compressed"``), the traffic's
    ``max_batch`` and its further engine options ``opts``."""
    from repro.launch.serve import NCServingEngine

    return NCServingEngine(params, program_config(cfg), max_batch=max_batch,
                           compressed=cfg["store"] == "compressed", **opts)


def request(rid: int, image: np.ndarray):
    from repro.launch.serve import NCRequest

    return NCRequest(rid=rid, image=image)
