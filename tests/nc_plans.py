"""Plans for the conv and FC tests, built the way ``nc_conv2d`` builds its own.

``nc_layers.nc_conv2d`` and ``nc_fc`` read a layer's decisions (pruned
passes, double buffering, checking, filter store, backend pin) only from
the plan they are given.  ``conv_plan`` and ``fc_plan`` build that plan
with ``schedule.plan_layer`` for the spec ``nc_conv2d`` derives from its
operands, so a test's plan differs from the default plan (``plan=None``)
only in the decisions the test asks for."""
import numpy as np

from repro.core import nc_layers as nc
from repro.core import schedule as sched
from repro.core.cache_geometry import XEON_E5_35MB
from repro.core.mapper import LayerSpec


def conv_spec(x, w, stride: int = 1, padding: str = "VALID") -> LayerSpec:
    """The spec ``nc_conv2d`` derives for ``x`` ([H, W, C] or
    [B, H, W, C]) and filters ``w`` [R, S, C, M]."""
    H = np.shape(x)[-3]
    R, S, C, M = np.shape(w)
    if padding == "SAME":
        H += sum(nc._same_pad(H, R, stride))
    return LayerSpec(name="nc_conv2d", kind="conv", H=H, R=R, S=S, C=C, M=M,
                     E=(H - R) // stride + 1, stride=stride)


def occupancy(w, w_qp) -> sched.LayerOccupancy:
    """The occupancy of ``w``'s quantized filters (integer weights are
    already quantized, as in ``nc_conv2d``)."""
    w = np.asarray(w)
    wq = w if np.issubdtype(w.dtype, np.integer) else nc._quantize_np(w, w_qp)
    return sched.LayerOccupancy.from_filter_rows(
        wq.reshape(-1, w.shape[-1]).T, w_qp.bits, int(w_qp.zero_point))


def conv_plan(x, w, stride: int = 1, *, padding: str = "VALID",
              geom=XEON_E5_35MB, **decisions) -> sched.SlicePlan:
    """``plan_layer`` for ``nc_conv2d(x, w, ..., stride, padding=padding)``
    with the ``decisions`` (``occupancy``, ``overlap``, ``integrity``,
    ``compressed``, ``tile_pixels``, ...) a test asks for."""
    batch = np.shape(x)[0] if np.ndim(x) == 4 else 1
    return sched.plan_layer(conv_spec(x, w, stride, padding), geom,
                            batch=batch, **decisions)


def fc_plan(x, w, **decisions) -> sched.SlicePlan:
    """:func:`conv_plan` for ``nc_fc(x, w, ...)``: ``x`` [K] or [B, K] as
    a 1x1 image, ``w`` [K, M] as 1x1 filters."""
    return conv_plan(np.asarray(x)[..., None, None, :],
                     np.asarray(w)[None, None], **decisions)
