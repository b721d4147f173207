"""Share of the roofline: the larger of the images' ops over the int8
peak and their bytes over HBM bandwidth (``work.py``, from the
configuration's layers, not the tiling), over kernel device time."""

from chipbench.work import roofline_share


def read(run):
    s = run.trace
    if not (s and s.kernel_s):
        return None
    share, _ = roofline_share(run.work, run.images, s.kernel_s,
                              run.peaks.int8_ops_per_s,
                              run.peaks.hbm_bytes_per_s)
    return share
