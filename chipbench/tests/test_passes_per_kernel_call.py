"""The reader of ``passes_per_kernel_call``: plan passes over native
``pallas`` calls, from the program's dispatch counters.  Runs on the CPU
at a tiny size, the kernel in the Pallas interpreter."""
import numpy as np

from chipbench import run


def test_passes_per_kernel_call_reads_the_dispatch_counters(monkeypatch):
    """Passes over native ``pallas`` calls, from a real conv on the
    interpreter (one call serving every plan pass); nothing from the
    counters of a program that does not count passes, or made no call."""
    from repro.core import backends
    from repro.core import nc_layers as nc
    from repro.core import quantize as q

    reader = run.metric_reader("passes_per_kernel_call")
    rng = np.random.default_rng(0)
    xq = rng.integers(0, 256, size=(8, 8, 4)).astype(np.uint8)
    wq = rng.integers(0, 256, size=(3, 3, 4, 6)).astype(np.uint8)
    backends.dispatch_stats_clear()
    *_, stats = nc.nc_conv2d(
        xq, wq, q.QuantParams(scale=np.float32(1 / 256), zero_point=0),
        q.QuantParams(scale=np.float32(0.05), zero_point=128),
        engine="pallas", tile_pixels=7, tile_filters=2, return_stats=True)
    assert stats.tiles == 18
    assert reader.read(None) == 18
    backends.dispatch_stats_clear()
    assert reader.read(None) is None
    monkeypatch.setattr(backends, "dispatch_stats", lambda: {"pallas": {
        "native": 11556, "fallback": 0, "bytes_to_device": 9,
        "bytes_from_device": 9}})
    assert reader.read(None) is None
