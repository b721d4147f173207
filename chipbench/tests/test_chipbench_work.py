"""Work counts against hand sums, and the peak table."""
import pytest

from chipbench import work
from chipbench.families import inception_v3
from chipbench.peaks import PEAKS, peaks_for

TWO = [
    # 3x3 conv, 8x8x4 in, 6 filters of which 3 live, VALID -> 6x6
    dict(H=8, W=8, C=4, R=3, S=3, M=6, M_live=3, E=6, F=6),
    # classifier: 10 in, 5 out, dense
    dict(H=1, W=1, C=10, R=1, S=1, M=5, M_live=5, E=1, F=1),
]


def test_two_layer_counts_match_hand_sums():
    macs = 6 * 6 * 3 * 3 * 4 * 3 + 10 * 5  # 3888 + 50
    byts = (8 * 8 * 4 + 3 * 3 * 4 * 3 + 6 * 6 * 6) + (10 + 10 * 5 + 5)
    assert work.network_work(TWO) == {"macs": macs, "ops": 2 * macs,
                                      "bytes": byts}


def test_roofline_names_the_bound_that_binds():
    w = {"ops": 1000, "bytes": 10}
    share, bound = work.roofline_share(w, 2, 1.0, ops_per_s=1e3,
                                       bytes_per_s=1e3)
    assert (share, bound) == (200.0, "compute")
    share, bound = work.roofline_share({"ops": 1, "bytes": 500}, 1, 1.0,
                                       ops_per_s=1e3, bytes_per_s=1e3)
    assert (share, bound) == (50.0, "memory")


def test_full_inception_counts():
    full = dict(img=299, classes=1001, width_div=1, prune_fraction=0.0,
                blocks=[n for n, _ in inception_v3.MIXED])
    layers = inception_v3.conv_layers(full)
    assert len(layers) == 95
    assert work.network_work(layers)["macs"] == 5_713_218_144
    pruned = work.network_work(inception_v3.conv_layers(
        dict(full, prune_fraction=0.5)))["macs"]
    assert 0.49 < pruned / 5_713_218_144 < 0.52


def test_peaks_keyed_by_device_kind():
    p = peaks_for("TPU v5 lite")
    assert (p.int8_ops_per_s, p.bf16_flops_per_s, p.hbm_bytes_per_s) == (
        393e12, 197e12, 819e9)
    assert "TPU v5e" in p.source and set(PEAKS) == {"TPU v5 lite"}
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
