"""Inception v3 through the shared executor (core/nc_network.py) is the
network it was before the executor left models/inception.py: at the
REDUCED config, the logits (byte for byte), every layer's emulated and
modeled cycles, passes and modeled time, and the concat requant cycles
equal a golden recorded with the executor still in the model file
(tests/golden/inception_reduced_forward.json), for a dense batch, a
sparse + overlapped + compressed batch on pruned weights, and a batch
streamed one image per chunk."""
import json
import pathlib

import jax
import numpy as np
import pytest

from repro.models import inception

GOLDEN = json.loads((pathlib.Path(__file__).parent / "golden"
                     / "inception_reduced_forward.json").read_text())
CASES = {
    "dense_b2": dict(),
    "sparse_overlap_compressed_b2": dict(sparse=True, overlap=True,
                                         compressed=True, prune=True),
    "stream_chunk1_b2": dict(stream_chunk=1),
}


@pytest.fixture(scope="module")
def x32():
    before = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", before)


def _params(cfg, seed):
    """He-normal filters (``init_params``) with a folded BatchNorm scale
    and bias drawn from ``seed``, so the bias add is exercised."""
    params = inception.init_params(jax.random.key(seed), config=cfg)
    rng = np.random.default_rng(seed)
    out = {}
    for name, p in params.items():
        m = p["scale"].shape[0]
        out[name] = {"w": np.asarray(p["w"]),
                     "scale": rng.uniform(0.8, 1.2, m).astype(np.float32),
                     "bias": (0.05 * rng.standard_normal(m)).astype(
                         np.float32)}
    return out


@pytest.mark.parametrize("case", list(CASES))
def test_inception_forward_matches_the_golden(x32, case):
    cfg = inception.REDUCED
    params = _params(cfg, 3)
    x = np.random.default_rng(4).random((2, cfg.img, cfg.img, 3),
                                        dtype=np.float32)
    kw = dict(CASES[case])
    if kw.pop("prune", False):
        kw["wpack"] = inception.prune_wpack(
            inception.prepare_conv_weights(params, cfg))
    logits, rep = inception.nc_forward(params, x, config=cfg, engine="host",
                                       **kw)
    gold = GOLDEN[case]
    hexed = np.asarray(logits, np.float32).tobytes().hex()
    assert hexed == gold["logits_f32_hex"]
    assert rep.concat_requant_cycles == gold["concat_requant_cycles"]
    got = [[l.name, l.kind, l.emulated_cycles, repr(float(l.modeled_cycles)),
            l.serial_passes, repr(float(l.modeled_s)), l.filter_loads,
            l.skipped_passes, l.live_output_bytes] for l in rep.layers]
    assert got == gold["layers"]
