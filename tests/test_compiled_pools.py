"""Pools on a device backend: one compiled reduce-window call
(``nc_layers._pool_device``) against the bit-serial walk that ``host`` and
an engine-less call run.  The compiled pool must give the walk's bytes and
its cycles, on the shapes the nets use and at the edges of the uint8
range and of the rounded divide; through ``nc_forward`` on ``jit`` and
``pallas`` (the Pallas interpreter on CPU) every pool's report and the
logits must equal the ``host`` run's."""
import itertools

import jax
import numpy as np
import pytest

from repro.core import bitserial as bs
from repro.core import nc_layers as nc
from repro.models import inception, resnet

POOLS = {"max": nc.nc_maxpool2d, "avg": nc.nc_avgpool2d}
# (window, stride, batch, size): each window with both strides, both
# batch sizes and the odd grids of the nets (147 is Inception's stem)
GRIDS = [(2, 2, 4, 17), (3, 1, 1, 35), (3, 2, 4, 17), (3, 2, 1, 147),
         (7, 1, 4, 17), (8, 2, 1, 35), (8, 1, 1, 17), (2, 1, 1, 147)]


@pytest.fixture(scope="module", autouse=True)
def x32():
    """The program's 32-bit types (other test modules enable x64)."""
    before = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", before)


def _activation(batch, size, channels, seed):
    """Seeded uint8 codes with a channel held at 255 and one at 0."""
    x = np.random.default_rng(seed).integers(
        0, 256, size=(batch, size, size, channels), dtype=np.uint8)
    x[..., 0] = 255
    x[..., 1] = 0
    return x


@pytest.mark.parametrize("kind,padding,grid", list(itertools.product(
    POOLS, ("VALID", "SAME"), GRIDS)))
def test_compiled_pool_is_the_walk_byte_for_byte(kind, padding, grid):
    window, stride, batch, size = grid
    x = _activation(batch, size, 4, seed=window * 100 + size)
    walk, walk_cycles = POOLS[kind](x, window, stride, padding=padding)
    got, cycles = POOLS[kind](x, window, stride, padding=padding,
                              engine="jit")
    assert got.dtype == np.uint8 and got.shape == walk.shape
    assert np.asarray(got).tobytes() == np.asarray(walk).tobytes()
    assert cycles == walk_cycles and type(cycles) is type(walk_cycles)
    # one image unbatched takes the same path
    one, one_cycles = POOLS[kind](x[0], window, stride, padding=padding,
                                  engine="pallas")
    assert np.asarray(one).tobytes() == np.asarray(walk)[0].tobytes()
    assert one_cycles * batch == cycles


@pytest.mark.parametrize("base", [0, 100, 254])
def test_average_rounds_ties_up_as_the_walk_does(base):
    """Every 2x2 window holds base, base, base + 1, base + 1: its sum
    leaves remainder count // 2, the tie, which rounds up to base + 1
    (255 where base is 254)."""
    x = np.full((2, 6, 6, 3), base, np.uint8)
    x[:, :, 1::2] += 1
    walk, walk_cycles = nc.nc_avgpool2d(x, 2, 2)
    got, cycles = nc.nc_avgpool2d(x, 2, 2, engine="jit")
    assert (np.asarray(walk) == base + 1).all()
    assert np.asarray(got).tobytes() == np.asarray(walk).tobytes()
    assert cycles == walk_cycles


@pytest.mark.parametrize("engine,compiled", [
    (None, False), ("host", False), ("jit", True), ("pallas", True)])
@pytest.mark.parametrize("kind", POOLS)
def test_the_backend_selects_the_path(monkeypatch, kind, engine, compiled):
    """``compiled_pools`` backends never step the bit-serial walk; ``host``
    and an engine-less call never make the compiled call."""
    def refuse(*args, **kwargs):
        raise AssertionError("the other path ran")

    if compiled:
        monkeypatch.setattr(bs, "bitserial_max", refuse)
        monkeypatch.setattr(bs, "bitserial_reduce", refuse)
    else:
        monkeypatch.setattr(nc, "_pool_device", refuse)
    x = _activation(1, 9, 4, seed=7)
    out, cycles = POOLS[kind](x, 3, 2, padding="SAME", engine=engine)
    assert out.shape == (1, 5, 5, 4) and cycles > 0


def _inception():
    cfg = inception.reduced_config(img=63, width_div=8, classes=8,
                                   stages=("a", "ra"))
    params = jax.tree.map(np.asarray,
                          inception.init_params(jax.random.key(0), config=cfg))
    return inception, cfg, params


def _resnet():
    params = jax.tree.map(np.asarray, resnet.init_params(
        jax.random.key(1), config=resnet.REDUCED))
    return resnet, resnet.REDUCED, params


@pytest.fixture(scope="module", params=["inception", "resnet"])
def host_forward(request):
    net, cfg, params = {"inception": _inception, "resnet": _resnet}[
        request.param]()
    x = np.random.default_rng(2).random((2, cfg.img, cfg.img, 3),
                                        dtype=np.float32)
    logits, rep = net.nc_forward(params, x, config=cfg, engine="host")
    return net, cfg, params, x, logits, rep


@pytest.mark.parametrize("engine", ["jit", "pallas"])
def test_forward_on_a_device_backend_keeps_logits_and_cycles(host_forward,
                                                             engine):
    net, cfg, params, x, logits, rep = host_forward
    got, got_rep = net.nc_forward(params, x, config=cfg, engine=engine)
    assert np.asarray(got).tobytes() == np.asarray(logits).tobytes()
    pools = [l for l in rep.layers if l.kind in ("maxpool", "avgpool")]
    got_pools = [l for l in got_rep.layers if l.kind in ("maxpool", "avgpool")]
    assert len(pools) >= 2
    assert got_pools == pools
    assert [l.emulated_cycles for l in got_rep.layers] == [
        l.emulated_cycles for l in rep.layers]
