"""The walks on a device backend.  Pools: one compiled reduce-window call
(``nc_layers._pool_device``) against the bit-serial walk that ``host`` and
an engine-less call run.  The compiled pool must give the walk's bytes and
its cycles, on the shapes the nets use and at the edges of the uint8
range and of the rounded divide.  The §IV-D min/max: one exact integer
reduction against the packed log tree, with the same scalars and cycles
at every row length and at the edges of the int32 and uint8 ranges.
Through ``nc_forward`` on ``jit`` and ``pallas`` (the Pallas interpreter
on CPU) every layer's report, the joins' included, and the logits must
equal the ``host`` run's."""
import functools
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
    """The device backends never step the bit-serial walk; ``host``
    (``emulates_walks``) and an engine-less call never make the compiled
    call."""
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


def test_forward_on_a_device_backend_keeps_minmax_and_joins(host_forward):
    net, cfg, params, x, logits, rep = host_forward
    got, got_rep = net.nc_forward(params, x, config=cfg, engine="jit")
    assert np.asarray(got).tobytes() == np.asarray(logits).tobytes()
    assert [(l.name, l.minmax_cycles, l.emulated_cycles)
            for l in got_rep.layers] == [
        (l.name, l.minmax_cycles, l.emulated_cycles) for l in rep.layers]
    assert sum(l.minmax_cycles > 0 for l in rep.layers) >= 2
    joins = [l for l in rep.layers if l.kind == "residual"]
    assert [l for l in got_rep.layers if l.kind == "residual"] == joins
    assert (len(joins) > 0) == (net is resnet)


# ---------------------------------------------------------------------------
# The §IV-D min/max: the exact reduction against the packed tree
# ---------------------------------------------------------------------------
# row lengths: the tree's smallest, a word's edges, powers of two and not,
# up to beyond a 17x17x768 image's 221,952 / 4 lanes
ROW_LENGTHS = [1, 2, 31, 32, 33, 289, 1024, 4097, 65536, 70000]


def _rows(kind, batch, k):
    """Seeded rows: int32 accumulators holding -2**31 and 2**31 - 1, or
    unsigned 8-bit codes holding 0 and 255 (each where the row has room
    for both; a one-lane row holds the first)."""
    rng = np.random.default_rng(batch * 100_003 + k)
    if kind == "int32":
        lo, hi, bits, signed = -2**31, 2**31 - 1, 32, True
    else:
        lo, hi, bits, signed = 0, 255, 8, False
    v = rng.integers(lo, hi, size=(batch, k), endpoint=True, dtype=np.int64)
    v[:, 0] = lo
    if k > 1:
        v[:, -1] = hi
    return v, bits, signed


@functools.lru_cache(maxsize=None)
def _tree(kind, batch, k):
    v, bits, signed = _rows(kind, batch, k)
    return nc.nc_minmax(v, bits=bits, signed=signed, engine="host")


@pytest.mark.parametrize("engine", ["jit", "pallas"])
@pytest.mark.parametrize("kind", ["int32", "uint8"])
@pytest.mark.parametrize("batch", [1, 4])
@pytest.mark.parametrize("k", ROW_LENGTHS)
def test_reduction_gives_the_trees_scalars_and_cycles(engine, kind, batch,
                                                      k):
    v, bits, signed = _rows(kind, batch, k)
    mn, mx, cycles = nc.nc_minmax(v, bits=bits, signed=signed, engine=engine)
    t_mn, t_mx, t_cycles = _tree(kind, batch, k)
    assert mn.dtype == t_mn.dtype == np.int64 and mn.shape == (batch,)
    np.testing.assert_array_equal(mn, t_mn)
    np.testing.assert_array_equal(mx, t_mx)
    np.testing.assert_array_equal(mn, v.min(axis=1))
    np.testing.assert_array_equal(mx, v.max(axis=1))
    assert cycles == t_cycles and type(cycles) is type(t_cycles)
    assert cycles == bs.minmax_cycles(k, bits) + (2 if signed else 0)


def test_reduction_wraps_out_of_range_lanes_as_the_tree_does():
    """A lane holds ``bits`` bits: a value beyond the int32 range is read
    as its two's-complement residue on both paths."""
    v = np.array([[2**31, -5, 7], [-2**31 - 1, 0, 3]], np.int64)
    tree = nc.nc_minmax(v, bits=32, signed=True, engine="host")
    got = nc.nc_minmax(v, bits=32, signed=True, engine="jit")
    for a, b in zip(got[:2], tree[:2]):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(tree[0], [-2**31, 0])
    np.testing.assert_array_equal(tree[1], [7, 2**31 - 1])
    assert got[2] == tree[2]


@pytest.mark.parametrize("engine,tree", [
    (None, True), ("host", True), ("jit", False), ("pallas", False)])
def test_the_backend_selects_the_minmax_path(monkeypatch, engine, tree):
    """The device backends never step the packed tree; ``host`` and an
    engine-less call always do."""
    ran = []
    walk = bs.bitserial_minmax

    def counted(*args, **kwargs):
        ran.append(1)
        return walk(*args, **kwargs)

    monkeypatch.setattr(bs, "bitserial_minmax", counted)
    v, bits, signed = _rows("int32", 2, 33)
    mn, mx, cycles = nc.nc_minmax(v, bits=bits, signed=signed, engine=engine)
    assert bool(ran) == tree
    np.testing.assert_array_equal(mn, v.min(axis=1))
    assert cycles == bs.minmax_cycles(33, 32) + 2
