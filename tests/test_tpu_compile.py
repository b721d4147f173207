"""Compile the Neural Cache path's Pallas kernels for a described TPU v5e.

Nothing runs: each kernel is lowered with ``interpret=False`` against a
v5e chip that is described, not attached, and compiled by the TPU
compiler, which refuses what the chip would refuse (unaligned blocks,
primitives Mosaic cannot lower, unsupported MXU operand types).  The tile
shapes are ones the ``inception.FULL`` schedule sends through the
``pallas`` backend: the stem's first conv, a ``Mixed_5b`` 1x1, a
``Mixed_7b`` 3x3 and the FC (rows x K x filters per tile), and the
whole-layer calls of the backend program, up to the largest layer, of
Inception and of ``resnet.FULL``; beside them the compiled pool call of
the device backends at the pools' shapes.

The topology is described inside a module fixture, never at import time;
the persistent compilation cache and x64 types are off around the
compiles.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bitserial_matmul import (bitserial_matmul,
                                            bitserial_matmul_a4)
from repro.kernels.quant_matmul import quant_matmul

# (tile rows, K, tile filters) from plan_network(inception_v3_specs(FULL))
FULL_TILES = [
    pytest.param((22201, 27, 1), id="Conv2d_1a_3x3"),
    pytest.param((1225, 192, 3), id="Mixed_5b_b0_0"),
    pytest.param((64, 4032, 3), id="Mixed_7b_b2_1"),
    pytest.param((1, 2048, 504), id="FullyConnected"),
]
# (rows, K, live filters) of whole layers, which the ``pallas`` backend
# takes in one call (batch 1; the pruned network's halves beside the
# largest, Conv2d_2a/2b: 21,609 rows padded to 32,768)
FULL_LAYERS = [
    pytest.param((21609, 288, 32), id="Conv2d_2a_3x3-layer"),
    pytest.param((21609, 288, 64), id="Conv2d_2b_3x3-layer"),
    pytest.param((22201, 27, 16), id="Conv2d_1a_3x3-layer-pruned"),
    pytest.param((289, 896, 192), id="Mixed_6b_b1_2-layer"),
    pytest.param((64, 4032, 384), id="Mixed_7b_b2_1-layer"),
    pytest.param((1, 2048, 1001), id="FullyConnected-layer"),
]
# (rows, K, filters) of whole layers of ``resnet.FULL`` at batch 1: the
# 7x7/2 stem (K = 147), a 1x1 at K = 64, the 56 px 3x3, the deepest 3x3
# and the classifier
RESNET_LAYERS = [
    pytest.param((12544, 147, 64), id="resnet-conv1-layer"),
    pytest.param((3136, 64, 64), id="resnet-block1_unit1_conv1-layer"),
    pytest.param((3136, 576, 64), id="resnet-block1_unit1_conv2-layer"),
    pytest.param((49, 4608, 512), id="resnet-block4_unit2_conv2-layer"),
    pytest.param((1, 2048, 1000), id="resnet-FullyConnected-layer"),
]

# (kind, [B, H, W, C], window, stride, padding) of the pools of
# ``inception.FULL`` and ``resnet.FULL``, at batch 1 and 4
FULL_POOLS = [
    pytest.param(("max", (1, 147, 147, 64), 3, 2, "VALID"),
                 id="MaxPool_3a_3x3"),
    pytest.param(("avg", (1, 35, 35, 192), 3, 1, "SAME"), id="Mixed_5b_b3_0"),
    pytest.param(("avg", (4, 17, 17, 768), 3, 1, "SAME"),
                 id="Mixed_6b_b3_0-b4"),
    pytest.param(("avg", (1, 8, 8, 2048), 8, 1, "VALID"), id="AvgPool"),
    pytest.param(("max", (1, 112, 112, 64), 3, 2, "SAME"), id="resnet-pool1"),
    pytest.param(("avg", (4, 7, 7, 2048), 7, 1, "VALID"),
                 id="resnet-AvgPool-b4"),
]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def chip_config():
    """Compile as the program runs on the chip: with 32-bit JAX types
    (test modules that enable x64 at import would otherwise change the
    lowering in a shared worker), and without the persistent cache, which
    stores a compile for a described chip but cannot read it back."""
    from jax.experimental.compilation_cache import compilation_cache
    before = (jax.config.jax_enable_compilation_cache,
              jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before[0])
    jax.config.update("jax_enable_x64", before[1])
    compilation_cache.reset_cache()


def _compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _s(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("tile", FULL_TILES)
def test_bitserial_signed_compiles(one_chip, tile):
    M, K, N = tile
    txt = _compile_text(
        lambda x, p: bitserial_matmul(x, p, 1.0, jnp.ones((N,)), n_bits=8,
                                      interpret=False),
        _s(one_chip, (M, K), jnp.int8), _s(one_chip, (K, N), jnp.uint8))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("tile", FULL_TILES)
def test_bitserial_exact_unsigned_compiles(one_chip, tile):
    M, K, N = tile
    txt = _compile_text(
        lambda x, p: bitserial_matmul(x, p, 1.0, 1.0, n_bits=8, signed=False,
                                      out_dtype=jnp.int32, interpret=False),
        _s(one_chip, (M, K), jnp.uint8), _s(one_chip, (K, N), jnp.uint8))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("tile", FULL_TILES)
def test_bitserial_a4_compiles(one_chip, tile):
    M, K, N = tile
    txt = _compile_text(
        lambda x, p: bitserial_matmul_a4(x, p, 1.0, jnp.ones((N,)),
                                         interpret=False),
        _s(one_chip, (M, (K + 1) // 2), jnp.uint8),
        _s(one_chip, (K, N), jnp.uint8))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("tile", FULL_TILES)
def test_quant_matmul_compiles(one_chip, tile):
    M, K, N = tile
    txt = _compile_text(
        lambda x, w: quant_matmul(x, w, 1.0, jnp.ones((N,)), interpret=False),
        _s(one_chip, (M, K), jnp.int8), _s(one_chip, (K, N), jnp.int8))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("tile", FULL_TILES + FULL_LAYERS + RESNET_LAYERS)
def test_pallas_backend_program_compiles(one_chip, tile, monkeypatch):
    """The program the ``pallas`` backend dispatches per call (a plan
    tile, or a whole layer's pass list): word-grid decode plus the exact
    unsigned kernel, at the bucketed row counts.  The platform check
    would see this CPU, so the test steers it."""
    from repro.core import backends
    from repro.core import bitserial as bs
    from repro.kernels import ops

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    M, K, N = tile
    wpr = bs._row_layout(K)[1]
    txt = _compile_text(
        lambda xf, wf: backends._pallas_exact_impl(xf, wf, K=K, w4a4=False),
        _s(one_chip, (8, bs.bucket_words(M), wpr), jnp.uint32),
        _s(one_chip, (8, bs.bucket_words(N), wpr), jnp.uint32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("pool", FULL_POOLS)
def test_compiled_pool_compiles(one_chip, pool):
    """The one reduce-window program a device backend runs per pool."""
    from repro.core import nc_layers

    kind, shape, window, stride, padding = pool
    txt = nc_layers._pool_device.lower(
        _s(one_chip, shape, jnp.uint8), kind=kind, window=window,
        stride=stride, padding=padding).compile().as_text()
    assert "reduce" in txt
