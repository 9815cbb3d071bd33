"""Compile every Pallas kernel of the tree for a described TPU v5e chip.

The TPU compiler is installed here even where no chip is attached: it
compiles for a topology that is only described, and refuses what Mosaic
would refuse on the chip (a load from HBM, an unaligned slice, too much
fast memory). These tests catch such a break without chip time. Nothing
runs, so they say nothing about results or speed.

This is the only test file that describes the chip. The topology is built
inside a module-scoped fixture, never at import: only one process may load
the TPU library at a time, and every test worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.common import round_up
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.frontier_expand import (frontier_expand_launch,
                                           relax_launch)
from repro.kernels.frontier_expand.frontier_expand import frontier_expand_pallas
from repro.kernels.segment_ell import segment_ell_pallas

# chip_smoke.py's store: 2^21 vertices, ~2^25 edges -> ~3.1M virtual rows
SMOKE_ROWS = 3_145_728
SMOKE_VERTICES = 1 << 21
# the Graph500 scale-20 BFS cell's plan (bench/configs/graph500-s20.json),
# and the SSSP cell's weighted plan over the same graph
# (bench/configs/graph500w-s20.json)
BFS_ROWS = 1_479_296
BFS_VERTICES = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args, **kw):
    compiled = fn.lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("rows,vertices", [(SMOKE_ROWS, SMOKE_VERTICES),
                                           (256, 1000)],
                         ids=["smoke", "small"])
def test_frontier_expand_compiles(one_chip, rows, vertices):
    compiled = _assert_mosaic(
        frontier_expand_pallas,
        _sds((rows, 32), jnp.int32, one_chip),
        _sds((rows, 32), jnp.bool_, one_chip),
        _sds((vertices, 128), jnp.float32, one_chip),
        interpret=False)
    mem = compiled.memory_analysis()
    # the panel and the output are the program's only large buffers
    assert mem.output_size_in_bytes == rows * 128 * 4


@pytest.mark.parametrize("rows,vertices,cols",
                         [(BFS_ROWS, BFS_VERTICES, 1), (256, 1000, 130)],
                         ids=["bfs", "small"])
def test_frontier_launch_compiles(one_chip, rows, vertices, cols):
    """The whole launch over a resident plan, and only (vertices, cols)
    counts out. A one-column panel (the BFS cell's single-root hops) is a
    flat XLA gather and sorted segment-sum, no kernel, its scratch a few
    of the plan's slot columns; a wider one is padded to whole lane tiles
    on the device and expanded by the Mosaic kernel."""
    compiled = frontier_expand_launch.lower(
        _sds((rows * 32,), jnp.int32, one_chip),
        _sds((rows,), jnp.int32, one_chip),
        _sds((vertices, cols), jnp.float32, one_chip),
        n_dst=vertices, use_kernel=True, interpret=False).compile()
    mem = compiled.memory_analysis()
    if cols == 1:
        assert "tpu_custom_call" not in compiled.as_text()
        out = compiled.out_info
        assert out.shape == (vertices, 1) and out.dtype == jnp.float32
        assert mem.temp_size_in_bytes < 8 * rows * 32 * 4
    else:
        assert "tpu_custom_call" in compiled.as_text()
    # the device tiles the counts' layout, but never to the padded lanes
    assert mem.output_size_in_bytes < vertices * round_up(cols, 128) * 4


def test_relax_launch_compiles(one_chip):
    """The min-plus relax over the SSSP cell's resident weighted plan: an
    XLA gather, add and sorted segment-min, no kernel; (vertices, 1)
    candidates out, and its scratch a few of the plan's 189 MB columns."""
    slots = BFS_ROWS * 32
    compiled = relax_launch.lower(
        _sds((slots,), jnp.int32, one_chip),
        _sds((slots,), jnp.float32, one_chip),
        _sds((BFS_ROWS,), jnp.int32, one_chip),
        _sds((BFS_VERTICES, 1), jnp.float32, one_chip),
        n_dst=BFS_VERTICES).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == BFS_VERTICES * 4
    assert mem.temp_size_in_bytes < 8 * slots * 4


def test_segment_ell_compiles(one_chip):
    _assert_mosaic(segment_ell_pallas,
                   _sds((256, 16), jnp.int32, one_chip),
                   _sds((256, 16), jnp.bool_, one_chip),
                   _sds((512, 256), jnp.float32, one_chip),
                   interpret=False)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag_compiles(one_chip, dtype):
    _assert_mosaic(embedding_bag_pallas,
                   _sds((256, 8), jnp.int32, one_chip),
                   _sds((256, 8), jnp.float32, one_chip),
                   _sds((1000, 128), dtype, one_chip),
                   interpret=False)
