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

from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.frontier_expand.frontier_expand import frontier_expand_pallas
from repro.kernels.segment_ell import segment_ell_pallas

# chip_smoke.py's store: 2^21 vertices, ~2^25 edges -> ~3.1M virtual rows
SMOKE_ROWS = 3_145_728
SMOKE_VERTICES = 1 << 21


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
