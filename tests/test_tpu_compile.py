"""Compile rehearsals for a described TPU v5e: the graph kernels and the
serving combine, compiled (not run) for the chip at real widths.

Nothing here runs on a device.  The topology is described inside a
module-scoped fixture, so collecting this file never loads the TPU
library; where it cannot be described, every test skips from there.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.engine.backends import segment_combine_windows
from repro.kernels.segment_spmm import segment_spmm_tiles
from repro.kernels.temporal_edgemap import (
    segment_min_tiles,
    segment_sum_tiles,
    temporal_relax_min_tiles,
)

# the sx-stackoverflow-scale deployment (chip_smoke.py): vertex count,
# and the tile-layout block count of its 63.5M edges at block_e=1024
N_VERTICES = 2_601_977
N_BLOCKS_MIN = 68_000
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_segment_min_tiles_compiles(one_chip):
    tile_v, block_e = 512, 1024
    n_tiles = -(-N_VERTICES // tile_v)
    e = N_BLOCKS_MIN * block_e
    i32 = functools.partial(_spec, dtype=jnp.int32, sharding=one_chip)
    compiled = segment_min_tiles.lower(
        i32((e,)), i32((e,)), i32((N_BLOCKS_MIN,)), n_tiles,
        tile_v=tile_v, block_e=block_e, interpret=False).compile()
    _assert_kernel(compiled)


def test_segment_sum_tiles_compiles(one_chip):
    """The one-feature sum (PageRank's) over the deployment's min layout."""
    tile_v, block_e = 512, 1024
    n_tiles = -(-N_VERTICES // tile_v)
    e = N_BLOCKS_MIN * block_e
    i32 = functools.partial(_spec, dtype=jnp.int32, sharding=one_chip)
    compiled = segment_sum_tiles.lower(
        i32((e,)), _spec((e,), jnp.float32, one_chip), i32((N_BLOCKS_MIN,)),
        n_tiles, tile_v=tile_v, block_e=block_e, interpret=False).compile()
    _assert_kernel(compiled)


def test_temporal_relax_min_tiles_compiles(one_chip):
    tile_v, block_e = 512, 1024
    n_tiles = -(-N_VERTICES // tile_v)
    e = N_BLOCKS_MIN * block_e
    i32 = functools.partial(_spec, dtype=jnp.int32, sharding=one_chip)
    compiled = temporal_relax_min_tiles.lower(
        i32((e,)), i32((e,)), i32((e,)), i32((e,)), i32((e,)),
        i32((N_BLOCKS_MIN,)), i32((2,)), n_tiles,
        tile_v=tile_v, block_e=block_e, interpret=False).compile()
    _assert_kernel(compiled)


def test_segment_spmm_tiles_compiles(one_chip):
    tile_v, block_e, tile_d, d = 256, 512, 128, 128
    n_tiles = -(-N_VERTICES // tile_v)
    nb = 4096
    e = nb * block_e
    i32 = functools.partial(_spec, dtype=jnp.int32, sharding=one_chip)
    compiled = segment_spmm_tiles.lower(
        i32((e,)), _spec((e, d), jnp.float32, one_chip), i32((e,)),
        i32((nb,)), n_tiles,
        tile_v=tile_v, block_e=block_e, tile_d=tile_d,
        interpret=False).compile()
    _assert_kernel(compiled)


def test_segment_combine_windows_fits_one_chip(one_chip):
    """The xla_segment combine of one fused round at the deployment's
    scale: 16 tenant rows over its 2^23-slot ring into 2.6M vertices."""
    w, k = 16, 1 << 23
    step = jax.jit(functools.partial(
        segment_combine_windows, num_segments=N_VERTICES, combine="min"))
    compiled = step.lower(
        _spec((w, k), jnp.int32, one_chip), _spec((k,), jnp.int32, one_chip),
        masks=_spec((w, k), jnp.bool_, one_chip)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total


def test_journeys_fused_step_fits_one_chip(one_chip):
    """The fused advance of a journey planner at London's scale (4.85M
    connections, 20,843 stops, a 2^19-slot ring): the delta scatter in
    C/8 chunks, then a group of four earliest-arrival rows and one of four
    latest-departure rows, each over the ring."""
    from repro.core.edgemap import EdgeView
    from repro.engine.plan import make_plan
    from repro.serve import window_sweep as ws

    e, v, c, q = 4_850_431, 20_843, 1 << 19, 4
    i32 = functools.partial(_spec, dtype=jnp.int32, sharding=one_chip)
    fields = (i32((e,)),) * 4 + (_spec((e,), jnp.float32, one_chip),)
    plan = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype, one_chip),
        make_plan("index", "xla_segment", budget=c, ring_capacity=c,
                  n_windows=2 * q))
    ring = EdgeView(*(i32((c,)),) * 4, _spec((c,), jnp.float32, one_chip),
                    _spec((c,), jnp.bool_, one_chip))
    schedule = tuple((alg, (), (0,) * q, tuple(range(q)), None)
                     for alg in ("earliest_arrival", "latest_departure"))
    compiled = ws._fused_step_ring.lower(
        fields, i32((e,)), plan, ring, (i32((q, v)),) * 2,
        (i32((q, 2)),) * 2, (i32((q,)),) * 2, (None, None), None, i32((3,)),
        method="index", n_vertices=v, capacity=c, delta_budget=c // 8,
        schedule=schedule).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total
    text = compiled.as_text()
    assert "fixpoint.latest_departure" in text, "the scope names no op"
