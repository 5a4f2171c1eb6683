"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.generators import synthetic_temporal_graph
from repro.kernels import ops
from repro.kernels.layout import build_tile_layout
from repro.kernels.ref import segment_spmm_ref, temporal_relax_min_ref
from repro.kernels.temporal_edgemap import INT_INF


@pytest.mark.parametrize("n_v,n_e,tile_v,block_e", [
    (100, 700, 64, 128),
    (700, 6000, 256, 512),
    (513, 2000, 128, 256),     # non-multiple vertex count
    (64, 64, 64, 128),         # fewer edges than one block
])
def test_relax_min_sweep(n_v, n_e, tile_v, block_e):
    g = synthetic_temporal_graph(n_v, n_e, seed=n_e)
    layout = ops.prepare_layout(np.asarray(g.dst), n_v, tile_v=tile_v, block_e=block_e)
    rng = np.random.default_rng(0)
    arrival = jnp.asarray(rng.integers(0, 1000, n_v), jnp.int32)
    frontier = jnp.asarray(rng.random(n_v) < 0.5)
    ts = np.asarray(g.t_start)
    win = (int(np.quantile(ts, 0.2)), int(np.quantile(ts, 0.9)))

    got = ops.relax_min(layout, g.dst, arrival, g.src, g.t_start, g.t_end,
                        frontier, win)
    arr_masked = jnp.where(frontier, arrival, INT_INF)
    ref = temporal_relax_min_ref(
        g.dst, arr_masked[g.src], g.t_start, g.t_end,
        jnp.ones(n_e, bool), win, n_v,
    )
    assert (np.asarray(got) == np.asarray(ref)).all()


def test_relax_min_strict():
    g = synthetic_temporal_graph(80, 500, seed=1)
    layout = ops.prepare_layout(np.asarray(g.dst), 80, tile_v=64, block_e=128)
    arrival = jnp.asarray(np.full(80, 50), jnp.int32)
    frontier = jnp.ones(80, dtype=bool)
    win = (0, 10_000)
    got = ops.relax_min(layout, g.dst, arrival, g.src, g.t_start, g.t_end,
                        frontier, win, strict=True)
    ref = temporal_relax_min_ref(
        g.dst, arrival[g.src], g.t_start, g.t_end, jnp.ones(500, bool),
        win, 80, strict=True,
    )
    assert (np.asarray(got) == np.asarray(ref)).all()


@pytest.mark.parametrize("n_v,n_e,tile_v,block_e", [
    (100, 700, 64, 128),
    (700, 6000, 256, 512),
    (513, 2000, 128, 256),     # non-multiple vertex count
    (64, 64, 64, 128),         # fewer edges than one block
])
def test_segment_sum_tiles_sweep(n_v, n_e, tile_v, block_e):
    """One-feature sums in the lane-packed layout == the segment oracle."""
    from repro.kernels.temporal_edgemap import segment_sum_tiles

    g = synthetic_temporal_graph(n_v, n_e, seed=n_e + 1)
    layout = ops.prepare_layout(np.asarray(g.dst), n_v, tile_v=tile_v,
                                block_e=block_e)
    rng = np.random.default_rng(n_e)
    msgs = rng.standard_normal(n_e).astype(np.float32)
    valid = rng.random(n_e) < 0.7
    perm = np.asarray(layout.perm)
    safe = np.maximum(perm, 0)
    dst_g = np.asarray(g.dst)[safe]
    msg_g = np.where((perm >= 0) & valid[safe], msgs[safe], 0).astype(np.float32)
    tiles = segment_sum_tiles(
        jnp.asarray(dst_g % tile_v, jnp.int32), jnp.asarray(msg_g),
        jnp.asarray(layout.block_tile), -(-n_v // tile_v),
        tile_v=tile_v, block_e=block_e)
    got = np.asarray(tiles).reshape(-1)[:n_v]
    ref = segment_spmm_ref(g.dst, jnp.asarray(msgs)[:, None],
                           jnp.asarray(valid), n_v)
    np.testing.assert_allclose(got, np.asarray(ref)[:, 0], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("d", [16, 48, 128, 130])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_spmm_sweep(d, dtype):
    g = synthetic_temporal_graph(300, 2500, seed=d)
    layout = ops.prepare_layout(np.asarray(g.dst), 300, tile_v=128, block_e=256)
    rng = np.random.default_rng(d)
    msgs = jnp.asarray(rng.standard_normal((2500, d)), dtype)
    got = ops.spmm(layout, g.dst, msgs, n_vertices=300)
    ref = segment_spmm_ref(g.dst, msgs, jnp.ones(2500, bool), 300)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_spmm_valid_mask():
    g = synthetic_temporal_graph(100, 900, seed=9)
    layout = ops.prepare_layout(np.asarray(g.dst), 100, tile_v=64, block_e=128)
    rng = np.random.default_rng(3)
    msgs = jnp.asarray(rng.standard_normal((900, 32)), jnp.float32)
    valid = jnp.asarray(rng.random(900) < 0.4)
    got = ops.spmm(layout, g.dst, msgs, n_vertices=100, valid_edges=valid)
    ref = segment_spmm_ref(g.dst, msgs, valid, 100)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_layout_partition_invariants():
    dst = np.random.default_rng(0).integers(0, 1000, 5000)
    lay = build_tile_layout(dst, 1000, tile_v=128, block_e=256)
    # every non-padding edge appears exactly once
    perm = lay.perm[lay.perm >= 0]
    assert sorted(perm.tolist()) == list(range(5000))
    # every block's edges belong to its tile
    for b in range(lay.n_blocks):
        blk = lay.perm[b * lay.block_e:(b + 1) * lay.block_e]
        blk = blk[blk >= 0]
        if blk.size:
            assert (dst[blk] // lay.tile_v == lay.block_tile[b]).all()


def test_kernel_backend_earliest_arrival():
    """The Pallas kernel as an engine backend: full EA fixpoint through
    fused relax launches matches the jnp engine."""
    from repro.core.algorithms import earliest_arrival
    from repro.data.generators import power_law_temporal_graph

    g = power_law_temporal_graph(300, 4000, seed=41)
    layout = ops.prepare_layout(np.asarray(g.dst), g.n_vertices,
                                tile_v=128, block_e=256)
    ts = np.asarray(g.t_start)
    win = (int(np.quantile(ts, 0.4)), int(np.asarray(g.t_end).max()))
    src = int(np.argmax(np.asarray(g.out_degree)))
    a = np.asarray(earliest_arrival(g, src, win))
    b = np.asarray(ops.earliest_arrival_kernel(g, layout, src, win))
    assert (a == b).all()
