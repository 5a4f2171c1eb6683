"""Pallas TPU kernel: fused temporal relax (windowed predicate + tile-local
segment-min) — the hot loop of TemporalEdgeMap.

XLA lowers ``segment_min`` over arbitrary destination ids to scatter-min,
which serializes on TPU.  This kernel exploits the destination-tile edge
layout (kernels/layout.py): each grid step owns one [tile_v] output tile in
VMEM, evaluates the window + ordering predicate on the VPU, and reduces its
edge block into the tile with a compare-select sweep — no scatter.

Layout on the chip: an edge block of ``block_e`` edges is a [R, 128] slab
(edges along lanes, R = block_e / 128 rows).  Each row is compared
against a [tile_v, 128] iota of local vertex ids (broadcast along
sublanes) and min-accumulated elementwise; one transpose and a sublane
reduction per block turn the [tile_v, 128] partials into the lane-major
[1, tile_v] tile.  The output is [n_tiles, 1, tile_v] so its block's last
two dims are whole-array dims.

Grid: (n_blocks,), sequential.  Scalar prefetch carries (block->tile map,
window).  Blocks of one tile are consecutive (the layout groups them), so
the output block stays resident in VMEM across them: the first block of a
tile fills it with INT_INF, later ones min-accumulate.  Tiles no block
owns keep the INT_INF of the aliased (HBM-resident) init buffer.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

INT_INF = jnp.iinfo(jnp.int32).max
LANES = 128
# per combine: (identity, accumulator dtype)
_IDENTITY = {"min": (INT_INF, jnp.int32), "sum": (0.0, jnp.float32)}


def _tile_reduce(dst_loc_ref, row, tile_v: int, op: str = "min"):
    """Per-vertex minima (``op="min"``, i32) or sums (``"sum"``, f32) of
    one edge block: ``dst_loc_ref`` is the [1, R, LANES] block of local
    destination ids (in [0, tile_v)), ``row(r)`` the [1, LANES] values of
    row ``r`` (the identity where masked).  Returns [1, tile_v]."""
    ident, dtype = _IDENTITY[op]
    vids = jax.lax.broadcasted_iota(jnp.int32, (tile_v, LANES), 0)
    acc = jnp.full((tile_v, LANES), ident, dtype)
    for r in range(dst_loc_ref.shape[1]):  # static unroll over block rows
        d = dst_loc_ref[0, r:r + 1, :]
        hit = jnp.where(vids == d, row(r), ident)
        acc = jnp.minimum(acc, hit) if op == "min" else acc + hit
    fold = jnp.min if op == "min" else jnp.sum
    return fold(acc.T, axis=0, keepdims=True)


def _accumulate_tile(block_tile_ref, out_ref, acc, op: str = "min"):
    """Fold ``acc`` into the output tile, filling it with the identity on
    the tile's first block (the grid visits each tile's blocks
    consecutively)."""
    ident, dtype = _IDENTITY[op]
    i = pl.program_id(0)
    prev = block_tile_ref[jnp.maximum(i - 1, 0)]
    first = jnp.logical_or(i == 0, block_tile_ref[i] != prev)

    @pl.when(first)
    def _fill():
        out_ref[...] = jnp.full(out_ref.shape, ident, dtype)

    if op == "min":
        out_ref[0] = jnp.minimum(out_ref[0], acc)
    else:
        out_ref[0] = out_ref[0] + acc


def _edge_slab(x, nb: int, block_e: int):
    return x.reshape(nb, block_e // LANES, LANES)


def _specs(block_e: int, tile_v: int):
    """(edge block spec, output tile spec) for a 1-D grid over blocks;
    the index maps ignore the scalar-prefetch refs except the tile map."""
    rows = block_e // LANES
    edge = pl.BlockSpec((1, rows, LANES), lambda i, bt, *_: (i, 0, 0))
    tile = pl.BlockSpec((1, 1, tile_v), lambda i, bt, *_: (bt[i], 0, 0))
    return edge, tile


def _check_block(block_e: int) -> None:
    if block_e % LANES:
        raise ValueError(f"block_e must be a multiple of {LANES}, got {block_e}")


def _relax_min_kernel(
    # scalar prefetch
    block_tile_ref,      # i32[NB]   drives the out index_map
    window_ref,          # i32[2]
    # VMEM blocks
    dst_loc_ref,         # i32[1, R, 128]  dst - tile_base, in [0, tile_v)
    arr_ref,             # i32[1, R, 128]  source arrival (INT_INF if masked)
    ts_ref,              # i32[1, R, 128]
    te_ref,              # i32[1, R, 128]
    valid_ref,           # i32[1, R, 128]  1 = structurally valid
    init_ref,            # HBM, aliased to out
    out_ref,             # i32[1, 1, tile_v]
    *,
    tile_v: int,
    strict: bool,
):
    del init_ref
    ta = window_ref[0]
    tb = window_ref[1]

    def cand_row(r):
        arr = arr_ref[0, r:r + 1, :]
        ts = ts_ref[0, r:r + 1, :]
        te = te_ref[0, r:r + 1, :]
        follows = (arr < ts) if strict else (arr <= ts)
        ok = (
            (valid_ref[0, r:r + 1, :] != 0)
            & (ts >= ta) & (te <= tb)
            & follows & (arr < INT_INF)
        )
        return jnp.where(ok, te, INT_INF)

    acc = _tile_reduce(dst_loc_ref, cand_row, tile_v)
    _accumulate_tile(block_tile_ref, out_ref, acc)


@functools.partial(
    jax.jit, static_argnames=("n_tiles", "tile_v", "block_e", "strict", "interpret")
)
def temporal_relax_min_tiles(
    dst_local,      # i32[NB*block_e] grouped by tile (layout order)
    arr_src,        # i32[NB*block_e]
    t_start,        # i32[NB*block_e]
    t_end,          # i32[NB*block_e]
    valid,          # i32[NB*block_e]
    block_tile,     # i32[NB]
    window,         # i32[2]
    n_tiles: int,
    *,
    tile_v: int = 512,
    block_e: int = 1024,
    strict: bool = False,
    interpret: Optional[bool] = None,
):
    """Returns out[n_tiles, tile_v] of per-tile minima (INT_INF elsewhere)."""
    _check_block(block_e)
    nb = block_tile.shape[0]
    init = jnp.full((n_tiles, 1, tile_v), INT_INF, jnp.int32)
    edge_spec, tile_spec = _specs(block_e, tile_v)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[edge_spec] * 5 + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tile_spec,
    )
    kernel = functools.partial(_relax_min_kernel, tile_v=tile_v, strict=strict)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, 1, tile_v), jnp.int32),
        input_output_aliases={7: 0},  # init (arg 7 incl. prefetch) -> out
        interpret=resolve_interpret(interpret),
    )(
        block_tile, jnp.asarray(window, jnp.int32),
        *(_edge_slab(x, nb, block_e)
          for x in (dst_local, arr_src, t_start, t_end, valid)),
        init,
    )
    return out.reshape(n_tiles, tile_v)


# ---------------------------------------------------------------------------
# Generic tile segment-min / segment-sum: the combine half of the fused
# kernel, exposed so the engine's pallas_tiled backend can reduce
# *arbitrary* relax candidates (predicate already applied by the edgemap) —
# not just the EA relax — and one-feature f32 messages (PageRank's) in the
# same lane-packed slabs: a [V]-valued sum padded to segment_spmm's
# 128-wide feature tiles would move 128x its bytes.
# ---------------------------------------------------------------------------

def _segment_kernel(
    block_tile_ref,      # i32[NB] scalar prefetch (drives the out index_map)
    dst_loc_ref,         # i32[1, R, 128]  dst - tile_base, in [0, tile_v)
    val_ref,             # [1, R, 128]     values (the identity where masked)
    init_ref,            # HBM, aliased to out
    out_ref,             # [1, 1, tile_v]
    *,
    tile_v: int,
    op: str,
):
    del init_ref
    acc = _tile_reduce(
        dst_loc_ref, lambda r: val_ref[0, r:r + 1, :], tile_v, op)
    _accumulate_tile(block_tile_ref, out_ref, acc, op)


def _segment_tiles(dst_local, vals, block_tile, n_tiles, tile_v, block_e,
                   interpret, op):
    _check_block(block_e)
    ident, dtype = _IDENTITY[op]
    nb = block_tile.shape[0]
    init = jnp.full((n_tiles, 1, tile_v), ident, dtype)
    edge_spec, tile_spec = _specs(block_e, tile_v)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[edge_spec] * 2 + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tile_spec,
    )
    out = pl.pallas_call(
        functools.partial(_segment_kernel, tile_v=tile_v, op=op),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, 1, tile_v), dtype),
        input_output_aliases={3: 0},  # init (arg 3 incl. prefetch) -> out
        interpret=resolve_interpret(interpret),
    )(
        block_tile,
        _edge_slab(dst_local, nb, block_e),
        _edge_slab(vals.astype(dtype), nb, block_e),
        init,
    )
    return out.reshape(n_tiles, tile_v)


@functools.partial(
    jax.jit, static_argnames=("n_tiles", "tile_v", "block_e", "interpret")
)
def segment_min_tiles(
    dst_local,      # i32[NB*block_e] grouped by tile (layout order)
    cand,           # i32[NB*block_e] candidates, INT_INF where masked
    block_tile,     # i32[NB]
    n_tiles: int,
    *,
    tile_v: int = 512,
    block_e: int = 1024,
    interpret: Optional[bool] = None,
):
    """Returns out[n_tiles, tile_v] per-tile minima (INT_INF elsewhere)."""
    return _segment_tiles(dst_local, cand, block_tile, n_tiles, tile_v,
                          block_e, interpret, "min")


@functools.partial(
    jax.jit, static_argnames=("n_tiles", "tile_v", "block_e", "interpret")
)
def segment_sum_tiles(
    dst_local,      # i32[NB*block_e] grouped by tile (layout order)
    msgs,           # f32[NB*block_e] one-feature messages, 0 where masked
    block_tile,     # i32[NB]
    n_tiles: int,
    *,
    tile_v: int = 512,
    block_e: int = 1024,
    interpret: Optional[bool] = None,
):
    """Returns out[n_tiles, tile_v] f32 per-tile sums (0 elsewhere)."""
    return _segment_tiles(dst_local, msgs, block_tile, n_tiles, tile_v,
                          block_e, interpret, "sum")
