"""Pallas TPU kernel: tiled segment-sum SpMM (gather -> one-hot MXU matmul
-> tile accumulate) — the GNN message-passing / EmbeddingBag primitive.

out[v, :] = sum over edges e with dst[e] == v of messages[e, :]

XLA's scatter-add serializes on TPU; with the destination-tile edge layout
each grid step turns its edge block into a [tile_v, block_e] one-hot matrix
and hits the MXU: out_tile += onehot @ messages_block.  This is the
standard dense-scatter trick (cf. MegaBlocks-style grouped matmuls) applied
to graph aggregation; arithmetic overhead is tile_v/avg_useful but runs at
MXU rather than scatter throughput.

Feature dim is additionally tiled by ``tile_d`` so (block_e x tile_d) and
(tile_v x tile_d) stay VMEM-resident and MXU-aligned (multiples of 128).
Destination ids arrive as a [R, 128] slab per block (edges along lanes);
each 128-edge row becomes a [tile_v, 128] one-hot (ids broadcast along
sublanes) and one matmul against that row's 128 messages.  Grid is
(feature tile, edge block) with the block axis innermost, so a tile's
consecutive blocks accumulate into one resident output block, zeroed on
the tile's first block; tiles no block owns keep the aliased zero init.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

LANES = 128


def _spmm_kernel(
    block_tile_ref,   # i32[NB] scalar prefetch
    dst_loc_ref,      # i32[1, R, 128]
    msg_ref,          # f32[1, block_e, tile_d]
    valid_ref,        # i32[1, R, 128]
    init_ref,         # HBM, aliased to out
    out_ref,          # f32[1, tile_v, tile_d]
    *,
    tile_v: int,
):
    del init_ref
    i = pl.program_id(1)
    prev = block_tile_ref[jnp.maximum(i - 1, 0)]

    @pl.when(jnp.logical_or(i == 0, block_tile_ref[i] != prev))
    def _fill():  # first block of this tile (blocks of a tile are consecutive)
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    vids = jax.lax.broadcasted_iota(jnp.int32, (tile_v, LANES), 0)
    contrib = jnp.zeros(out_ref.shape[1:], jnp.float32)
    for r in range(dst_loc_ref.shape[1]):  # static unroll over block rows
        onehot = ((vids == dst_loc_ref[0, r:r + 1, :])
                  & (valid_ref[0, r:r + 1, :] != 0))
        msg = msg_ref[0, r * LANES:(r + 1) * LANES, :]
        contrib = contrib + jax.lax.dot_general(
            onehot.astype(msg.dtype), msg,
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    out_ref[0] = out_ref[0] + contrib


@functools.partial(
    jax.jit,
    static_argnames=("n_tiles", "tile_v", "block_e", "tile_d", "interpret"),
)
def segment_spmm_tiles(
    dst_local,      # i32[NB*block_e] grouped by tile (layout order)
    messages,       # f32[NB*block_e, D]
    valid,          # i32[NB*block_e]
    block_tile,     # i32[NB]
    n_tiles: int,
    *,
    tile_v: int = 256,
    block_e: int = 512,
    tile_d: int = 128,
    interpret: Optional[bool] = None,
):
    """Returns out[n_tiles, tile_v, D] of per-tile feature sums."""
    if block_e % LANES:
        raise ValueError(f"block_e must be a multiple of {LANES}, got {block_e}")
    nb = block_tile.shape[0]
    rows = block_e // LANES
    d = messages.shape[-1]
    pad_d = (-d) % tile_d
    if pad_d:
        messages = jnp.pad(messages, ((0, 0), (0, pad_d)))
    dp = d + pad_d
    nd = dp // tile_d
    init = jnp.zeros((n_tiles, tile_v, dp), jnp.float32)

    # grid (feature tile, edge block): the edge-block axis is innermost so
    # a tile's consecutive blocks revisit one resident output block
    edge_spec = pl.BlockSpec((1, rows, LANES), lambda j, i, bt: (i, 0, 0))
    msg_spec = pl.BlockSpec((1, block_e, tile_d), lambda j, i, bt: (i, 0, j))
    tile_spec = pl.BlockSpec((1, tile_v, tile_d), lambda j, i, bt: (bt[i], 0, j))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nd, nb),
        in_specs=[edge_spec, msg_spec, edge_spec,
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tile_spec,
    )
    out = pl.pallas_call(
        functools.partial(_spmm_kernel, tile_v=tile_v),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, tile_v, dp), jnp.float32),
        input_output_aliases={4: 0},
        interpret=resolve_interpret(interpret),
    )(
        block_tile,
        dst_local.reshape(nb, rows, LANES),
        messages.astype(jnp.float32).reshape(nb, block_e, dp),
        valid.reshape(nb, rows, LANES),
        init,
    )
    return out[..., :d]
