"""Jit'd wrappers binding the Pallas kernels to the graph-engine API.

``relax_min`` / ``spmm`` take plain edge arrays, apply the destination-tile
layout (built once per graph and cached by callers), invoke the kernel, and
unpack tiles back to a dense [V] / [V, D] result.  ``interpret=None``
leaves interpret mode to the platform (``repro.kernels.resolve_interpret``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.layout import TileLayout, build_tile_layout
from repro.kernels.segment_spmm import segment_spmm_tiles
from repro.kernels.temporal_edgemap import INT_INF, temporal_relax_min_tiles


def prepare_layout(dst, n_vertices: int, tile_v: int = 512, block_e: int = 1024) -> TileLayout:
    return build_tile_layout(np.asarray(dst), n_vertices, tile_v, block_e)


def _gather_padded(arr, perm, fill):
    safe = jnp.maximum(perm, 0)
    out = jnp.asarray(arr)[safe]
    return jnp.where(perm >= 0, out, fill)


def relax_min(
    layout: TileLayout,
    dst,
    arrival,         # i32[V] per-vertex state (source side)
    src,
    t_start,
    t_end,
    frontier,        # bool[V]
    window,
    *,
    strict: bool = False,
    interpret: Optional[bool] = None,
):
    """Fused temporal relax via the Pallas kernel: returns cand[V] minima."""
    perm = jnp.asarray(layout.perm)
    # pre-mask: non-frontier sources relax nothing -> arrival = INF
    arr_masked = jnp.where(frontier, arrival, INT_INF)
    arr_src = _gather_padded(arr_masked[jnp.asarray(src)], perm, INT_INF)
    dst_g = _gather_padded(jnp.asarray(dst), perm, 0)
    dst_local = dst_g - (dst_g // layout.tile_v) * layout.tile_v
    ts_g = _gather_padded(t_start, perm, 0)
    te_g = _gather_padded(t_end, perm, 0)
    valid = (perm >= 0).astype(jnp.int32)

    tiles = temporal_relax_min_tiles(
        dst_local, arr_src, ts_g, te_g, valid,
        jnp.asarray(layout.block_tile), jnp.asarray(window, jnp.int32),
        layout.n_tiles,
        tile_v=layout.tile_v, block_e=layout.block_e,
        strict=strict, interpret=interpret,
    )
    n_v = arrival.shape[0]
    return tiles.reshape(-1)[:n_v]


def earliest_arrival_kernel(
    g,
    layout: TileLayout,
    source: int,
    window,
    *,
    strict: bool = False,
    max_rounds: int = 0,
    interpret: Optional[bool] = None,
):
    """Earliest arrival executed through the Pallas relax kernel — the
    kernel as an engine backend rather than a standalone op.  Host fixpoint
    loop (round count = temporal diameter); each round is one fused
    gather->predicate->tile-segment-min kernel launch."""
    V = g.n_vertices
    arrival = jnp.full(V, INT_INF, jnp.int32).at[source].set(jnp.int32(window[0]))
    frontier = jnp.zeros(V, bool).at[source].set(True)
    max_rounds = max_rounds or V + 1
    for _ in range(max_rounds):
        cand = relax_min(
            layout, g.dst, arrival, g.src, g.t_start, g.t_end, frontier,
            window, strict=strict, interpret=interpret,
        )
        new = jnp.minimum(arrival, cand)
        frontier = new < arrival
        if not bool(frontier.any()):
            return new
        arrival = new
    return arrival


def spmm(
    layout: TileLayout,
    dst,
    messages,        # f32[E, D] per-edge messages (already gathered/scaled)
    *,
    n_vertices: int,
    valid_edges=None,
    tile_v: int = 256,
    block_e: int = 512,
    interpret: Optional[bool] = None,
):
    """Segment-sum of messages by destination via the Pallas kernel."""
    perm = jnp.asarray(layout.perm)
    dst_g = _gather_padded(jnp.asarray(dst), perm, 0)
    dst_local = dst_g - (dst_g // layout.tile_v) * layout.tile_v
    safe = jnp.maximum(perm, 0)
    msg_g = jnp.asarray(messages)[safe]
    valid = perm >= 0
    if valid_edges is not None:
        valid &= _gather_padded(valid_edges, perm, False)
    tiles = segment_spmm_tiles(
        dst_local, msg_g, valid.astype(jnp.int32),
        jnp.asarray(layout.block_tile), layout.n_tiles,
        tile_v=layout.tile_v, block_e=layout.block_e,
        interpret=interpret,
    )
    d = messages.shape[-1]
    return tiles.reshape(-1, d)[:n_vertices]
