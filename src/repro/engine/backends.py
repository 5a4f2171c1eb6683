"""ExecutionBackend: how a plan's combines actually execute (DESIGN.md §1).

Two implementations of the one protocol:

  * ``xla_segment``  — masked ``jax.ops.segment_{min,max,sum}`` (XLA lowers
    these to scatter; fine on CPU/GPU, serializing on TPU);
  * ``pallas_tiled`` — destination-tile fused kernels
    (kernels/layout.py + kernels/temporal_edgemap.py + kernels/segment_spmm.py):
    the scatter becomes a VMEM-local compare-select sweep (min), the same
    sweep adding (one-feature sum) or a one-hot MXU matmul (feature-matrix
    sum).  Interpret mode follows the platform
    (``repro.kernels.resolve_interpret``).

The pallas backend accelerates what the tile layout covers — int32 min and
f32 sum combines over the graph's native destination order (scan method,
out direction).  Everything else falls back to xla_segment, so a plan's
backend choice is a performance hint, never a correctness constraint —
but never a silent one: every combine a ``pallas_tiled`` plan sends to the
segment path is counted by reason in :func:`pallas_fallbacks` (once per
trace of the enclosing program, since the choice is made while tracing).
"""
from __future__ import annotations

from typing import Dict, Optional, Protocol

import jax
import jax.numpy as jnp

from repro.engine.plan import AccessPlan

INT_INF = jnp.iinfo(jnp.int32).max

_PALLAS_FALLBACKS: Dict[str, int] = {}


def pallas_fallbacks() -> Dict[str, int]:
    """Combines a ``pallas_tiled`` plan sent to the segment path, by reason
    (``ineligible``: dtype, rank or edge count the layout does not cover;
    ``not_layout_order``: segment ids not in the layout's edge order;
    ``edge_sharded``: an edge-sharded mesh body).  Counted at trace time."""
    return dict(_PALLAS_FALLBACKS)


def _fell_back(reason: str) -> None:
    _PALLAS_FALLBACKS[reason] = _PALLAS_FALLBACKS.get(reason, 0) + 1


def _identity(combine: str, dtype) -> jax.Array:
    if combine == "min":
        return jnp.array(INT_INF if jnp.issubdtype(dtype, jnp.integer) else jnp.inf, dtype)
    if combine == "max":
        return jnp.array(
            jnp.iinfo(jnp.int32).min if jnp.issubdtype(dtype, jnp.integer) else -jnp.inf,
            dtype,
        )
    if combine == "sum":
        return jnp.array(0, dtype)
    raise ValueError(combine)


_COLLECTIVE = dict(min=jax.lax.pmin, max=jax.lax.pmax, sum=jax.lax.psum)


def _masked_reduce(values, segment_ids, num_segments: int, combine: str,
                   mask=None):
    ident = _identity(combine, values.dtype)
    if mask is not None:
        m = mask
        while m.ndim < values.ndim:
            m = m[..., None]
        values = jnp.where(m, values, ident)
        # route invalid lanes to segment 0 (still identity-valued, harmless)
        segment_ids = jnp.where(mask, segment_ids, 0)
    fn = dict(
        min=jax.ops.segment_min, max=jax.ops.segment_max, sum=jax.ops.segment_sum
    )[combine]
    # segment_min/max fill empty segments with the dtype's max/min (the
    # identity), segment_sum with 0 — identity semantics hold without fixup.
    return fn(values, segment_ids, num_segments=num_segments)


def segment_combine(values, segment_ids, num_segments: int, combine: str,
                    mask=None, axis=None):
    """Masked segment-reduce; invalid lanes contribute the identity.  Named
    ``combine`` in the program.

    ``axis`` names a mesh axis the EDGE axis of ``values``/``segment_ids``
    is sharded over (DESIGN.md §7.7): each device reduces its local edge
    chunk into a full [num_segments] partial, then one ``pmin/pmax/psum``
    over the axis combines the partials — min/max/sum are associative and
    identity-padded, so the sharded result equals the unsharded one (sum
    up to f32 reduction order).  ``axis=None`` is the plain local reduce."""
    with jax.named_scope("combine"):
        out = _masked_reduce(values, segment_ids, num_segments, combine, mask)
        if axis is not None:
            out = _COLLECTIVE[combine](out, axis_name=axis)
        return out


def segment_combine_windows(values, segment_ids, num_segments: int,
                            combine: str, masks=None, axis=None):
    """Batched masked segment-reduce over a shared edge set (DESIGN.md §6):
    ``values`` is [W, K, ...] (one candidate row per query window), ``masks``
    [W, K]; ``segment_ids`` [K] is shared across windows.  Returns
    [W, num_segments, ...] — W reductions over ONE gathered edge set.
    ``axis`` as in :func:`segment_combine`: one cross-edge-shard collective
    per call, applied to the whole [W, num_segments] partial at once.
    Named ``combine`` in the program."""
    with jax.named_scope("combine"):
        if masks is None:
            out = jax.vmap(
                lambda v: _masked_reduce(v, segment_ids, num_segments,
                                         combine)
            )(values)
        else:
            out = jax.vmap(
                lambda v, m: _masked_reduce(v, segment_ids, num_segments,
                                            combine, mask=m)
            )(values, masks)
        if axis is not None:
            out = _COLLECTIVE[combine](out, axis_name=axis)
        return out


class ExecutionBackend(Protocol):
    """Backend protocol: execute a (masked) segment combine, single-window
    or batched over a window axis sharing one edge set."""

    name: str

    def combine(self, plan: Optional[AccessPlan], values, segment_ids,
                num_segments: int, op: str, mask=None):
        ...

    def combine_windows(self, plan: Optional[AccessPlan], values, segment_ids,
                        num_segments: int, op: str, masks=None):
        ...


class XlaSegmentBackend:
    """Today's masked segment-reduce, unchanged."""

    name = "xla_segment"

    def combine(self, plan, values, segment_ids, num_segments, op, mask=None):
        del plan
        return segment_combine(values, segment_ids, num_segments, op, mask=mask)

    def combine_windows(self, plan, values, segment_ids, num_segments, op,
                        masks=None):
        del plan
        return segment_combine_windows(values, segment_ids, num_segments, op,
                                       masks=masks)


class PallasTiledBackend:
    """Destination-tile fused kernels, selected by the plan's layout.

    ``combine`` expects ``segment_ids`` in the same edge order the layout
    was built from (the graph's native order; callers gate on that).
    """

    name = "pallas_tiled"

    # -- eligibility (static, trace-time) -----------------------------------
    def _supports(self, plan, values, num_segments, op) -> bool:
        if plan is None or plan.layout_perm.shape[0] == 0:
            return False
        if plan.n_edges and values.shape[0] != plan.n_edges:
            return False
        if num_segments > plan.n_tiles * plan.tile_v:
            return False
        if op == "min":
            return values.ndim == 1 and values.dtype == jnp.int32
        if op == "sum":
            return (
                values.ndim in (1, 2)
                and jnp.issubdtype(values.dtype, jnp.floating)
            )
        return False

    def _gathered(self, plan, segment_ids):
        perm = plan.layout_perm
        safe = jnp.maximum(perm, 0)
        seg_g = jnp.where(perm >= 0, jnp.asarray(segment_ids)[safe], 0)
        dst_local = seg_g - (seg_g // plan.tile_v) * plan.tile_v
        return safe, perm >= 0, dst_local

    def combine(self, plan, values, segment_ids, num_segments, op, mask=None):
        if not self._supports(plan, values, num_segments, op):
            _fell_back("ineligible")
            return segment_combine(values, segment_ids, num_segments, op, mask=mask)
        if op == "min":
            return self._combine_min(plan, values, segment_ids, num_segments, mask)
        return self._combine_sum(plan, values, segment_ids, num_segments, mask)

    def combine_windows(self, plan, values, segment_ids, num_segments, op,
                        masks=None):
        """Batched combine over a window axis: the layout gather happens once,
        then the tiled kernel runs per window under ``lax.map`` (one trace,
        W sequential kernel launches — the kernel itself is not re-batched)."""
        if not self._supports(plan, values[0], num_segments, op):
            _fell_back("ineligible")
            return segment_combine_windows(values, segment_ids, num_segments,
                                           op, masks=masks)
        if op == "min":
            return self._combine_min_windows(
                plan, values, segment_ids, num_segments, masks)
        return self._combine_sum_windows(
            plan, values, segment_ids, num_segments, masks)

    def _combine_min(self, plan, values, segment_ids, num_segments, mask):
        from repro.kernels.temporal_edgemap import segment_min_tiles

        cand = values if mask is None else jnp.where(mask, values, INT_INF)
        safe, in_perm, dst_local = self._gathered(plan, segment_ids)
        cand_g = jnp.where(in_perm, cand[safe], INT_INF)
        tiles = segment_min_tiles(
            dst_local, cand_g, plan.layout_block_tile, plan.n_tiles,
            tile_v=plan.tile_v, block_e=plan.block_e)
        return tiles.reshape(-1)[:num_segments]

    def _combine_sum(self, plan, values, segment_ids, num_segments, mask):
        from repro.kernels.segment_spmm import segment_spmm_tiles
        from repro.kernels.temporal_edgemap import segment_sum_tiles

        safe, in_perm, dst_local = self._gathered(plan, segment_ids)
        valid = in_perm if mask is None else in_perm & mask[safe]
        if values.ndim == 1:  # one feature: lane-packed, no feature padding
            msg_g = jnp.where(valid, values[safe], 0)
            tiles = segment_sum_tiles(
                dst_local, msg_g, plan.layout_block_tile, plan.n_tiles,
                tile_v=plan.tile_v, block_e=plan.block_e)
            return tiles.reshape(-1)[:num_segments]
        tiles = segment_spmm_tiles(
            dst_local, values[safe], valid.astype(jnp.int32),
            plan.layout_block_tile, plan.n_tiles,
            tile_v=plan.tile_v, block_e=plan.block_e)
        return tiles.reshape(-1, values.shape[-1])[:num_segments]

    # -- batched-window variants (shared layout gather, per-window kernel) ---
    def _combine_min_windows(self, plan, values, segment_ids, num_segments,
                             masks):
        from repro.kernels.temporal_edgemap import segment_min_tiles

        cand = values if masks is None else jnp.where(masks, values, INT_INF)
        safe, in_perm, dst_local = self._gathered(plan, segment_ids)
        cand_g = jnp.where(in_perm[None, :], cand[:, safe], INT_INF)  # [W, Ep]

        def one(c):
            tiles = segment_min_tiles(
                dst_local, c, plan.layout_block_tile, plan.n_tiles,
                tile_v=plan.tile_v, block_e=plan.block_e)
            return tiles.reshape(-1)[:num_segments]

        return jax.lax.map(one, cand_g)

    def _combine_sum_windows(self, plan, values, segment_ids, num_segments,
                             masks):
        from repro.kernels.segment_spmm import segment_spmm_tiles
        from repro.kernels.temporal_edgemap import segment_sum_tiles

        safe, in_perm, dst_local = self._gathered(plan, segment_ids)
        if masks is None:
            valid = jnp.broadcast_to(in_perm, (values.shape[0], in_perm.shape[0]))
        else:
            valid = in_perm[None, :] & masks[:, safe]
        if values.ndim == 2:  # [W, K]: one feature per window, lane-packed
            msg_g = jnp.where(valid, values[:, safe], 0)

            def one(m):
                tiles = segment_sum_tiles(
                    dst_local, m, plan.layout_block_tile, plan.n_tiles,
                    tile_v=plan.tile_v, block_e=plan.block_e)
                return tiles.reshape(-1)[:num_segments]

            return jax.lax.map(one, msg_g)

        def one(args):
            m, v = args
            tiles = segment_spmm_tiles(
                dst_local, m, v.astype(jnp.int32),
                plan.layout_block_tile, plan.n_tiles,
                tile_v=plan.tile_v, block_e=plan.block_e)
            return tiles.reshape(-1, m.shape[-1])[:num_segments]

        return jax.lax.map(one, (values[:, safe, :], valid))


_BACKENDS = {
    "xla_segment": XlaSegmentBackend(),
    "pallas_tiled": PallasTiledBackend(),
}


def get_backend(name: str) -> ExecutionBackend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(f"unknown backend {name!r}; have {sorted(_BACKENDS)}")


def _takes_layout(plan, use_layout: bool, axis) -> bool:
    """Whether a plan-directed combine runs the tiled kernels; a
    ``pallas_tiled`` plan that cannot is counted as a fallback."""
    if plan is None or plan.backend != "pallas_tiled":
        return False
    if axis is not None:
        _fell_back("edge_sharded")
        return False
    if not use_layout:
        _fell_back("not_layout_order")
        return False
    return True


def combine_for_plan(
    plan: Optional[AccessPlan],
    values,
    segment_ids,
    num_segments: int,
    op: str,
    mask=None,
    *,
    use_layout: bool = False,
):
    """Plan-directed combine.  ``use_layout=True`` asserts the caller's
    ``segment_ids`` are in the edge order the plan's layout was built from
    (scan view, reduce-into-destination); only then may the tiled kernels
    run.  All other combines take the xla path.  A plan carrying
    ``edge_axis`` (an edge-sharded shard_map body, DESIGN.md §7.7) always
    takes the segment path — the tile layout is a whole-graph static
    grouping that does not partition along the ring shards — and finishes
    with the one cross-shard collective."""
    axis = None if plan is None else plan.edge_axis
    if _takes_layout(plan, use_layout, axis):
        return get_backend("pallas_tiled").combine(
            plan, values, segment_ids, num_segments, op, mask=mask
        )
    return segment_combine(values, segment_ids, num_segments, op, mask=mask,
                           axis=axis)


def combine_windows_for_plan(
    plan: Optional[AccessPlan],
    values,           # [W, K, ...]
    segment_ids,      # [K] shared across windows
    num_segments: int,
    op: str,
    masks=None,       # [W, K]
    *,
    use_layout: bool = False,
):
    """Batched plan-directed combine (DESIGN.md §6): W per-window reductions
    over ONE shared candidate edge set, returning [W, num_segments, ...].
    Same layout-eligibility (and ``edge_axis``) contract as
    :func:`combine_for_plan`."""
    axis = None if plan is None else plan.edge_axis
    if _takes_layout(plan, use_layout, axis):
        return get_backend("pallas_tiled").combine_windows(
            plan, values, segment_ids, num_segments, op, masks=masks
        )
    return segment_combine_windows(values, segment_ids, num_segments, op,
                                   masks=masks, axis=axis)


__all__ = [
    "ExecutionBackend",
    "XlaSegmentBackend",
    "PallasTiledBackend",
    "segment_combine",
    "segment_combine_windows",
    "get_backend",
    "pallas_fallbacks",
    "combine_for_plan",
    "combine_windows_for_plan",
]
