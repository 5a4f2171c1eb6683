"""TemporalEdgeMap / VertexMap — the Ligra-style programming model extended
to time (paper §4.4, Table 2), in SPMD/XLA form.

Frontier representation: a dense boolean mask over vertices (CPU Ligra
switches between sparse and dense frontiers; on TPU the dense form is the
vectorizable one, and frontier emptiness is a cheap ``jnp.any``).

Access paths (selective indexing, paper §5) are no longer chosen here by a
bare string: the edgemap executes an :class:`repro.engine.AccessPlan`
produced by ``repro.engine.plan_query`` — method (scan | index | hybrid),
budgets, and execution backend (xla_segment | pallas_tiled) in one static
record (DESIGN.md §1).  All paths are semantically identical
(property-tested); they differ only in work, which is the paper's entire
design point.

Batched multi-window execution (DESIGN.md §6): ``temporal_edge_map_batched``
serves W query windows from ONE edge view built over their union window —
the gather is paid once, each window contributes only a validity mask, and
the combine emits [W, V] in one plan-directed batched reduction.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.predicates import OrderingPredicateType, edge_follows, in_window
from repro.core.temporal_graph import TemporalGraph
from repro.core.tger import TGERIndex, gather_window_edges, window_range
from repro.engine.backends import (  # noqa: F401 (re-export)
    combine_for_plan,
    combine_windows_for_plan,
    segment_combine,
    segment_combine_windows,
)
from repro.engine.frontier import (  # noqa: F401 (re-export)
    FrontierView,
    advance_frontier_view,
    build_frontier_view,
    companion_for_view,
)
from repro.engine.plan import AccessPlan, make_plan

INT_INF = jnp.iinfo(jnp.int32).max
FLOAT_INF = jnp.float32(jnp.inf)


class EdgeView(NamedTuple):
    """A (possibly gathered) set of candidate temporal edges."""

    src: jax.Array      # i32[K]
    dst: jax.Array      # i32[K]
    t_start: jax.Array  # i32[K]
    t_end: jax.Array    # i32[K]
    weight: jax.Array   # f32[K]
    mask: jax.Array     # bool[K] — structural validity (gather padding)


def scan_view(g: TemporalGraph) -> EdgeView:
    return EdgeView(
        g.src, g.dst, g.t_start, g.t_end, g.weight,
        jnp.ones(g.n_edges, dtype=bool),
    )


def index_view(g: TemporalGraph, idx: TGERIndex, window, budget: int) -> EdgeView:
    """Gather the <=budget edges whose start time lies in the window, via the
    global time-first permutation: O(log E) search + O(budget) gather."""
    lo, hi = window_range(idx, window[0], window[1])
    eids, pos = gather_window_edges(idx, lo, budget)
    mask = pos < hi
    return EdgeView(
        g.src[eids], g.dst[eids], g.t_start[eids], g.t_end[eids],
        g.weight[eids], mask,
    )


def hybrid_view(g: TemporalGraph, idx: TGERIndex, window,
                per_vertex_budget: int) -> EdgeView:
    """Heavy/light per-vertex-class access (paper §5 at vertex granularity).

    Light edges (sources below the indexing cutoff) are scanned; each HEAVY
    vertex contributes only its per-vertex TGER window range — a vectorized
    ``bounded_searchsorted`` over its start-sorted T-CSR slice — gathered
    under a shared static ``per_vertex_budget``.  Work is
    O(E_light + H·(log deg + K)) instead of O(E): the skewed-hub regime the
    paper's selective indexing targets.

    XLA static-shape deviation (DESIGN.md §2): the paper lets an unselective
    heavy vertex fall back to scanning its own adjacency; with static shapes
    that costs the same as scanning everything, so here heavy vertices are
    always index-accessed and completeness requires per_vertex_budget >=
    each heavy vertex's in-window degree (callers size it from the
    per-vertex SAT estimates; the view is exact whenever the budget covers —
    property-tested).
    """
    from repro.core.tger import vertex_range

    ws = jnp.asarray(window[0], jnp.int32)
    we = jnp.asarray(window[1], jnp.int32)
    # light partition: static gather of the unindexed-source edges
    le = idx.light_eids
    l_mask = jnp.arange(le.shape[0]) < idx.n_light_edges
    l_view = (g.src[le], g.dst[le], g.t_start[le], g.t_end[le], g.weight[le], l_mask)

    # heavy partition: per-vertex window ranges, budgeted gather
    hv = jnp.maximum(idx.indexed_ids, 0)                       # [H]
    lo, hi = vertex_range(g, hv, ws, we)                       # [H], [H]
    pos = lo[:, None] + jnp.arange(per_vertex_budget)[None, :]  # [H, K]
    h_mask = (pos < hi[:, None]) & (idx.indexed_ids >= 0)[:, None]
    pos_c = jnp.minimum(pos, g.n_edges - 1).reshape(-1)
    h_view = (
        g.src[pos_c], g.dst[pos_c], g.t_start[pos_c], g.t_end[pos_c],
        g.weight[pos_c], h_mask.reshape(-1),
    )
    return EdgeView(*[
        jnp.concatenate([l, h]) for l, h in zip(l_view, h_view)
    ])


def hybrid_budget(g: TemporalGraph, idx: TGERIndex, window,
                  floor: int = 16) -> int:
    """Static per-vertex budget guaranteeing hybrid_view completeness.
    Thin wrapper over the engine planner's vectorized implementation."""
    from repro.engine.plan import per_vertex_window_budget

    return per_vertex_window_budget(
        g, idx, (int(window[0]), int(window[1])), floor=floor
    )


# ---------------------------------------------------------------------------
# Plan-directed view building
# ---------------------------------------------------------------------------

def ensure_plan(plan: Optional[AccessPlan]) -> AccessPlan:
    """``plan=None`` means the default full-scan plan on xla_segment."""
    return plan if plan is not None else make_plan("scan")


def view_for_plan(
    g: TemporalGraph,
    tger: Optional[TGERIndex],
    window,
    plan: AccessPlan,
) -> EdgeView:
    """Build the candidate-edge view the plan's method prescribes."""
    if plan.method == "index":
        if tger is None or plan.budget <= 0:
            raise ValueError("index access requires a TGER and a positive budget")
        return index_view(g, tger, window, plan.budget)
    if plan.method == "hybrid":
        if tger is None or plan.per_vertex_budget <= 0:
            raise ValueError("hybrid access requires a TGER and a per-vertex budget")
        return hybrid_view(g, tger, window, plan.per_vertex_budget)
    return scan_view(g)


# ---------------------------------------------------------------------------
# Ring-buffer views (DESIGN.md §7.3)
#
# The incremental sliding-window server needs the view to be POSITIONALLY
# STABLE across advances: the slot an edge occupies must not depend on the
# current window, so a forward slide touches only the entering positions.
# The identity is ``slot(p) = p mod C`` over the relevant time-first
# permutation (global for index plans, heavy-only for hybrid plans, with the
# light partition a window-independent static prefix).  An advance from
# ``lo`` to ``lo'`` then re-gathers exactly the entering positions
# [lo + C, lo' + C) — fixed-shape scatters of ``delta_budget`` positions, as
# many as the slide needs, in one loop — and recomputes the O(C) validity
# mask from the new [lo, hi); every surviving slot's payload is untouched,
# so the advanced buffer is bit-identical to a cold ring build at the new
# window (property-tested, wrap-around included).
# ---------------------------------------------------------------------------

def ring_positions(lo, capacity: int) -> jax.Array:
    """Time-first position resident in each ring slot: the unique
    p in [lo, lo+capacity) with p ≡ slot (mod capacity)."""
    s = jnp.arange(capacity, dtype=jnp.int32)
    lo = jnp.asarray(lo, jnp.int32)
    return lo + jnp.mod(s - lo, capacity)


def scatter_entering(arrays, fields, perm, lo_prev, lo_new, *,
                     capacity: int, chunk: int, slot_of: Callable):
    """Write the positions [lo_prev + C, lo_new + C) that enter a ring slid
    forward from ``lo_prev`` to ``lo_new`` into ``arrays`` (the ring's five
    edge fields), at the slots ``slot_of(enter, ok)`` gives (out of range
    = dropped).  ``chunk`` positions per step of a loop that runs as many
    steps as the slide needs, so one compiled program serves every slide
    up to C."""
    first = jnp.asarray(lo_prev, jnp.int32) + capacity
    end = jnp.asarray(lo_new, jnp.int32) + capacity

    def step(i, arrays):
        enter = first + i * chunk + jnp.arange(chunk, dtype=jnp.int32)
        eids = perm[jnp.minimum(enter, perm.shape[0] - 1)]
        slots = slot_of(enter, enter < end)
        return tuple(p.at[slots].set(f[eids], mode="drop")
                     for p, f in zip(arrays, fields))

    return jax.lax.fori_loop(0, (end - first + chunk - 1) // chunk, step,
                             tuple(arrays))


def _gather_fields(g: TemporalGraph, eids):
    return (g.src[eids], g.dst[eids], g.t_start[eids], g.t_end[eids],
            g.weight[eids])


@functools.partial(jax.jit, static_argnames=("capacity",))
def index_ring_view(g: TemporalGraph, idx: TGERIndex, lo, hi, *,
                    capacity: int) -> EdgeView:
    """Cold build of the index-plan ring view: slot p%C holds time-first
    position p for p in [lo, lo+C), masked to the valid [lo, hi).  Holds
    the same edge SET as ``index_view(g, idx, window, budget=C)`` — only
    slot order differs, which no masked segment combine can observe."""
    pos = ring_positions(lo, capacity)
    eids = idx.perm_by_start[jnp.minimum(pos, g.n_edges - 1)]
    return EdgeView(*_gather_fields(g, eids), pos < hi)


def advance_index_ring_fields(fields, perm, prev: EdgeView, lo_prev, lo_new,
                              hi_new, *, capacity: int,
                              delta_budget: int) -> EdgeView:
    """Raw-array form of :func:`advance_index_ring` — ``fields`` is the
    (src, dst, t_start, t_end, weight) tuple and ``perm`` the time-first
    permutation.  The serving hot loop passes exactly these arrays instead
    of the full graph/TGER pytrees: per-call pytree flattening is real
    dispatch latency at serving budgets."""
    new = scatter_entering(
        prev[:5], fields, perm, lo_prev, lo_new, capacity=capacity,
        chunk=delta_budget,
        slot_of=lambda enter, ok: jnp.where(
            ok, jnp.mod(enter, capacity), capacity))   # OOB -> dropped
    return EdgeView(*new, ring_positions(lo_new, capacity) < hi_new)


def advance_index_ring(g: TemporalGraph, idx: TGERIndex, prev: EdgeView,
                       lo_prev, lo_new, hi_new, *, capacity: int,
                       delta_budget: int) -> EdgeView:
    """Slide the index ring forward: scatter only the ENTERING positions
    [lo_prev+C, lo_new+C) into the slots they own (the ones being vacated),
    ``delta_budget`` of them per scatter, then recompute the mask.
    Requires 0 <= lo_new - lo_prev <= C (host-checked by the server; it
    falls cold otherwise)."""
    return advance_index_ring_fields(
        (g.src, g.dst, g.t_start, g.t_end, g.weight), idx.perm_by_start,
        prev, lo_prev, lo_new, hi_new,
        capacity=capacity, delta_budget=delta_budget)


@functools.partial(jax.jit, static_argnames=("capacity",))
def hybrid_ring_view(g: TemporalGraph, idx: TGERIndex, lo, hi, *,
                     capacity: int) -> EdgeView:
    """Cold build of the hybrid ring view: the light partition is a static
    (window-independent) prefix, the heavy partition a ring over the HEAVY
    time-first permutation — [lo, hi) are positions in that order.  Holds
    the same edge SET as a completeness-budgeted ``hybrid_view`` (light
    edges + heavy in-window-start edges); the per-vertex gather becomes one
    contiguous positional range, which is what makes the advance a delta."""
    le = idx.light_eids
    l_mask = jnp.arange(le.shape[0]) < idx.n_light_edges
    pos = ring_positions(lo, capacity)
    eids = idx.heavy_perm_by_start[
        jnp.minimum(pos, idx.heavy_perm_by_start.shape[0] - 1)]
    fields = [
        jnp.concatenate([l, h])
        for l, h in zip(_gather_fields(g, le), _gather_fields(g, eids))
    ]
    return EdgeView(*fields, jnp.concatenate([l_mask, pos < hi]))


def advance_hybrid_ring_fields(fields, heavy_perm, prev: EdgeView, lo_prev,
                               lo_new, hi_new, *, capacity: int,
                               delta_budget: int) -> EdgeView:
    """Raw-array form of :func:`advance_hybrid_ring`.  The light-prefix
    length is recovered from the resident buffer (``len - capacity``), so
    only the five edge arrays and the heavy permutation travel."""
    L = prev.src.shape[0] - capacity
    new = scatter_entering(
        prev[:5], fields, heavy_perm, lo_prev, lo_new, capacity=capacity,
        chunk=delta_budget,
        slot_of=lambda enter, ok: jnp.where(
            ok, L + jnp.mod(enter, capacity), prev.src.shape[0]))
    h_mask = ring_positions(lo_new, capacity) < hi_new
    mask = jax.lax.dynamic_update_slice_in_dim(prev.mask, h_mask, L, 0)
    return EdgeView(*new, mask)


def advance_hybrid_ring(g: TemporalGraph, idx: TGERIndex, prev: EdgeView,
                        lo_prev, lo_new, hi_new, *, capacity: int,
                        delta_budget: int) -> EdgeView:
    """Slide the hybrid ring's heavy partition forward (positions over the
    heavy time-first permutation); the light prefix is untouched."""
    return advance_hybrid_ring_fields(
        (g.src, g.dst, g.t_start, g.t_end, g.weight), idx.heavy_perm_by_start,
        prev, lo_prev, lo_new, hi_new,
        capacity=capacity, delta_budget=delta_budget)


def ring_companion_delta(src_field, perm, prev: EdgeView, lo_prev, lo_new,
                         *, capacity: int, light_prefix: int = 0):
    """Host-side ``(slots, old_from, new_from)`` delta of one ring advance
    — the exact triplet :func:`advance_frontier_view` consumes to keep a
    frontier-rung companion (DESIGN.md §7.9) in sync with an advanced ring
    instead of re-sorting it.  ``prev`` is the view BEFORE the advance;
    ``src_field``/``perm`` are the graph's src column and the (index:
    global, hybrid: heavy) time-first permutation; ``light_prefix`` offsets
    hybrid slot ids past the static light partition.  The entering
    positions [lo_prev + C, lo_new + C) mirror the advance's own scatter —
    end-of-stream positions clamp to the last permutation entry exactly
    like ``advance_*_ring_fields`` does, so the delta matches the resident
    payload bit-for-bit (those slots are masked dead either way).  The
    advance contract (lo_new - lo_prev <= capacity) makes the slots
    distinct, as ``advance_frontier_view`` requires."""
    import numpy as np

    lo_prev, lo_new = int(lo_prev), int(lo_new)
    enter = np.arange(lo_prev + capacity, lo_new + capacity, dtype=np.int64)
    slots = (light_prefix + (enter % capacity)).astype(np.int32)
    perm = np.asarray(perm)
    eids = perm[np.minimum(enter, perm.shape[0] - 1)]
    old_from = np.asarray(prev.src)[slots]
    new_from = np.asarray(src_field)[eids]
    return slots, old_from, new_from


def ring_view_for_plan(
    g: TemporalGraph,
    tger: Optional[TGERIndex],
    window,
    plan: AccessPlan,
) -> Tuple[EdgeView, int, int, int]:
    """Host-level cold ring build for the plan's method: returns
    ``(edges, lo, hi, capacity)`` with (lo, hi) the host-side position range
    the server's advance bookkeeping slides (-1/-1/0 for scan, whose 'ring'
    is the untouched full view)."""
    from repro.core.tger import (
        heavy_window_positions_host,
        window_positions_host,
    )
    from repro.engine.plan import rung

    if plan.method == "index":
        if tger is None or plan.budget <= 0:
            raise ValueError("index access requires a TGER and a positive budget")
        lo, hi = window_positions_host(tger, window)
        capacity = plan.ring_capacity or plan.budget
        if hi - lo > capacity:
            # a pinned plan whose rung predates this window: the ring can
            # only hold positions [lo, lo+C) and the mask would silently
            # validate slots the gather never filled — refuse instead of
            # serving a partial view (planner-built plans always cover;
            # only an explicit stale plan= can get here)
            raise ValueError(
                f"window {(int(window[0]), int(window[1]))} spans "
                f"{hi - lo} time-first positions but the pinned index "
                f"plan's ring capacity is {capacity}: under this plan the "
                f"serving horizon is the {capacity} most recent in-window "
                f"positions (>= position {hi - capacity}), and positions "
                f"[{lo}, {hi - capacity}) are below it.  Serve historical "
                f"windows through the cold tier (serve_batch(..., "
                f"coldstore=ColdStore(g, tger))) or drop the pinned plan "
                f"so the planner re-rungs the capacity")
        return index_ring_view(g, tger, lo, hi, capacity=capacity), lo, hi, capacity
    if plan.method == "hybrid":
        if tger is None:
            raise ValueError("hybrid access requires a TGER")
        lo, hi = heavy_window_positions_host(tger, window)
        capacity = plan.ring_capacity or rung(max(hi - lo, 16))
        if hi - lo > capacity:  # plan's rung predates this window: re-rung
            capacity = rung(hi - lo)
        return hybrid_ring_view(g, tger, lo, hi, capacity=capacity), lo, hi, capacity
    return scan_view(g), -1, -1, 0


RelaxFn = Callable[[EdgeView, jax.Array], Tuple[jax.Array, jax.Array]]
# relax(edges, src_state_gathered) -> (candidate_values[K,...], extra_valid[K])


def _endpoints(edges: EdgeView, direction: str):
    if direction == "out":
        return edges.src, edges.dst
    if direction == "in":
        return edges.dst, edges.src
    raise ValueError(direction)


def union_window(windows) -> Tuple[jax.Array, jax.Array]:
    """The hull [min t0, max t1] of a [W, 2] window batch — the one window a
    batched sweep's shared edge view must cover."""
    w = jnp.asarray(windows, jnp.int32).reshape(-1, 2)
    return jnp.min(w[:, 0]), jnp.max(w[:, 1])


def edge_map_over_view(
    edges: EdgeView,
    window: Tuple[jax.Array, jax.Array],
    frontier: jax.Array,            # bool[V]
    src_state,                      # pytree of [V, ...] arrays gathered at source side
    relax: RelaxFn,
    combine: str,
    *,
    plan: AccessPlan,
    n_vertices: int,
    direction: str = "out",
    check_window: bool = True,
    compute_touched: bool = True,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """One relaxation round over a PREBUILT edge view (the round core shared
    by the single-window and batched edgemaps; sweeps that hoist the view
    out of their fixpoint loop call this directly).
    ``compute_touched=False`` skips the extra per-round segment-sum when the
    caller derives its frontier from the combined values (every fixpoint
    loop does) and returns ``touched=None``."""
    from_v, to_v = _endpoints(edges, direction)

    valid = edges.mask & frontier[from_v]
    if check_window:
        valid &= in_window(edges.t_start, edges.t_end, window[0], window[1])

    gathered = jax.tree_util.tree_map(lambda a: a[from_v], src_state)
    cand, extra = relax(edges, gathered)
    valid &= extra

    # layout eligibility is static: native dst order only
    use_layout = plan.method == "scan" and direction == "out"
    out = combine_for_plan(
        plan, cand, to_v, n_vertices, combine, mask=valid,
        use_layout=use_layout,
    )
    if not compute_touched:
        return out, None
    touched = segment_combine(
        valid.astype(jnp.int32), to_v, n_vertices, "sum", mask=None,
        axis=plan.edge_axis,
    ) > 0
    return out, touched


def temporal_edge_map(
    g: TemporalGraph,
    window: Tuple[jax.Array, jax.Array],
    frontier: jax.Array,            # bool[V]
    src_state,                      # pytree of [V, ...] arrays gathered at source side
    relax: RelaxFn,
    combine: str,
    *,
    pred: Optional[OrderingPredicateType] = None,
    direction: str = "out",         # 'out': reduce into dst; 'in': reduce into src
    tger: Optional[TGERIndex] = None,
    plan: Optional[AccessPlan] = None,
    check_window: bool = True,
    compute_touched: bool = True,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Apply one round of temporal edge relaxation under an AccessPlan.

    Returns (combined[V, ...], touched[V]) where ``touched`` marks segments
    that received at least one valid contribution; ``compute_touched=False``
    skips that extra segment-sum and returns ``touched=None``.  The ordering
    predicate is evaluated inside ``relax`` (it needs algorithm state);
    ``pred`` is accepted for symmetry with Table 2 and handed to relax via
    closure by the algorithm implementations.

    The plan's backend executes the main combine; the tiled Pallas path is
    eligible when reducing into destinations over the graph's native edge
    order (scan method, out direction) — otherwise execution falls back to
    the masked segment-reduce.
    """
    plan = ensure_plan(plan)
    edges = view_for_plan(g, tger, window, plan)
    return edge_map_over_view(
        edges, window, frontier, src_state, relax, combine,
        plan=plan, n_vertices=g.n_vertices,
        direction=direction, check_window=check_window,
        compute_touched=compute_touched,
    )


def edge_map_over_view_batched(
    edges: EdgeView,
    windows: jax.Array,             # i32[W, 2]
    frontiers: jax.Array,           # bool[W, V]
    src_state,                      # pytree of [W, V, ...] per-window state
    relax: RelaxFn,
    combine: str,
    *,
    plan: AccessPlan,
    n_vertices: int,
    direction: str = "out",
    check_window: bool = True,
    compute_touched: bool = True,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """One batched relaxation round over a PREBUILT (union-window) view:
    per-window masking is vmapped over the shared candidate edges and the
    combine executes once as a [W, ·] batched reduction — no per-window
    re-gather (DESIGN.md §6).  ``compute_touched=False`` skips the W extra
    segment-sums when the caller derives its frontier from the combined
    values (the batched fixpoint loops do) and returns ``touched=None``."""
    from_v, to_v = _endpoints(edges, direction)

    def per_window(window, frontier, state):
        valid = edges.mask & frontier[from_v]
        if check_window:
            valid &= in_window(edges.t_start, edges.t_end, window[0], window[1])
        gathered = jax.tree_util.tree_map(lambda a: a[from_v], state)
        cand, extra = relax(edges, gathered)
        return cand, valid & extra

    cand, valid = jax.vmap(per_window)(
        jnp.asarray(windows, jnp.int32), frontiers, src_state
    )

    use_layout = plan.method == "scan" and direction == "out"
    out = combine_windows_for_plan(
        plan, cand, to_v, n_vertices, combine, masks=valid,
        use_layout=use_layout,
    )
    if not compute_touched:
        return out, None
    touched = jax.vmap(
        lambda v: segment_combine(v.astype(jnp.int32), to_v, n_vertices, "sum",
                                  axis=plan.edge_axis)
    )(valid) > 0
    return out, touched


def temporal_edge_map_batched(
    g: TemporalGraph,
    windows,                        # i32[W, 2] query windows
    frontiers: jax.Array,           # bool[W, V]
    src_state,                      # pytree of [W, V, ...]
    relax: RelaxFn,
    combine: str,
    *,
    pred: Optional[OrderingPredicateType] = None,
    direction: str = "out",
    tger: Optional[TGERIndex] = None,
    plan: Optional[AccessPlan] = None,
    check_window: bool = True,
    compute_touched: bool = True,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Batched multi-window TemporalEdgeMap: ONE edge view built over the
    union window serves all W windows; returns (combined[W, V, ...],
    touched[W, V] — or ``None`` under ``compute_touched=False``).  Plans
    produced by ``plan_query(..., windows=[...])`` budget for the union, so
    each window's valid edges are a masked subset of the one gathered
    candidate set."""
    plan = ensure_plan(plan)
    windows = jnp.asarray(windows, jnp.int32).reshape(-1, 2)
    edges = view_for_plan(g, tger, union_window(windows), plan)
    return edge_map_over_view_batched(
        edges, windows, frontiers, src_state, relax, combine,
        plan=plan, n_vertices=g.n_vertices,
        direction=direction, check_window=check_window,
        compute_touched=compute_touched,
    )


def vertex_map(frontier: jax.Array, fn: Callable[[jax.Array], jax.Array]) -> jax.Array:
    """VertexMap (Table 2): new frontier = {u in U | F(u)}; F vectorized."""
    keep = fn(jnp.arange(frontier.shape[0]))
    return frontier & keep


def frontier_from_sources(n_vertices: int, sources) -> jax.Array:
    f = jnp.zeros(n_vertices, dtype=bool)
    return f.at[jnp.asarray(sources)].set(True)


def frontier_nonempty(frontier: jax.Array) -> jax.Array:
    return jnp.any(frontier)


__all__ = [
    "EdgeView",
    "scan_view",
    "index_view",
    "hybrid_view",
    "hybrid_budget",
    "view_for_plan",
    "ring_positions",
    "index_ring_view",
    "advance_index_ring",
    "advance_index_ring_fields",
    "hybrid_ring_view",
    "advance_hybrid_ring",
    "advance_hybrid_ring_fields",
    "ring_companion_delta",
    "ring_view_for_plan",
    "FrontierView",
    "build_frontier_view",
    "advance_frontier_view",
    "companion_for_view",
    "ensure_plan",
    "union_window",
    "segment_combine",
    "segment_combine_windows",
    "temporal_edge_map",
    "temporal_edge_map_batched",
    "edge_map_over_view",
    "edge_map_over_view_batched",
    "vertex_map",
    "frontier_from_sources",
    "frontier_nonempty",
    "INT_INF",
]
