"""Temporal BFS: minimum-hop temporal-respecting paths.

Round h maintains the best (earliest) arrival achievable within <= h hops;
a vertex's hop count is the first round it becomes reachable.  Exact for
min-hop because arrival-per-round is the min over all <= h-hop paths.

Both the single-window run and the batched [W, V] sweep execute on the
gather-once FixpointRunner (DESIGN.md §7): the edge view and window mask
are hoisted, so index/hybrid plans gather once per query, not per round.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.edgemap import (
    INT_INF,
    EdgeView,
    ensure_plan,
    frontier_from_sources,
    union_window,
    view_for_plan,
)
from repro.engine.backends import combine_windows_for_plan
from repro.engine.fixpoint import FixpointRunner
from repro.engine.frontier import (
    LadderSpec,
    companion_for_view,
    ladder_eligible,
    rowwise_combine,
    run_laddered,
    sparse_window_valid,
    take_rows,
)
from repro.engine.plan import AccessPlan
from repro.core.predicates import OrderingPredicateType, edge_follows
from repro.core.temporal_graph import TemporalGraph
from repro.core.tger import TGERIndex


def _bfs_relax(pred: OrderingPredicateType):
    def relax(edges, arr_src):
        ok = edge_follows(pred, arr_src, edges.t_start, edges.t_end)
        return edges.t_end, ok

    return relax


@functools.partial(
    jax.jit, static_argnames=("pred", "max_rounds")
)
def temporal_bfs(
    g: TemporalGraph,
    source,
    window: Tuple[jax.Array, jax.Array],
    tger: Optional[TGERIndex] = None,
    *,
    pred: OrderingPredicateType = OrderingPredicateType.SUCCEEDS,
    plan: Optional[AccessPlan] = None,
    max_rounds: int = 0,
):
    """Returns (hops[V], arrival[V]); hops = INT_INF when unreachable."""
    runner = FixpointRunner.for_query(
        g, tger, window, plan=ensure_plan(plan), max_rounds=max_rounds
    )
    V = g.n_vertices
    ta = jnp.asarray(window[0], jnp.int32)
    arrival0 = jnp.full(V, INT_INF, jnp.int32).at[source].set(ta)
    hops0 = jnp.full(V, INT_INF, jnp.int32).at[source].set(0)
    frontier0 = frontier_from_sources(V, source)
    relax = _bfs_relax(pred)

    def cond(state):
        _, _, frontier = state
        return jnp.any(frontier)

    def body(state, rnd):
        arrival, hops, frontier = state
        cand, _ = runner.step(frontier, arrival, relax, "min")
        new_arrival = jnp.minimum(arrival, cand)
        improved = new_arrival < arrival
        newly_reached = improved & (hops == INT_INF)
        new_hops = jnp.where(newly_reached, rnd + 1, hops)
        return new_arrival, new_hops, improved

    arrival, hops, _ = runner.run(cond, body, (arrival0, hops0, frontier0))
    return hops, arrival


@functools.partial(
    jax.jit, static_argnames=("n_vertices", "pred", "max_rounds")
)
@jax.named_scope("fixpoint.bfs")
def _temporal_bfs_over_view_dense(
    edges: EdgeView,
    windows: jax.Array,             # i32[Q, 2]
    *,
    plan: AccessPlan,
    n_vertices: int,
    sources=None,                   # scalar (broadcast) | i32[Q] per-row
    pred: OrderingPredicateType = OrderingPredicateType.SUCCEEDS,
    max_rounds: int = 0,
):
    runner = FixpointRunner.for_view(
        edges, windows=windows, sources=sources, plan=plan,
        n_vertices=n_vertices, max_rounds=max_rounds,
    )
    arrival0 = runner.seeded(INT_INF, runner.windows[:, 0])
    hops0 = runner.seeded(INT_INF, 0)
    frontier0 = runner.source_frontier()
    relax = _bfs_relax(pred)

    def cond(state):
        _, _, frontier = state
        return jnp.any(frontier)

    def body(state, rnd):
        arrival, hops, frontier = state
        cand, _ = runner.step(frontier, arrival, relax, "min")
        new_arrival = jnp.minimum(arrival, cand)
        improved = new_arrival < arrival
        newly_reached = improved & (hops == INT_INF)
        new_hops = jnp.where(newly_reached, rnd + 1, hops)
        return new_arrival, new_hops, improved

    arrival, hops, _ = runner.run(cond, body, (arrival0, hops0, frontier0))
    return hops, arrival


@functools.lru_cache(maxsize=None)
def _bfs_ladder_spec(pred: OrderingPredicateType) -> LadderSpec:
    """BFS's ladder contract: state ``(arrival, hops, frontier)``.  Hop
    numbering reads the GLOBAL round counter (run_laddered threads one i32
    round count through every segment), so laddered hop counts equal the
    dense round-indexed numbering exactly."""
    relax = _bfs_relax(pred)

    def _post(arrival, hops, cand, rnd):
        new_arrival = jnp.minimum(arrival, cand)
        improved = new_arrival < arrival
        newly_reached = improved & (hops == INT_INF)
        new_hops = jnp.where(newly_reached, rnd + 1, hops)
        return new_arrival, new_hops, improved

    def dense_round(edges, valid, windows, plan, state, rnd, V):
        arrival, hops, frontier = state

        def per_window(wvalid, f, arr):
            cand, extra = relax(edges, arr[edges.src])
            return cand, wvalid & f[edges.src] & extra

        cand, vmask = jax.vmap(per_window)(valid, frontier, arrival)
        out = combine_windows_for_plan(
            plan, cand, edges.dst, V, "min", masks=vmask,
            use_layout=(plan.method == "scan"))
        return _post(arrival, hops, out, rnd)

    def sparse_round(edges, windows, plan, gathered, state, rnd, V):
        arrival, hops, frontier = state
        (slots, cov), = gathered
        ok, ts, te = sparse_window_valid(edges, windows, slots, cov)
        arr_src = take_rows(arrival, edges.src[slots])
        ok &= edge_follows(pred, arr_src, ts, te)
        out = rowwise_combine(te, edges.dst[slots], V, "min", ok)
        return _post(arrival, hops, out, rnd)

    return LadderSpec("bfs", dense_round, sparse_round, lambda s: s[2])


def temporal_bfs_over_view(
    edges: EdgeView,
    windows: jax.Array,             # i32[Q, 2]
    *,
    plan: AccessPlan,
    n_vertices: int,
    sources=None,                   # scalar (broadcast) | i32[Q] per-row
    pred: OrderingPredicateType = OrderingPredicateType.SUCCEEDS,
    max_rounds: int = 0,
    init=None,
):
    """Batched min-hop BFS over a PREBUILT (union-covering) edge view — the
    uniform multi-source entry point (DESIGN.md §7.4): row q solves
    ``(sources[q], windows[q])``, so one gathered (or ring-advanced) view
    answers a whole (source × window) batch.

    ``init`` must be None: hop counts are ROUND-indexed (hops[v] = the
    first round arrival improves), so a warm-started run cannot reproduce
    the cold hop numbering — the serving layer refuses bfs warm starts
    for exactly this reason (DESIGN.md §7.4 soundness table).

    Under a ladder-enabled plan a host-level call runs the frontier-rung
    ladder (DESIGN.md §7.9), bit-identical to the dense fixpoint — hop
    counts included, since the ladder's round counter is global across
    segments."""
    if init is not None:
        raise ValueError(
            "temporal_bfs_over_view does not accept a warm init: hop "
            "counts are round-indexed and only exact from a cold start")
    if ladder_eligible(plan, edges, windows, sources):
        runner = FixpointRunner.for_view(
            edges, windows=windows, sources=sources, plan=plan,
            n_vertices=n_vertices, max_rounds=max_rounds,
        )
        arrival0 = runner.seeded(INT_INF, runner.windows[:, 0])
        hops0 = runner.seeded(INT_INF, 0)
        frontier0 = runner.source_frontier()
        comp = companion_for_view(edges.src, n_vertices)
        (arrival, hops, _), _ = run_laddered(
            _bfs_ladder_spec(pred), edges, runner.windows, runner.valid,
            plan, n_vertices, (arrival0, hops0, frontier0),
            companions=(comp,), max_rounds=runner.max_rounds,
        )
        return hops, arrival
    return _temporal_bfs_over_view_dense(
        edges, windows, plan=plan, n_vertices=n_vertices, sources=sources,
        pred=pred, max_rounds=max_rounds,
    )


@functools.partial(
    jax.jit, static_argnames=("pred", "max_rounds")
)
def temporal_bfs_batched(
    g: TemporalGraph,
    source,
    windows,                        # i32[W, 2] query windows
    tger: Optional[TGERIndex] = None,
    *,
    pred: OrderingPredicateType = OrderingPredicateType.SUCCEEDS,
    plan: Optional[AccessPlan] = None,
    max_rounds: int = 0,
):
    """Batched multi-window BFS (DESIGN.md §6): (hops[W, V], arrival[W, V])
    from ONE union-window gather — per-window masks over the shared view,
    [W, V] min-combines per round.  Row w is bit-identical to
    ``temporal_bfs(g, source, windows[w], ...)`` under the same plan: hop
    counts are per-row exact because a converged row's frontier is empty, so
    its hops never update while other rows keep relaxing.  ``source`` must
    be a SCALAR — arrays are rejected rather than silently reinterpreted
    (pre-§7.4 multi-seed vs the new per-row source axis); use
    ``temporal_bfs_over_view(sources=...)`` for per-row sources."""
    if np.ndim(source) != 0:
        raise ValueError(
            "temporal_bfs_batched takes a scalar source; use "
            "temporal_bfs_over_view(sources=[...]) for per-row sources")
    plan = ensure_plan(plan)
    windows = jnp.asarray(windows, jnp.int32).reshape(-1, 2)
    edges = view_for_plan(g, tger, union_window(windows), plan)
    return temporal_bfs_over_view(
        edges, windows, sources=source, plan=plan, n_vertices=g.n_vertices,
        pred=pred, max_rounds=max_rounds,
    )


__all__ = ["temporal_bfs", "temporal_bfs_batched", "temporal_bfs_over_view"]
