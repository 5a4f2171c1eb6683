"""Time-constrained reachability under the OVERLAPS ordering predicate
(paper Table 1: influence propagation / information cascades).

Overlaps chains require start(A) <= start(B) and end(A) <= end(B) for
consecutive edges — both interval ends participate, so per-vertex state is
the (start, end) of the last edge on the path.  Minimizing both
coordinates is a two-objective problem; we keep the lexicographically
minimal (end, start) pair per vertex — maintained with a two-pass
segment-min (min end, then min start among end-achievers) since JAX runs
32-bit and packing is unavailable.  This is SOUND (every reported vertex
is truly overlaps-reachable; the witness chain is materialized by the
relaxation) and exact whenever minimizing end never sacrifices a needed
start (e.g. co-ordered starts/ends — property-tested; the exhaustive
Pareto oracle lives in core/reference.py).

Execution rides the gather-once FixpointRunner (DESIGN.md §7): the edge
view and per-window validity are hoisted; the batched sweep vmaps the
per-window fixpoint over the precomputed [W, E'] validity matrix.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.edgemap import (
    INT_INF,
    EdgeView,
    ensure_plan,
    frontier_from_sources,
    segment_combine,
    union_window,
    view_for_plan,
)
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
from repro.core.temporal_graph import TemporalGraph
from repro.core.tger import TGERIndex


def _solve_window(edges, base_ok, window, source, n_vertices: int,
                  max_rounds: int, init=None, axis=None):
    """The one overlaps fixpoint over a prebuilt edge view with a
    PRECOMPUTED validity mask: shared by the single-window run and (vmapped
    over the [W, E'] validity rows) the batched sweep.  ``init`` optionally
    warm-starts (s_end, s_start) — sound when every finite init pair is the
    last-edge interval of a real overlaps chain inside this window.
    ``axis`` (the plan's ``edge_axis``) makes each segment-min global
    across edge shards — pass 2's achievers then compare against the
    GLOBAL pass-1 min, so the two-pass lexicographic min stays exact."""
    V = n_vertices
    ta = window[0]

    # state: (last_end, last_start); source seeds with (ta, ta) — its first
    # edge only needs ts >= ta, te >= ta, which the window implies.
    if init is None:
        end0 = jnp.full(V, INT_INF, jnp.int32).at[source].set(ta)
        start0 = jnp.full(V, INT_INF, jnp.int32).at[source].set(ta)
        frontier0 = frontier_from_sources(V, source)
    else:
        end0, start0 = init
        frontier0 = end0 < INT_INF

    def cond(carry):
        rnd, _, _, frontier = carry
        return (rnd < max_rounds) & jnp.any(frontier)

    def body(carry):
        rnd, s_end, s_start, frontier = carry
        pe = s_end[edges.src]
        ps = s_start[edges.src]
        ok = (
            base_ok & frontier[edges.src] & (pe < INT_INF)
            & (ps <= edges.t_start) & (pe <= edges.t_end)
        )
        # two-pass lexicographic min: (1) min end per dst, (2) min start
        # among the edges achieving that end.
        min_end = segment_combine(edges.t_end, edges.dst, V, "min", mask=ok,
                                  axis=axis)
        achieves = ok & (edges.t_end == min_end[edges.dst])
        min_start = segment_combine(edges.t_start, edges.dst, V, "min",
                                    mask=achieves, axis=axis)
        better = (min_end < s_end) | ((min_end == s_end) & (min_start < s_start))
        new_end = jnp.where(better, min_end, s_end)
        new_start = jnp.where(better, min_start, s_start)
        return rnd + 1, new_end, new_start, better

    _, s_end, s_start, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), end0, start0, frontier0)
    )
    reachable = s_end < INT_INF
    return (
        reachable,
        jnp.where(reachable, s_start, 0),
        jnp.where(reachable, s_end, 0),
    )


@functools.partial(jax.jit, static_argnames=("max_rounds",))
def overlaps_reachability(
    g: TemporalGraph,
    source,
    window: Tuple[jax.Array, jax.Array],
    tger: Optional[TGERIndex] = None,
    *,
    plan: Optional[AccessPlan] = None,
    max_rounds: int = 0,
):
    """Returns (reachable[V] bool, last_start[V], last_end[V])."""
    plan = ensure_plan(plan)
    runner = FixpointRunner.for_query(
        g, tger, window, plan=plan, max_rounds=max_rounds
    )
    return _solve_window(
        runner.edges, runner.valid, runner.window, source, g.n_vertices,
        runner.max_rounds, axis=plan.edge_axis,
    )


@functools.partial(jax.jit, static_argnames=("n_vertices", "max_rounds"))
@jax.named_scope("fixpoint.reachability")
def _overlaps_reachability_over_view_dense(
    edges: EdgeView,
    windows: jax.Array,             # i32[Q, 2]
    *,
    plan: AccessPlan,
    n_vertices: int,
    sources=None,                   # scalar (broadcast) | i32[Q] per-row
    max_rounds: int = 0,
    init=None,                      # optional ([Q, V] end, [Q, V] start)
):
    runner = FixpointRunner.for_view(
        edges, windows=windows, sources=sources, plan=plan,
        n_vertices=n_vertices, max_rounds=max_rounds,
    )
    if runner.sources is None:
        raise ValueError("overlaps_reachability_over_view needs sources=")
    ax = plan.edge_axis
    if init is None:
        return jax.vmap(
            lambda w, s, ok: _solve_window(
                edges, ok, (w[0], w[1]), s, n_vertices, runner.max_rounds,
                axis=ax)
        )(runner.windows, runner.sources, runner.valid)
    return jax.vmap(
        lambda w, s, ok, e0, s0: _solve_window(
            edges, ok, (w[0], w[1]), s, n_vertices, runner.max_rounds,
            init=(e0, s0), axis=ax)
    )(runner.windows, runner.sources, runner.valid, init[0], init[1])


def _reach_rounds(edges_t_end, edges_t_start, dst, s_end, s_start, ok, V,
                  combine):
    """The shared two-pass lexicographic-min update: ``combine(vals, ids,
    mask)`` is either the dense per-row segment combine or the sparse
    gathered one — both minimize over the SAME valid-edge multiset, so the
    results agree bit-for-bit (integer min is order-free)."""
    min_end = combine(edges_t_end, dst, ok)
    achieves = ok & (edges_t_end == take_rows(min_end, dst))
    min_start = combine(edges_t_start, dst, achieves)
    better = (min_end < s_end) | ((min_end == s_end) & (min_start < s_start))
    new_end = jnp.where(better, min_end, s_end)
    new_start = jnp.where(better, min_start, s_start)
    return new_end, new_start, better


def _reach_dense_round(edges, valid, windows, plan, state, rnd, V):
    s_end, s_start, frontier = state
    ok = jax.vmap(
        lambda wvalid, f, pe, ps: (
            wvalid & f[edges.src] & (pe[edges.src] < INT_INF)
            & (ps[edges.src] <= edges.t_start)
            & (pe[edges.src] <= edges.t_end))
    )(valid, frontier, s_end, s_start)
    combine = lambda vals, ids, m: jax.vmap(
        lambda v, i, mm: segment_combine(v, i, V, "min", mask=mm,
                                         axis=plan.edge_axis))(vals, ids, m)
    te = jnp.broadcast_to(edges.t_end, ok.shape)
    ts = jnp.broadcast_to(edges.t_start, ok.shape)
    dst = jnp.broadcast_to(edges.dst, ok.shape)
    return _reach_rounds(te, ts, dst, s_end, s_start, ok, V, combine)


def _reach_sparse_round(edges, windows, plan, gathered, state, rnd, V):
    s_end, s_start, frontier = state
    (slots, cov), = gathered
    ok, ts, te = sparse_window_valid(edges, windows, slots, cov)
    src_at = edges.src[slots]
    pe = take_rows(s_end, src_at)
    ps = take_rows(s_start, src_at)
    ok &= (pe < INT_INF) & (ps <= ts) & (pe <= te)
    combine = lambda vals, ids, m: rowwise_combine(vals, ids, V, "min", m)
    return _reach_rounds(te, ts, edges.dst[slots], s_end, s_start, ok, V,
                         combine)


_REACH_SPEC = LadderSpec("reach", _reach_dense_round, _reach_sparse_round,
                         lambda s: s[2])


def overlaps_reachability_over_view(
    edges: EdgeView,
    windows: jax.Array,             # i32[Q, 2]
    *,
    plan: AccessPlan,
    n_vertices: int,
    sources=None,                   # scalar (broadcast) | i32[Q] per-row
    max_rounds: int = 0,
    init=None,                      # optional ([Q, V] end, [Q, V] start)
):
    """Batched overlaps fixpoints over a PREBUILT (union-covering) view —
    the uniform multi-source entry point (DESIGN.md §7.4): row q solves
    ``(sources[q], windows[q])``, the source axis vmapped alongside the
    window axis.  Per-window validity is precomputed once ([Q, E']); the
    fixpoint is vmapped over its rows.

    Under a ladder-enabled plan a host-level call runs the frontier-rung
    ladder (DESIGN.md §7.9) with the two-pass lexicographic min evaluated
    on only the gathered frontier-incident slots — bit-identical to the
    dense sweep (a converged row's empty frontier makes every later round
    a no-op in both formulations)."""
    if ladder_eligible(plan, edges, windows, sources,
                       None if init is None else init[0]):
        runner = FixpointRunner.for_view(
            edges, windows=windows, sources=sources, plan=plan,
            n_vertices=n_vertices, max_rounds=max_rounds,
        )
        if runner.sources is None and init is None:
            raise ValueError("overlaps_reachability_over_view needs sources=")
        if init is None:
            ta = runner.windows[:, 0]
            end0 = runner.seeded(INT_INF, ta)
            start0 = runner.seeded(INT_INF, ta)
            frontier0 = runner.source_frontier()
        else:
            end0, start0 = jnp.asarray(init[0]), jnp.asarray(init[1])
            frontier0 = end0 < INT_INF
        comp = companion_for_view(edges.src, n_vertices)
        (s_end, s_start, _), _ = run_laddered(
            _REACH_SPEC, edges, runner.windows, runner.valid, plan,
            n_vertices, (end0, start0, frontier0), companions=(comp,),
            max_rounds=runner.max_rounds,
        )
        reachable = s_end < INT_INF
        return (
            reachable,
            jnp.where(reachable, s_start, 0),
            jnp.where(reachable, s_end, 0),
        )
    return _overlaps_reachability_over_view_dense(
        edges, windows, plan=plan, n_vertices=n_vertices, sources=sources,
        max_rounds=max_rounds, init=init,
    )


@functools.partial(jax.jit, static_argnames=("max_rounds",))
def overlaps_reachability_batched(
    g: TemporalGraph,
    source,
    windows,                        # i32[W, 2] query windows
    tger: Optional[TGERIndex] = None,
    *,
    plan: Optional[AccessPlan] = None,
    max_rounds: int = 0,
):
    """Batched multi-window overlaps reachability (DESIGN.md §6): ONE edge
    view over the union window, per-window fixpoints vmapped over it.
    Returns (reachable[W, V], last_start[W, V], last_end[W, V]), row w
    identical to the single-window run on windows[w]."""
    plan = ensure_plan(plan)
    windows = jnp.asarray(windows, jnp.int32).reshape(-1, 2)
    edges = view_for_plan(g, tger, union_window(windows), plan)
    return overlaps_reachability_over_view(
        edges, windows, sources=source, plan=plan, n_vertices=g.n_vertices,
        max_rounds=max_rounds,
    )
