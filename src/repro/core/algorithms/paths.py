"""Temporal minimal-path algorithms (paper §2.3, §6): earliest arrival,
latest departure, fastest, shortest duration.

All are frontier relaxations over the gather-once FixpointRunner
(DESIGN.md §7): the edge view, window-validity mask and endpoint selection
are hoisted out of the ``lax.while_loop`` — index/hybrid plans pay their
binary search + budgeted gather exactly ONCE per query, not once per
relaxation round.  ``WRITEMIN`` becomes ``segment_min``, the CAS'd
frontier becomes a changed-mask (Alg. 2 pattern).
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
    segment_combine,
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
from repro.core.tger import TGERIndex, vertex_range

INT_NEG_INF = jnp.iinfo(jnp.int32).min


# ---------------------------------------------------------------------------
# Earliest Arrival (paper Algorithm 2)
# ---------------------------------------------------------------------------

def _ea_relax(pred: OrderingPredicateType):
    def relax(edges, arr_src):
        ok = edge_follows(pred, arr_src, edges.t_start, edges.t_end)
        return edges.t_end, ok

    return relax


@functools.partial(
    jax.jit,
    static_argnames=("pred", "max_rounds", "visit_once", "with_metrics",
                     "frontier_trace"),
)
def earliest_arrival(
    g: TemporalGraph,
    source,
    window: Tuple[jax.Array, jax.Array],
    tger: Optional[TGERIndex] = None,
    *,
    pred: OrderingPredicateType = OrderingPredicateType.SUCCEEDS,
    plan: Optional[AccessPlan] = None,
    max_rounds: int = 0,
    visit_once: bool = False,
    with_metrics: bool = False,
    frontier_trace: bool = False,
):
    """t[v] = earliest arrival time from ``source`` to v within [ta, tb].

    ``visit_once=True`` reproduces Alg. 2's CAS(Visited) literally (each
    vertex joins the frontier at most once); the default label-correcting
    variant (frontier = improved vertices) is the standard correct form and
    matches it on graphs where earliest arrivals are settled in one visit.

    Access method + backend come from ``plan`` (repro.engine.plan_query);
    the view is gathered once, before the fixpoint loop.

    ``with_metrics=True`` returns ``(arrival, FixpointMetrics)`` — the
    runner's ``touched``-driven convergence record (round count + total
    touched vertices), at the cost of one extra segment-sum per round.
    ``frontier_trace=True`` (with metrics) additionally fills
    ``FixpointMetrics.frontier_trace`` with the per-round occupancy — the
    regime evidence the frontier-rung ladder reads (DESIGN.md §7.9).
    """
    runner = FixpointRunner.for_query(
        g, tger, window, plan=ensure_plan(plan), max_rounds=max_rounds
    )
    V = g.n_vertices
    ta = jnp.asarray(window[0], jnp.int32)
    arrival0 = jnp.full(V, INT_INF, jnp.int32).at[source].set(ta)
    frontier0 = frontier_from_sources(V, source)
    relax = _ea_relax(pred)

    def cond(state):
        _, frontier, _ = state
        return jnp.any(frontier)

    def step_state(state, touched=False):
        arrival, frontier, visited = state
        cand, touched_v = runner.step(
            frontier, arrival, relax, "min", compute_touched=touched)
        new_arrival = jnp.minimum(arrival, cand)
        improved = new_arrival < arrival
        if visit_once:
            new_frontier = improved & ~visited
            visited = visited | improved
        else:
            new_frontier = improved
        return (new_arrival, new_frontier, visited), touched_v

    init = (arrival0, frontier0, frontier0)
    if with_metrics:
        (arrival, _, _), metrics = runner.run_with_metrics(
            cond, lambda state, rnd: step_state(state, touched=True), init,
            frontier_trace=frontier_trace)
        return arrival, metrics
    arrival, _, _ = runner.run(
        cond, lambda state, rnd: step_state(state)[0], init)
    return arrival


def earliest_arrival_multi(g, sources, window, tger=None, **kw):
    """Multi-source EA: vmap over sources (paper runs 100 top-degree sources;
    the source batch is the axis the distributed engine shards over
    ``model``)."""
    fn = lambda s: earliest_arrival(g, s, window, tger, **kw)
    return jax.vmap(fn)(jnp.asarray(sources))


@functools.partial(
    jax.jit,
    static_argnames=("n_vertices", "pred", "max_rounds", "visit_once",
                     "with_rounds"),
)
@jax.named_scope("fixpoint.earliest_arrival")
def _earliest_arrival_over_view_dense(
    edges: EdgeView,
    windows: jax.Array,             # i32[Q, 2]
    *,
    plan: AccessPlan,
    n_vertices: int,
    sources=None,                   # scalar (broadcast) | i32[Q] per-row
    pred: OrderingPredicateType = OrderingPredicateType.SUCCEEDS,
    max_rounds: int = 0,
    visit_once: bool = False,
    init: Optional[jax.Array] = None,   # [Q, V] warm-start arrival
    with_rounds: bool = False,
):
    runner = FixpointRunner.for_view(
        edges, windows=windows, sources=sources, plan=plan,
        n_vertices=n_vertices, max_rounds=max_rounds,
    )
    if init is None:
        arrival0 = runner.seeded(INT_INF, runner.windows[:, 0])
        frontier0 = runner.source_frontier()
    else:
        arrival0 = init
        frontier0 = arrival0 < INT_INF
    relax = _ea_relax(pred)

    def cond(state):
        _, frontier, _ = state
        return jnp.any(frontier)

    def body(state, rnd):
        arrival, frontier, visited = state
        cand, _ = runner.step(frontier, arrival, relax, "min")
        new_arrival = jnp.minimum(arrival, cand)
        improved = new_arrival < arrival
        if visit_once:
            new_frontier = improved & ~visited
            visited = visited | improved
        else:
            new_frontier = improved
        return new_arrival, new_frontier, visited

    init = (arrival0, frontier0, frontier0)
    if with_rounds:
        (arrival, _, _), rounds = runner.run(cond, body, init,
                                             with_rounds=True)
        return arrival, rounds
    arrival, _, _ = runner.run(cond, body, init)
    return arrival


@functools.lru_cache(maxsize=None)
def _ea_ladder_spec(pred: OrderingPredicateType) -> LadderSpec:
    """EA's ladder contract (one spec object per predicate, so same-pred
    solves share the segment jit caches).  State is ``(arrival, frontier)``
    — the label-correcting variant only; ``visit_once`` stays dense."""
    relax = _ea_relax(pred)

    def dense_round(edges, valid, windows, plan, state, rnd, V):
        arrival, frontier = state

        def per_window(wvalid, f, arr):
            cand, extra = relax(edges, arr[edges.src])
            return cand, wvalid & f[edges.src] & extra

        cand, vmask = jax.vmap(per_window)(valid, frontier, arrival)
        out = combine_windows_for_plan(
            plan, cand, edges.dst, V, "min", masks=vmask,
            use_layout=(plan.method == "scan"))
        new_arrival = jnp.minimum(arrival, out)
        return new_arrival, new_arrival < arrival

    def sparse_round(edges, windows, plan, gathered, state, rnd, V):
        arrival, frontier = state
        (slots, cov), = gathered
        ok, ts, te = sparse_window_valid(edges, windows, slots, cov)
        arr_src = take_rows(arrival, edges.src[slots])
        ok &= edge_follows(pred, arr_src, ts, te)
        out = rowwise_combine(te, edges.dst[slots], V, "min", ok)
        new_arrival = jnp.minimum(arrival, out)
        return new_arrival, new_arrival < arrival

    return LadderSpec("ea", dense_round, sparse_round, lambda s: s[1])


def _ea_laddered(edges, windows, *, plan, n_vertices, sources, pred,
                 max_rounds, init, with_rounds):
    runner = FixpointRunner.for_view(
        edges, windows=windows, sources=sources, plan=plan,
        n_vertices=n_vertices, max_rounds=max_rounds,
    )
    if init is None:
        arrival0 = runner.seeded(INT_INF, runner.windows[:, 0])
        frontier0 = runner.source_frontier()
    else:
        arrival0 = jnp.asarray(init)
        frontier0 = arrival0 < INT_INF
    comp = companion_for_view(edges.src, n_vertices)
    (arrival, _), rounds = run_laddered(
        _ea_ladder_spec(pred), edges, runner.windows, runner.valid, plan,
        n_vertices, (arrival0, frontier0), companions=(comp,),
        max_rounds=runner.max_rounds,
    )
    return (arrival, rounds) if with_rounds else arrival


def earliest_arrival_over_view(
    edges: EdgeView,
    windows: jax.Array,             # i32[Q, 2]
    *,
    plan: AccessPlan,
    n_vertices: int,
    sources=None,                   # scalar (broadcast) | i32[Q] per-row
    pred: OrderingPredicateType = OrderingPredicateType.SUCCEEDS,
    max_rounds: int = 0,
    visit_once: bool = False,
    init: Optional[jax.Array] = None,   # [Q, V] warm-start arrival
    with_rounds: bool = False,
):
    """The batched EA fixpoint over a PREBUILT (union-covering) edge view —
    the uniform multi-source entry point (DESIGN.md §7.4): row q solves
    ``(sources[q], windows[q])``, so one gathered view answers a whole
    (source × window) batch; a scalar ``sources`` broadcasts (the
    single-tenant sweep).

    This is the piece the incremental sliding-window server reuses: it
    advances one ring view across sweeps and runs only the rows that need
    solving.  ``init`` warm-starts the fixpoint with [Q, V] arrival labels
    (frontier = the finite labels) — sound whenever every finite init
    label witnesses a real temporal path inside its row's window (EA is a
    monotone min fixpoint: relaxation from any sound over-approximation
    converges to the same fixpoint, provided the frontier seeds every
    finite-label vertex).  ``with_rounds=True`` returns ``(arrival,
    rounds)`` for serving observability.

    Under a ladder-enabled plan (``plan.ladder > 0``) a HOST-LEVEL call
    (concrete view, label-correcting variant) runs the frontier-rung
    ladder (DESIGN.md §7.9) — bit-identical to the dense fixpoint, sparse
    tail rounds proportional to the live frontier.  Traced calls (the
    fused serving step) and ``visit_once`` fall through to the dense
    jitted program unchanged.
    """
    if not visit_once and ladder_eligible(plan, edges, windows, init,
                                          sources):
        return _ea_laddered(
            edges, windows, plan=plan, n_vertices=n_vertices,
            sources=sources, pred=pred, max_rounds=max_rounds, init=init,
            with_rounds=with_rounds,
        )
    return _earliest_arrival_over_view_dense(
        edges, windows, plan=plan, n_vertices=n_vertices, sources=sources,
        pred=pred, max_rounds=max_rounds, visit_once=visit_once, init=init,
        with_rounds=with_rounds,
    )


@functools.partial(
    jax.jit,
    static_argnames=("pred", "max_rounds", "visit_once"),
)
def earliest_arrival_batched(
    g: TemporalGraph,
    source,
    windows,                        # i32[W, 2] query windows
    tger: Optional[TGERIndex] = None,
    *,
    pred: OrderingPredicateType = OrderingPredicateType.SUCCEEDS,
    plan: Optional[AccessPlan] = None,
    max_rounds: int = 0,
    visit_once: bool = False,
) -> jax.Array:
    """Batched multi-window EA (DESIGN.md §6): arrival[w, v] = earliest
    arrival from ``source`` to v within windows[w], for all W windows in ONE
    sweep.  The edge view is built once over the union window and hoisted
    out of the fixpoint loop — each window pays only a mask + its slice of
    the batched combine, amortizing the traversal the way GoFFish's
    subgraph-per-interval model does across time-series intervals.  Row w is
    bit-identical to ``earliest_arrival(g, source, windows[w], ...)`` under
    the same (union-budgeted) plan.  W is static (one compilation per sweep
    width); converged windows ride the remaining rounds as no-ops.

    ``source`` must be a SCALAR (shared by every row).  Arrays are
    rejected rather than reinterpreted: pre-§7.4 code seeded every row at
    ALL of an array's vertices (multi-seed), the new source axis would
    seed row w at source[w] — a silent numerical difference.  Use
    ``earliest_arrival`` / ``earliest_arrival_multi`` for multi-seed
    queries and ``earliest_arrival_over_view(sources=...)`` for explicit
    per-row sources."""
    if np.ndim(source) != 0:
        raise ValueError(
            "earliest_arrival_batched takes a scalar source; use "
            "earliest_arrival_over_view(sources=[...]) for per-row sources "
            "or earliest_arrival(g, [s1, s2, ...], ...) for a multi-seed "
            "single query")
    plan = ensure_plan(plan)
    windows = jnp.asarray(windows, jnp.int32).reshape(-1, 2)
    edges = view_for_plan(g, tger, union_window(windows), plan)
    return earliest_arrival_over_view(
        edges, windows, sources=source, plan=plan, n_vertices=g.n_vertices,
        pred=pred, max_rounds=max_rounds, visit_once=visit_once,
    )


# ---------------------------------------------------------------------------
# Latest Departure
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("pred", "max_rounds")
)
def latest_departure(
    g: TemporalGraph,
    target,
    window: Tuple[jax.Array, jax.Array],
    tger: Optional[TGERIndex] = None,
    *,
    pred: OrderingPredicateType = OrderingPredicateType.SUCCEEDS,
    plan: Optional[AccessPlan] = None,
    max_rounds: int = 0,
) -> jax.Array:
    """ld[v] = latest time one can depart v and still reach ``target`` within
    the window.  Symmetric to EA on the in-direction with segment_max; the
    in-direction view is likewise gathered once."""
    runner = FixpointRunner.for_query(
        g, tger, window, plan=ensure_plan(plan), direction="in",
        max_rounds=max_rounds,
    )
    V = g.n_vertices
    tb = jnp.asarray(window[1], jnp.int32)
    ld0 = jnp.full(V, INT_NEG_INF, jnp.int32).at[target].set(tb)
    frontier0 = frontier_from_sources(V, target)

    relax = _ld_relax(pred)

    def cond(state):
        _, frontier = state
        return jnp.any(frontier)

    def body(state, rnd):
        ld, frontier = state
        cand, _ = runner.step(frontier, ld, relax, "max")
        new_ld = jnp.maximum(ld, cand)
        improved = new_ld > ld
        return new_ld, improved

    ld, _ = runner.run(cond, body, (ld0, frontier0))
    return ld


def _ld_relax(pred: OrderingPredicateType):
    if pred not in (OrderingPredicateType.SUCCEEDS,
                    OrderingPredicateType.STRICTLY_SUCCEEDS):
        raise ValueError("latest_departure supports succeeds predicates")

    def relax(edges, ld_dst):
        # chaining (u,v,[ts,te]) before the continuation leaving v at ld[v]:
        # succeeds: te <= ld[v]; strict: te < ld[v].
        if pred is OrderingPredicateType.STRICTLY_SUCCEEDS:
            ok = edges.t_end < ld_dst
        else:
            ok = edges.t_end <= ld_dst
        return edges.t_start, ok

    return relax


@functools.partial(
    jax.jit,
    static_argnames=("n_vertices", "pred", "max_rounds", "with_rounds"),
)
@jax.named_scope("fixpoint.latest_departure")
def latest_departure_over_view(
    edges: EdgeView,
    windows: jax.Array,             # i32[Q, 2]
    *,
    plan: AccessPlan,
    n_vertices: int,
    sources=None,                   # scalar (broadcast) | i32[Q] targets
    pred: OrderingPredicateType = OrderingPredicateType.SUCCEEDS,
    max_rounds: int = 0,
    init: Optional[jax.Array] = None,   # [Q, V] warm-start departures
    with_rounds: bool = False,
):
    """The batched latest-departure fixpoint over a prebuilt view — the
    in-direction twin of :func:`earliest_arrival_over_view`: row q is
    ``latest_departure(g, sources[q], windows[q])`` (``sources`` are the
    rows' TARGETS), seeded at ``(sources[q], windows[q, 1])`` with every
    other vertex at INT32_MIN (unreached) and combined with ``max`` over
    in-edges.  ``init`` replaces the seeded labels (frontier = the reached
    vertices); ``with_rounds=True`` returns ``(departure, rounds)``.  The
    serving step runs it dense; there is no ladder path."""
    runner = FixpointRunner.for_view(
        edges, windows=windows, sources=sources, plan=plan,
        n_vertices=n_vertices, direction="in", max_rounds=max_rounds,
    )
    if init is None:
        ld0 = runner.seeded(INT_NEG_INF, runner.windows[:, 1])
        frontier0 = runner.source_frontier()
    else:
        ld0 = init
        frontier0 = ld0 > INT_NEG_INF
    relax = _ld_relax(pred)

    def cond(state):
        _, frontier = state
        return jnp.any(frontier)

    def body(state, rnd):
        ld, frontier = state
        cand, _ = runner.step(frontier, ld, relax, "max")
        new_ld = jnp.maximum(ld, cand)
        return new_ld, new_ld > ld

    (ld, _), rounds = runner.run(cond, body, (ld0, frontier0),
                                 with_rounds=True)
    return (ld, rounds) if with_rounds else ld


# ---------------------------------------------------------------------------
# Fastest (min over departures d of EA(leave >= d) - d)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("pred", "max_rounds", "n_departures"),
)
def fastest(
    g: TemporalGraph,
    source,
    window: Tuple[jax.Array, jax.Array],
    tger: Optional[TGERIndex] = None,
    *,
    pred: OrderingPredicateType = OrderingPredicateType.SUCCEEDS,
    plan: Optional[AccessPlan] = None,
    max_rounds: int = 0,
    n_departures: int = 32,
) -> jax.Array:
    """f[v] = min elapsed time of any temporal path source->v in the window.

    Per Wu et al. [25], fastest(v) = min over source departure times t_d of
    EA(window=[t_d, tb])[v] - t_d.  The candidate departures are the source's
    (<= n_departures) earliest out-edge start times inside the window, read
    via the TGER per-vertex 3-sided range query.  The departure ladder
    [(t_d, tb), ...] IS a window batch, so the whole ladder runs as ONE
    batched EA sweep over a single union-window gather (the pre-runner
    implementation vmapped D full single-window EAs — D gathers)."""
    plan = ensure_plan(plan)
    ta, tb = jnp.asarray(window[0], jnp.int32), jnp.asarray(window[1], jnp.int32)
    lo, hi = vertex_range(g, jnp.asarray(source), ta, tb)
    pos = lo + jnp.arange(n_departures, dtype=jnp.int32)
    valid = pos < hi
    departs = jnp.where(
        valid, g.t_start[jnp.minimum(pos, g.n_edges - 1)], tb
    ).astype(jnp.int32)
    # dedupe consecutive equal departures cheaply: invalidate repeats
    rep = jnp.concatenate([jnp.array([False]), departs[1:] == departs[:-1]])
    valid &= ~rep

    windows = jnp.stack([departs, jnp.full_like(departs, tb)], axis=1)  # [D, 2]
    arr = earliest_arrival_batched(
        g, source, windows, tger, pred=pred, plan=plan, max_rounds=max_rounds,
    )                                                                   # [D, V]
    durs = jnp.where(arr == INT_INF, INT_INF, arr - departs[:, None])
    durs = jnp.where(valid[:, None], durs, INT_INF)
    out = jnp.min(durs, axis=0)
    return out.at[source].set(0)


# ---------------------------------------------------------------------------
# Shortest Duration (Pareto staircase over arrival buckets — DESIGN.md §3.2)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("pred", "max_rounds", "n_buckets", "use_weights"),
)
def shortest_duration(
    g: TemporalGraph,
    source,
    window: Tuple[jax.Array, jax.Array],
    tger: Optional[TGERIndex] = None,
    *,
    pred: OrderingPredicateType = OrderingPredicateType.SUCCEEDS,
    plan: Optional[AccessPlan] = None,
    max_rounds: int = 0,
    n_buckets: int = 64,
    use_weights: bool = False,
) -> jax.Array:
    """d[v] = min summed traversal time (or edge weight, with use_weights)
    over temporal paths source->v in the window.

    State is a monotone Pareto staircase dur[v, p] = best cost among paths
    arriving no later than bound[p].  Exact when distinct event times fit in
    n_buckets; otherwise sound (never reports an infeasible cost) with
    bucket-resolution completeness.  This replaces Wu et al.'s per-vertex
    ragged Pareto lists, which do not vectorize.

    The bucket assignments (q, p_src) are loop-invariant like the window
    mask, so they are computed once on the runner's hoisted view.
    """
    plan = ensure_plan(plan)
    runner = FixpointRunner.for_query(
        g, tger, window, plan=plan, max_rounds=max_rounds
    )
    edges, base_valid = runner.edges, runner.valid
    V, P = g.n_vertices, n_buckets
    ta, tb = jnp.asarray(window[0], jnp.int32), jnp.asarray(window[1], jnp.int32)
    # bucket bounds: uniform grid over the window (inclusive of tb).
    bounds = ta + ((tb - ta).astype(jnp.float32) * (jnp.arange(P) + 1) / P).astype(jnp.int32)

    dur0 = jnp.full((V, P), jnp.inf, jnp.float32).at[source, :].set(0.0)
    frontier0 = frontier_from_sources(V, source)

    cost = (
        edges.weight if use_weights
        else (edges.t_end - edges.t_start).astype(jnp.float32)
    )
    # arrival bucket of each edge's end time: first p with bound[p] >= te.
    q = jnp.searchsorted(bounds, edges.t_end, side="left").astype(jnp.int32)
    q = jnp.minimum(q, P - 1)
    # usable source bucket: last p with bound[p] <= ts (strict: <= ts-1).
    ts_bound = (
        edges.t_start - 1
        if pred is OrderingPredicateType.STRICTLY_SUCCEEDS
        else edges.t_start
    )
    p_src = jnp.searchsorted(bounds, ts_bound, side="right").astype(jnp.int32) - 1
    src_ok = p_src >= 0
    # source vertex itself may also depart at ts directly (arrival "ta", cost 0
    # handled by dur0 row) — p_src=-1 edges are only usable from the source,
    # whose staircase is 0 everywhere, so clamp and keep them valid from source.
    p_src_c = jnp.maximum(p_src, 0)

    def cond(state):
        _, frontier = state
        return jnp.any(frontier)

    def body(state, rnd):
        dur, frontier = state
        src_sl = dur[edges.src, p_src_c]                       # [E']
        from_source = edges.src == source
        usable = base_valid & frontier[edges.src] & (src_ok | from_source)
        src_cost = jnp.where(from_source, 0.0, src_sl)
        cand = src_cost + cost
        flat_ids = edges.dst * P + q
        upd = segment_combine(cand, flat_ids, V * P, "min", mask=usable,
                              axis=plan.edge_axis)
        upd = upd.reshape(V, P)
        new_dur = jnp.minimum(dur, upd)
        new_dur = jax.lax.cummin(new_dur, axis=1, reverse=False)
        improved_v = jnp.any(new_dur < dur, axis=1)
        return new_dur, improved_v

    dur, _ = runner.run(cond, body, (dur0, frontier0))
    return dur[:, P - 1]


__all__ = [
    "earliest_arrival",
    "earliest_arrival_multi",
    "earliest_arrival_batched",
    "earliest_arrival_over_view",
    "latest_departure",
    "latest_departure_over_view",
    "fastest",
    "shortest_duration",
]
