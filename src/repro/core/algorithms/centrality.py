"""Temporal betweenness centrality (Brandes over the earliest-arrival DAG).

Forward: path counts sigma accumulate in arrival-time-bucket order over the
optimal-edge DAG (an edge (s,d,[ts,te]) is EA-optimal iff it is window-valid,
satisfies the ordering predicate against t[s], and te == t[d]).  Backward:
dependencies delta accumulate in reverse bucket order.  Exact when arrivals
strictly increase along optimal paths (strict predicate / positive
durations) and bucket count >= distinct arrival times; the paper's T.BC
similarly counts minimal temporal paths (it uses shortest-duration paths;
we count earliest-arrival paths — noted in DESIGN.md).

Execution rides the gather-once FixpointRunner view (DESIGN.md §7):
``temporal_betweenness_over_view`` is the uniform multi-source entry point
(DESIGN.md §7.4) — row q computes the single-source dependency vector of
``(sources[q], windows[q])`` over ONE prebuilt (union-covering) view, with
the EA upsweep running as one batched fixpoint across all rows;
``temporal_betweenness`` sums those rows (the classic BC reduction) and
``temporal_betweenness_batched`` serves per-window rows for one source."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.algorithms.paths import earliest_arrival_over_view
from repro.core.edgemap import (
    INT_INF,
    EdgeView,
    ensure_plan,
    segment_combine,
    union_window,
    view_for_plan,
)
from repro.engine.fixpoint import FixpointRunner
from repro.engine.frontier import ladder_eligible
from repro.engine.plan import AccessPlan
from repro.core.predicates import OrderingPredicateType, edge_follows
from repro.core.temporal_graph import TemporalGraph
from repro.core.tger import TGERIndex


def _brandes_row(edges, valid_row, window, source, t, P: int,
                 pred: OrderingPredicateType, V: int, axis=None):
    """One (source, window) row's dependency vector over the hoisted view:
    ``t`` is the row's earliest-arrival labels, ``valid_row`` its window
    validity mask — both precomputed outside (and vmapped over rows).
    ``axis`` (the plan's ``edge_axis``) makes the per-bucket sigma/delta
    sums global across edge shards; the fori_loop trip counts are static,
    so the shards stay trivially in lockstep."""
    ta, tb = window[0], window[1]
    reached = t < INT_INF
    t_src = t[edges.src]
    opt = (
        valid_row
        & (t_src < INT_INF)
        & edge_follows(pred, t_src, edges.t_start, edges.t_end)
        & (edges.t_end == t[edges.dst])
        & (edges.dst != source)
    )

    # arrival buckets: uniform grid over the window.
    bounds = ta + ((tb - ta).astype(jnp.float32) * (jnp.arange(P) + 1) / P).astype(jnp.int32)
    bv = jnp.where(
        reached, jnp.minimum(jnp.searchsorted(bounds, t, side="left"), P - 1), P
    ).astype(jnp.int32)
    b_dst = bv[edges.dst]
    vid = jnp.arange(V, dtype=jnp.int32)

    # ---- forward: sigma in bucket order --------------------------------
    sigma0 = jnp.zeros(V, jnp.float32).at[source].set(1.0)

    def fwd(p, sigma):
        m = opt & (b_dst == p)
        contrib = segment_combine(sigma[edges.src], edges.dst, V, "sum",
                                  mask=m, axis=axis)
        assign = reached & (bv == p) & (vid != source)
        return jnp.where(assign, contrib, sigma)

    sigma = jax.lax.fori_loop(0, P, fwd, sigma0)

    # ---- backward: dependencies in reverse bucket order ------------------
    delta0 = jnp.zeros(V, jnp.float32)
    safe_sigma = jnp.maximum(sigma, 1e-30)

    def bwd(i, delta):
        p = P - 1 - i
        m = opt & (b_dst == p)
        w = (sigma[edges.src] / safe_sigma[edges.dst]) * (1.0 + delta[edges.dst])
        add = segment_combine(w, edges.src, V, "sum",
                              mask=m & (sigma[edges.dst] > 0), axis=axis)
        return delta + add

    delta = jax.lax.fori_loop(0, P, bwd, delta0)
    return delta.at[source].set(0.0)


@functools.partial(
    jax.jit,
    static_argnames=("n_vertices", "pred", "n_buckets"),
)
@jax.named_scope("fixpoint.betweenness")
def _brandes_from_t(edges, windows, sources, valid, t, *, plan,
                    n_vertices: int, pred: OrderingPredicateType,
                    n_buckets: int):
    """The vmapped forward/backward Brandes passes given precomputed EA
    labels ``t`` — shared by the dense program (which traces its EA
    upsweep inline) and the laddered host path (which computes ``t``
    through the frontier-rung ladder, bit-identical, then runs this one
    jitted downsweep).  Static fori_loop trip counts: one compilation per
    (shape, n_buckets)."""
    return jax.vmap(
        lambda w, s, ok, t_row: _brandes_row(
            edges, ok, (w[0], w[1]), s, t_row, n_buckets, pred, n_vertices,
            axis=plan.edge_axis)
    )(windows, sources, valid, t)


def temporal_betweenness_over_view(
    edges: EdgeView,
    windows: jax.Array,             # i32[Q, 2]
    *,
    plan: AccessPlan,
    n_vertices: int,
    sources=None,                   # scalar (broadcast) | i32[Q] per-row
    pred: OrderingPredicateType = OrderingPredicateType.STRICTLY_SUCCEEDS,
    max_rounds: int = 0,
    n_buckets: int = 64,
    init=None,
) -> jax.Array:
    """delta[q, v] = dependency of v on sources[q] within windows[q] — the
    uniform multi-source entry point over a PREBUILT (union-covering) view.
    The EA upsweep runs as ONE batched fixpoint over all rows; the
    forward/backward Brandes passes are vmapped over the row axis.  Summing
    rows that share a window gives classic BC (``temporal_betweenness``).

    ``init`` must be None: dependencies are not a monotone fixpoint (they
    are a two-pass DAG accumulation), so there is no sound warm start —
    the serving layer refuses betweenness warm starts (DESIGN.md §7.4).

    Under a ladder-enabled plan a host-level call runs the EA upsweep
    through the frontier-rung ladder (DESIGN.md §7.9) — the deep integer
    fixpoint is where the rounds go — and feeds the bit-identical arrival
    labels to the same jitted Brandes downsweep (float accumulation order
    unchanged, so the dependencies match the dense program exactly)."""
    if init is not None:
        raise ValueError(
            "temporal_betweenness_over_view does not accept a warm init: "
            "Brandes dependencies are recomputed per run")
    runner = FixpointRunner.for_view(
        edges, windows=windows, sources=sources, plan=plan,
        n_vertices=n_vertices, max_rounds=max_rounds,
    )
    if runner.sources is None:
        raise ValueError("temporal_betweenness_over_view needs sources=")
    t = earliest_arrival_over_view(
        edges, runner.windows, sources=runner.sources, plan=plan,
        n_vertices=n_vertices, pred=pred, max_rounds=max_rounds,
    )                                                  # [Q, V]
    return _brandes_from_t(
        edges, runner.windows, runner.sources, runner.valid, t, plan=plan,
        n_vertices=n_vertices, pred=pred, n_buckets=n_buckets)


def temporal_betweenness(
    g: TemporalGraph,
    sources,
    window: Tuple[jax.Array, jax.Array],
    tger: Optional[TGERIndex] = None,
    *,
    pred: OrderingPredicateType = OrderingPredicateType.STRICTLY_SUCCEEDS,
    plan: Optional[AccessPlan] = None,
    max_rounds: int = 0,
    n_buckets: int = 64,
) -> jax.Array:
    """BC[v] = sum over sources of the dependency of v (Brandes).  The
    source batch runs as rows of ONE ``temporal_betweenness_over_view``
    call — a single union gather instead of a per-source view build."""
    plan = ensure_plan(plan)
    sources = jnp.asarray(sources, jnp.int32).reshape(-1)
    edges = view_for_plan(g, tger, window, plan)
    windows = jnp.broadcast_to(
        jnp.asarray([window[0], window[1]], jnp.int32), (sources.shape[0], 2))
    deltas = temporal_betweenness_over_view(
        edges, windows, sources=sources, plan=plan, n_vertices=g.n_vertices,
        pred=pred, max_rounds=max_rounds, n_buckets=n_buckets,
    )
    return jnp.sum(deltas, axis=0)


@functools.partial(jax.jit, static_argnames=("pred", "max_rounds", "n_buckets"))
def temporal_betweenness_batched(
    g: TemporalGraph,
    source,
    windows,                        # i32[W, 2] query windows
    tger: Optional[TGERIndex] = None,
    *,
    pred: OrderingPredicateType = OrderingPredicateType.STRICTLY_SUCCEEDS,
    plan: Optional[AccessPlan] = None,
    max_rounds: int = 0,
    n_buckets: int = 64,
) -> jax.Array:
    """delta[w, v] = dependency rows of ONE source across W windows from a
    single union-window gather (the serving-shaped batch)."""
    plan = ensure_plan(plan)
    windows = jnp.asarray(windows, jnp.int32).reshape(-1, 2)
    edges = view_for_plan(g, tger, union_window(windows), plan)
    return temporal_betweenness_over_view(
        edges, windows, sources=source, plan=plan, n_vertices=g.n_vertices,
        pred=pred, max_rounds=max_rounds, n_buckets=n_buckets,
    )


__all__ = [
    "temporal_betweenness",
    "temporal_betweenness_batched",
    "temporal_betweenness_over_view",
]
