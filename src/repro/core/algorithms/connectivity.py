"""Temporal connected components: hash-min label propagation over the edges
valid inside the query window (weak connectivity over the temporal slice —
the standard definition used by shared-memory temporal systems).

Label propagation is a fixpoint like the path relaxations: the edge view
and window validity are loop-invariant, so both the single-window run and
the batched [W, V] sweep execute on the gather-once FixpointRunner's
hoisted view (DESIGN.md §7)."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.core.edgemap import (
    EdgeView,
    combine_for_plan,
    combine_windows_for_plan,
    ensure_plan,
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


@functools.partial(jax.jit, static_argnames=("max_rounds",))
def temporal_cc(
    g: TemporalGraph,
    window: Tuple[jax.Array, jax.Array],
    tger: Optional[TGERIndex] = None,
    *,
    plan: Optional[AccessPlan] = None,
    max_rounds: int = 0,
) -> jax.Array:
    """labels[V]: component id = min vertex id in the component (vertices
    with no valid incident edge are singletons)."""
    plan_ = ensure_plan(plan)
    runner = FixpointRunner.for_query(
        g, tger, window, plan=plan_, max_rounds=max_rounds
    )
    edges, valid = runner.edges, runner.valid
    V = g.n_vertices
    labels0 = jnp.arange(V, dtype=jnp.int32)

    def cond(state):
        _, changed = state
        return changed

    def body(state, rnd):
        labels, _ = state
        lab_src = labels[edges.src]
        lab_dst = labels[edges.dst]
        # undirected propagation: push min label both ways, through the
        # plan's backend (the dst push is in native edge order, so the
        # tiled layout is eligible exactly like the runner's step)
        fwd = combine_for_plan(plan_, lab_src, edges.dst, V, "min",
                               mask=valid, use_layout=runner.use_layout)
        bwd = combine_for_plan(plan_, lab_dst, edges.src, V, "min",
                               mask=valid)
        new_labels = jnp.minimum(labels, jnp.minimum(fwd, bwd))
        # pointer-jump (hash-min shortcut): labels[v] = labels[labels[v]]
        new_labels = jnp.minimum(new_labels, new_labels[new_labels])
        changed = jnp.any(new_labels != labels)
        return new_labels, changed

    labels, _ = runner.run(cond, body, (labels0, jnp.bool_(True)))
    return labels


def _cc_compacts(edges: EdgeView, plan: AccessPlan, n_vertices: int,
                 init) -> bool:
    """Whether the dense batched solve runs in the view's own vertex space
    (DESIGN.md §7.4): a cold solve (``init`` None: warm-start labels name
    vertices of the whole graph) over an unsharded edge axis (the vertex
    list of an edge shard is local to its device) whose 2·E' endpoints are
    at most a quarter of V.  Static: it reads shapes and the plan only."""
    return (init is None and plan.edge_axis is None
            and 2 * edges.src.shape[0] <= n_vertices // 4)


@functools.partial(jax.jit, static_argnames=("n_vertices", "max_rounds"))
@jax.named_scope("fixpoint.cc")
def _temporal_cc_over_view_dense(
    edges: EdgeView,
    windows: jax.Array,             # i32[Q, 2]
    *,
    plan: AccessPlan,
    n_vertices: int,
    max_rounds: int = 0,
    init: Optional[jax.Array] = None,   # [Q, V] warm-start labels
) -> jax.Array:
    runner = FixpointRunner.for_view(
        edges, windows=windows, plan=plan, n_vertices=n_vertices,
        max_rounds=max_rounds,
    )
    valid = runner.valid                               # [Q, E']
    V = n_vertices
    Q = runner.windows.shape[0]
    compact = _cc_compacts(edges, plan, V, init)
    if compact:
        # number the vertices that live slots touch densely in vertex
        # order (a prefix count, not a sort: a sort of 2·E' keys takes
        # the chip's compiler seconds); labels live on these K = 2·E'
        # compact ids, so every min and the pointer jump run as in the
        # dense round restricted to touched vertices.  ``uniq`` maps a
        # compact id back to its vertex, V past the touched ones; a
        # masked slot's lanes take id 0 and are never valid.
        E = edges.src.shape[0]
        ends = jnp.concatenate([edges.src, edges.dst])
        live = jnp.concatenate([edges.mask, edges.mask])
        touched = jnp.zeros(V, jnp.int32).at[
            jnp.where(live, ends, V)].set(1, mode="drop")
        comp = jnp.where(live, (jnp.cumsum(touched) - touched)[ends], 0)
        uniq = jnp.full(2 * E, V, jnp.int32).at[
            jnp.where(live, comp, 2 * E)].set(ends, mode="drop")
        src, dst, n, use_layout = comp[:E], comp[E:], 2 * E, False
    else:
        src, dst, n, use_layout = (edges.src, edges.dst, V,
                                   runner.use_layout)
    labels0 = (
        jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (Q, n))
        if init is None else jnp.asarray(init, jnp.int32)
    )

    def cond(state):
        _, changed = state
        return changed

    def body(state, rnd):
        labels, _ = state
        lab_src = labels[:, src]                       # [Q, E']
        lab_dst = labels[:, dst]
        fwd = combine_windows_for_plan(plan, lab_src, dst, n, "min",
                                       masks=valid, use_layout=use_layout)
        bwd = combine_windows_for_plan(plan, lab_dst, src, n, "min",
                                       masks=valid)
        new_labels = jnp.minimum(labels, jnp.minimum(fwd, bwd))
        new_labels = jnp.minimum(
            new_labels, jnp.take_along_axis(new_labels, new_labels, axis=1)
        )
        changed = jnp.any(new_labels != labels)
        return new_labels, changed

    labels, _ = runner.run(cond, body, (labels0, jnp.bool_(True)))
    if compact:
        # untouched vertices keep their own id; sentinel slots drop
        labels = jnp.broadcast_to(jnp.arange(V, dtype=jnp.int32), (Q, V)
                                  ).at[:, uniq].set(uniq[labels], mode="drop")
    return labels


def _cc_dense_round(edges, valid, windows, plan, state, rnd, V):
    labels, _ = state
    lab_src = labels[:, edges.src]
    lab_dst = labels[:, edges.dst]
    fwd = combine_windows_for_plan(plan, lab_src, edges.dst, V, "min",
                                   masks=valid,
                                   use_layout=(plan.method == "scan"))
    bwd = combine_windows_for_plan(plan, lab_dst, edges.src, V, "min",
                                   masks=valid)
    new_labels = jnp.minimum(labels, jnp.minimum(fwd, bwd))
    new_labels = jnp.minimum(
        new_labels, jnp.take_along_axis(new_labels, new_labels, axis=1))
    return new_labels, new_labels != labels


def _cc_sparse_round(edges, windows, plan, gathered, state, rnd, V):
    # the changed-vertex frontier covers BOTH propagation directions via
    # the two companions: edges whose SOURCE changed carry the fwd push,
    # edges whose DST changed the bwd push.  An edge with neither endpoint
    # changed contributes a label its target already absorbed in the round
    # the endpoint last changed (labels are non-increasing), so dropping
    # it leaves every min untouched — per-round bit-identity, not just at
    # the fixpoint.  The pointer-jump shortcut stays dense ([Q, V], no
    # edge work); jump-induced changes enter the frontier like any other.
    labels, _ = state
    (s_slots, s_cov), (d_slots, d_cov) = gathered
    ok_f, _, _ = sparse_window_valid(edges, windows, s_slots, s_cov)
    fwd = rowwise_combine(take_rows(labels, edges.src[s_slots]),
                          edges.dst[s_slots], V, "min", ok_f)
    ok_b, _, _ = sparse_window_valid(edges, windows, d_slots, d_cov)
    bwd = rowwise_combine(take_rows(labels, edges.dst[d_slots]),
                          edges.src[d_slots], V, "min", ok_b)
    new_labels = jnp.minimum(labels, jnp.minimum(fwd, bwd))
    new_labels = jnp.minimum(
        new_labels, jnp.take_along_axis(new_labels, new_labels, axis=1))
    return new_labels, new_labels != labels


_CC_SPEC = LadderSpec("cc", _cc_dense_round, _cc_sparse_round,
                      lambda s: s[1])


def temporal_cc_over_view(
    edges: EdgeView,
    windows: jax.Array,             # i32[Q, 2]
    *,
    plan: AccessPlan,
    n_vertices: int,
    sources=None,                   # accepted for signature uniformity: must be None
    max_rounds: int = 0,
    init: Optional[jax.Array] = None,   # [Q, V] warm-start labels
) -> jax.Array:
    """Batched hash-min label propagation over a PREBUILT (union-covering)
    edge view — the uniform entry point (DESIGN.md §7.4).  Connected
    components are source-free, so ``sources`` must be None (each row is a
    window-only query).

    ``init`` warm-starts the labels.  EXACT (bit-identical to a cold run)
    whenever every init label is an upper bound on the row's true
    component minimum AND is itself the id of a vertex in the same
    component — e.g. the converged labels of any window CONTAINED in the
    row's window (its components are sub-components, and a sub-component
    minimum is a member vertex's id).  Min-label propagation converges to
    the per-component minimum of the init labels, which under that
    precondition is exactly the component minimum.

    Under a ladder-enabled plan a host-level call runs the frontier-rung
    ladder (DESIGN.md §7.9) with the changed-vertex set as the frontier
    and BOTH propagation directions gathered through dual companions
    (by-source and by-dst) — bit-identical to the dense sweep per round.

    Otherwise a cold solve over a view whose 2·E' endpoints are at most a
    quarter of V runs in the view's own vertex space (bit-identical, round
    for round).  A host-level call that does so adds 1 to the request's
    counter ``cc.compact_solves``; a call under a trace (inside a fused
    serving step or a caller's jit) adds nothing."""
    if sources is not None:
        raise ValueError("temporal_cc is source-free: pass sources=None")
    if ladder_eligible(plan, edges, windows, init):
        runner = FixpointRunner.for_view(
            edges, windows=windows, plan=plan, n_vertices=n_vertices,
            max_rounds=max_rounds,
        )
        V = n_vertices
        Q = runner.windows.shape[0]
        labels0 = (
            jnp.broadcast_to(jnp.arange(V, dtype=jnp.int32), (Q, V))
            if init is None else jnp.asarray(init, jnp.int32)
        )
        changed0 = jnp.ones((Q, V), bool)
        comps = (companion_for_view(edges.src, V),
                 companion_for_view(edges.dst, V))
        (labels, _), _ = run_laddered(
            _CC_SPEC, edges, runner.windows, runner.valid, plan, V,
            (labels0, changed0), companions=comps,
            max_rounds=runner.max_rounds,
        )
        return labels
    if _cc_compacts(edges, plan, n_vertices, init) and not any(
            isinstance(a, jax.core.Tracer) for a in (edges.src, windows)):
        telemetry.count("cc.compact_solves", 1)
    return _temporal_cc_over_view_dense(
        edges, windows, plan=plan, n_vertices=n_vertices,
        max_rounds=max_rounds, init=init,
    )


@functools.partial(jax.jit, static_argnames=("max_rounds",))
def temporal_cc_batched(
    g: TemporalGraph,
    windows,                        # i32[W, 2] query windows
    tger: Optional[TGERIndex] = None,
    *,
    plan: Optional[AccessPlan] = None,
    max_rounds: int = 0,
) -> jax.Array:
    """Batched multi-window connected components (DESIGN.md §6):
    labels[w, v] over all W windows from ONE union-window gather — the
    per-window [W, E'] validity matrix is precomputed once and the min-label
    pushes run as [W, ·] batched reductions.  Row w is bit-identical to
    ``temporal_cc(g, windows[w], ...)`` under the same plan: hash-min label
    propagation is monotone non-increasing and idempotent, so a converged
    row rides extra rounds (forced by slower rows) as a no-op."""
    plan_ = ensure_plan(plan)
    windows = jnp.asarray(windows, jnp.int32).reshape(-1, 2)
    edges = view_for_plan(g, tger, union_window(windows), plan_)
    return temporal_cc_over_view(
        edges, windows, plan=plan_, n_vertices=g.n_vertices,
        max_rounds=max_rounds,
    )


# the ROADMAP/API-facing alias: "connected components" is the workload name,
# temporal_cc_batched the module-consistent one.
connected_components_batched = temporal_cc_batched

__all__ = [
    "temporal_cc",
    "temporal_cc_batched",
    "temporal_cc_over_view",
    "connected_components_batched",
]
