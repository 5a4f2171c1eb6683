"""Temporal k-core: iterative peeling of vertices whose (undirected) degree
within the query window drops below k; plus full coreness decomposition.

Peeling is a fixpoint over a loop-invariant edge set: the view and the
window-validity mask come precomputed from the gather-once FixpointRunner
(DESIGN.md §7), so index/hybrid plans pay their gather once per query."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.edgemap import (
    EdgeView,
    ensure_plan,
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
)
from repro.engine.plan import AccessPlan
from repro.core.temporal_graph import TemporalGraph
from repro.core.tger import TGERIndex


@functools.partial(jax.jit, static_argnames=("max_rounds",))
def temporal_kcore(
    g: TemporalGraph,
    k,
    window: Tuple[jax.Array, jax.Array],
    tger: Optional[TGERIndex] = None,
    *,
    plan: Optional[AccessPlan] = None,
    max_rounds: int = 0,
) -> jax.Array:
    """alive[V] bool: membership of the temporal k-core within the window."""
    runner = FixpointRunner.for_query(
        g, tger, window, plan=ensure_plan(plan), max_rounds=max_rounds
    )
    edges, valid0 = runner.edges, runner.valid
    V = g.n_vertices
    alive0 = jnp.ones(V, dtype=bool)
    k = jnp.asarray(k, jnp.int32)

    def cond(state):
        _, changed = state
        return changed

    def body(state, rnd):
        alive, _ = state
        live_edge = valid0 & alive[edges.src] & alive[edges.dst]
        ones = live_edge.astype(jnp.int32)
        deg = (
            segment_combine(ones, edges.dst, V, "sum")
            + segment_combine(ones, edges.src, V, "sum")
        )
        new_alive = alive & (deg >= k)
        changed = jnp.any(new_alive != alive)
        return new_alive, changed

    alive, _ = runner.run(cond, body, (alive0, jnp.bool_(True)))
    return alive


@functools.partial(jax.jit, static_argnames=("n_vertices", "max_rounds"))
@jax.named_scope("fixpoint.kcore")
def _temporal_kcore_over_view_dense(
    edges: EdgeView,
    windows: jax.Array,             # i32[Q, 2]
    *,
    plan: AccessPlan,
    n_vertices: int,
    k,
    max_rounds: int = 0,
) -> jax.Array:
    runner = FixpointRunner.for_view(
        edges, windows=windows, plan=plan, n_vertices=n_vertices,
        max_rounds=max_rounds,
    )
    valid = runner.valid                               # [Q, E']
    V = n_vertices
    Q = runner.windows.shape[0]
    alive0 = jnp.ones((Q, V), dtype=bool)
    k = jnp.asarray(k, jnp.int32)

    def cond(state):
        _, changed = state
        return changed

    ax = plan.edge_axis

    def body(state, rnd):
        alive, _ = state
        live = valid & alive[:, edges.src] & alive[:, edges.dst]   # [Q, E']
        ones = live.astype(jnp.int32)
        # degrees are global across edge shards (axis=ax psums the two
        # partial sums), so the peeling decision — and hence `changed` —
        # is identical on every shard: the while_loop stays in lockstep.
        deg = jax.vmap(
            lambda o: segment_combine(o, edges.dst, V, "sum", axis=ax)
            + segment_combine(o, edges.src, V, "sum", axis=ax)
        )(ones)
        new_alive = alive & (deg >= k)
        changed = jnp.any(new_alive != alive)
        return new_alive, changed

    alive, _ = runner.run(cond, body, (alive0, jnp.bool_(True)))
    return alive


def _kcore_dense_round(edges, valid, windows, plan, state, rnd, V):
    # the bit-identity anchor: recompute degrees from scratch exactly like
    # the dense body; ``deg``/``died`` in the carried state are rebuilt so
    # a following sparse segment can delta-update from a consistent pair.
    alive, _, _, k = state
    live = valid & alive[:, edges.src] & alive[:, edges.dst]
    ones = live.astype(jnp.int32)
    deg = jax.vmap(
        lambda o: segment_combine(o, edges.dst, V, "sum",
                                  axis=plan.edge_axis)
        + segment_combine(o, edges.src, V, "sum", axis=plan.edge_axis)
    )(ones)
    new_alive = alive & (deg >= k)
    return new_alive, deg, alive & ~new_alive, k


def _kcore_sparse_round(edges, windows, plan, gathered, state, rnd, V):
    # Frontier = the vertices that died LAST round; the round first
    # delta-subtracts their incident live edges (gathered through BOTH
    # companions: by-source covers the dst endpoints, by-dst the src
    # endpoints), then peels with the repaired degrees.  No alive-masking
    # is needed on the far endpoint: an edge whose far endpoint is already
    # dead lands its subtraction on a dead vertex, whose degree is never
    # read again (alive & (deg >= k) keeps dead vertices dead regardless)
    # — so the live-vertex degrees match the dense recompute exactly and
    # the peeling sequence is bit-identical.
    alive, deg, died, k = state
    (s_slots, s_cov), (d_slots, d_cov) = gathered
    ok_s, _, _ = sparse_window_valid(edges, windows, s_slots, s_cov)
    ok_d, _, _ = sparse_window_valid(edges, windows, d_slots, d_cov)
    deg = deg - rowwise_combine(
        jnp.ones(s_slots.shape, jnp.int32), edges.dst[s_slots], V, "sum",
        ok_s)
    deg = deg - rowwise_combine(
        jnp.ones(d_slots.shape, jnp.int32), edges.src[d_slots], V, "sum",
        ok_d)
    new_alive = alive & (deg >= k)
    return new_alive, deg, alive & ~new_alive, k


_KCORE_SPEC = LadderSpec("kcore", _kcore_dense_round, _kcore_sparse_round,
                         lambda s: s[2])


def temporal_kcore_over_view(
    edges: EdgeView,
    windows: jax.Array,             # i32[Q, 2]
    *,
    plan: AccessPlan,
    n_vertices: int,
    k,
    sources=None,                   # accepted for signature uniformity: must be None
    max_rounds: int = 0,
    init=None,
) -> jax.Array:
    """Batched k-core peeling over a PREBUILT (union-covering) edge view —
    the uniform entry point (DESIGN.md §7.4): alive[q, v] = membership of
    the temporal k-core within windows[q].  Source-free (``sources`` must
    be None); ``k`` is shared by all rows of the batch (queries with
    different k are separate batch groups).

    ``init`` must be None: peeling only REMOVES vertices, so a warm alive
    set from another window could never resurrect a vertex the wider
    window's extra edges keep alive — the serving layer refuses kcore warm
    starts (DESIGN.md §7.4 soundness table).

    Under a ladder-enabled plan a host-level call runs the frontier-rung
    ladder (DESIGN.md §7.9): the died-last-round set is the frontier, and
    sparse rounds delta-subtract only the died vertices' incident edges
    instead of recounting every degree — the long sparse tail of a deep
    peel.  The first round is always dense (everything starts alive), and
    ``k`` rides in the carried state, so one compiled ladder serves every
    k."""
    if sources is not None:
        raise ValueError("temporal_kcore is source-free: pass sources=None")
    if init is not None:
        raise ValueError(
            "temporal_kcore_over_view does not accept a warm init: peeling "
            "cannot resurrect vertices, so only the all-alive start is exact")
    if ladder_eligible(plan, edges, windows, k):
        runner = FixpointRunner.for_view(
            edges, windows=windows, plan=plan, n_vertices=n_vertices,
            max_rounds=max_rounds,
        )
        V = n_vertices
        Q = runner.windows.shape[0]
        alive0 = jnp.ones((Q, V), dtype=bool)
        # died0 = all-true forces the first segment dense (its measured
        # sumdeg is 2E' — always above the handoff cutoff), which rebuilds
        # (deg, died) consistently before any sparse round runs.
        state0 = (alive0, jnp.zeros((Q, V), jnp.int32), alive0,
                  jnp.asarray(k, jnp.int32))
        comps = (companion_for_view(edges.src, V),
                 companion_for_view(edges.dst, V))
        (alive, _, _, _), _ = run_laddered(
            _KCORE_SPEC, edges, runner.windows, runner.valid, plan, V,
            state0, companions=comps, max_rounds=runner.max_rounds,
        )
        return alive
    return _temporal_kcore_over_view_dense(
        edges, windows, plan=plan, n_vertices=n_vertices, k=k,
        max_rounds=max_rounds,
    )


@functools.partial(jax.jit, static_argnames=("max_rounds",))
def temporal_kcore_batched(
    g: TemporalGraph,
    k,
    windows,                        # i32[W, 2] query windows
    tger: Optional[TGERIndex] = None,
    *,
    plan: Optional[AccessPlan] = None,
    max_rounds: int = 0,
) -> jax.Array:
    """Batched multi-window k-core: alive[w, v] over all W windows from ONE
    union-window gather.  Row w matches ``temporal_kcore(g, k, windows[w],
    ...)`` under the same plan (peeling is per-row monotone; a converged
    row rides extra rounds as a no-op)."""
    plan = ensure_plan(plan)
    windows = jnp.asarray(windows, jnp.int32).reshape(-1, 2)
    edges = view_for_plan(g, tger, union_window(windows), plan)
    return temporal_kcore_over_view(
        edges, windows, plan=plan, n_vertices=g.n_vertices, k=k,
        max_rounds=max_rounds,
    )


@functools.partial(jax.jit, static_argnames=("k_max",))
def temporal_coreness(
    g: TemporalGraph,
    window: Tuple[jax.Array, jax.Array],
    tger: Optional[TGERIndex] = None,
    *,
    k_max: int = 64,
    plan: Optional[AccessPlan] = None,
) -> jax.Array:
    """core[v] = max k such that v belongs to the temporal k-core within the
    window (full decomposition).  Peeling reuses the (k-1)-core's alive set
    — the k-core is a subset — so total work is O(k_max * rounds * E'); the
    view and window mask are hoisted once across ALL k_max peels."""
    runner = FixpointRunner.for_query(g, tger, window, plan=ensure_plan(plan))
    edges, valid0 = runner.edges, runner.valid
    V = g.n_vertices

    def peel_to(alive, k):
        def cond(carry):
            alive_, changed = carry
            return changed

        def body(carry):
            alive_, _ = carry
            live_edge = valid0 & alive_[edges.src] & alive_[edges.dst]
            ones = live_edge.astype(jnp.int32)
            deg = (
                segment_combine(ones, edges.dst, V, "sum")
                + segment_combine(ones, edges.src, V, "sum")
            )
            new_alive = alive_ & (deg >= k)
            return new_alive, jnp.any(new_alive != alive_)

        alive, _ = jax.lax.while_loop(cond, body, (alive, jnp.bool_(True)))
        return alive

    def step(carry, k):
        alive, core = carry
        alive = peel_to(alive, k)
        core = jnp.where(alive, k, core)
        return (alive, core), None

    alive0 = jnp.ones(V, dtype=bool)
    core0 = jnp.zeros(V, jnp.int32)
    (alive, core), _ = jax.lax.scan(
        step, (alive0, core0), jnp.arange(1, k_max + 1, dtype=jnp.int32)
    )
    return core
