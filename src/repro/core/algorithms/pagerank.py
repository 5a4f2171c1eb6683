"""Temporal PageRank: damped power iteration over the window-valid edge set
(paper §6.1 runs 100 iterations with a [t_a, t_b] input window).

The window-validity matrix, degrees and dangling sets are all
iteration-invariant: they are computed once on the FixpointRunner's hoisted
view (DESIGN.md §7) and the power iteration reuses the runner's uniform
step for its [W, ·] batched sum combine."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.edgemap import (
    EdgeView,
    combine_windows_for_plan,
    ensure_plan,
    union_window,
    view_for_plan,
)
from repro.engine.fixpoint import FixpointRunner
from repro.core.temporal_graph import TemporalGraph
from repro.core.tger import TGERIndex
from repro.engine.plan import AccessPlan


@functools.partial(
    jax.jit, static_argnames=("n_iters",)
)
def temporal_pagerank(
    g: TemporalGraph,
    window: Tuple[jax.Array, jax.Array],
    tger: Optional[TGERIndex] = None,
    *,
    damping: float = 0.85,
    n_iters: int = 100,
    plan: Optional[AccessPlan] = None,
) -> jax.Array:
    """The W=1 slice of the batched sweep (one power-iteration body to
    maintain; the batched path's window mask reduces to the single-window
    validity mask)."""
    ta = jnp.asarray(window[0], jnp.int32)
    tb = jnp.asarray(window[1], jnp.int32)
    windows = jnp.stack([ta, tb])[None, :]
    return temporal_pagerank_batched(
        g, windows, tger, damping=damping, n_iters=n_iters, plan=plan
    )[0]


@functools.partial(
    jax.jit, static_argnames=("n_vertices", "n_iters")
)
@jax.named_scope("fixpoint.pagerank")
def temporal_pagerank_over_view(
    edges: EdgeView,
    windows: jax.Array,             # i32[Q, 2]
    *,
    plan: AccessPlan,
    n_vertices: int,
    sources=None,                   # accepted for signature uniformity: must be None
    damping: float = 0.85,
    n_iters: int = 100,
    init: Optional[jax.Array] = None,   # [Q, V] warm start
) -> jax.Array:
    """The batched power iteration over a PREBUILT (union-covering) edge
    view — the piece the incremental sliding-window server calls on its
    advanced view.  PageRank is source-free, so ``sources`` must be None
    (signature uniformity with the other ``*_over_view`` entry points,
    DESIGN.md §7.4).  ``init`` warm-starts the iteration (PageRank's damped
    iteration contracts to a unique fixed point, so a warm start changes
    only the residual after n_iters, not the limit — re-iterating from the
    previous sweep's nearby answer converges faster, but the finite-iteration
    output is NOT bit-identical to a cold uniform start; pass ``init=None``
    for the bit-reproducible serving mode).

    The frontier-rung ladder (DESIGN.md §7.9) is deliberately a NO-OP
    here: power iteration touches every vertex every round (the frontier
    never shrinks), and float sums are order-sensitive — a sparse-gathered
    reassociation would break bit-reproducibility.  A ladder-enabled plan
    runs the same dense program."""
    if sources is not None:
        raise ValueError("temporal_pagerank is source-free: pass sources=None")
    runner = FixpointRunner(
        edges, windows=windows, plan=plan, n_vertices=n_vertices,
    )
    V = n_vertices
    W = runner.windows.shape[0]
    valid = runner.valid                                    # [W, E']
    # degree reduce goes into src — native-order layout does not apply
    out_deg = combine_windows_for_plan(
        plan, valid.astype(jnp.float32), edges.src, V, "sum"
    )                                                       # [W, V]
    inv_deg = jnp.where(out_deg > 0, 1.0 / jnp.maximum(out_deg, 1.0), 0.0)
    dangling = out_deg == 0
    ones_frontier = jnp.ones((W, V), dtype=bool)

    def relax(e, state):
        pr_src, inv_src = state
        return pr_src * inv_src, jnp.ones(e.src.shape[0], dtype=bool)

    pr0 = (
        jnp.full((W, V), 1.0 / V, jnp.float32) if init is None
        else jnp.asarray(init, jnp.float32)
    )

    def body(pr, _):
        agg, _ = runner.step(ones_frontier, (pr, inv_deg), relax, "sum")
        dangling_mass = (
            jnp.sum(jnp.where(dangling, pr, 0.0), axis=1, keepdims=True) / V
        )
        pr_new = (1.0 - damping) / V + damping * (agg + dangling_mass)
        return pr_new, None

    pr, _ = jax.lax.scan(body, pr0, None, length=n_iters)
    return pr


@functools.partial(
    jax.jit, static_argnames=("n_iters",)
)
def temporal_pagerank_batched(
    g: TemporalGraph,
    windows,                        # i32[W, 2] query windows
    tger: Optional[TGERIndex] = None,
    *,
    damping: float = 0.85,
    n_iters: int = 100,
    plan: Optional[AccessPlan] = None,
) -> jax.Array:
    """Batched multi-window PageRank (DESIGN.md §6): pr[w, v] over all W
    windows from ONE union-window edge view — per-window validity masks and
    a [W, ·] batched sum combine per power iteration, no per-window
    re-gather.  Degrees (and hence dangling sets) are per-window."""
    plan = ensure_plan(plan)
    windows = jnp.asarray(windows, jnp.int32).reshape(-1, 2)
    edges = view_for_plan(g, tger, union_window(windows), plan)
    return temporal_pagerank_over_view(
        edges, windows, plan=plan, n_vertices=g.n_vertices,
        damping=damping, n_iters=n_iters,
    )
