"""Plain reference for the served temporal-graph algorithms.

Straightforward numpy over the benchmark's own edge columns (never the
program's arrays): the edges of a window ``[ta, tb]`` are those with
``t_start >= ta`` and ``t_end <= tb``; every fixpoint relaxes all of them
round by round until nothing changes.  Integer answers are exact; PageRank
is float64.  Unreached vertices carry ``INF`` (the int32 maximum).

``pagerank_lowp`` is the control: the same power iteration computed in a
lower precision (bfloat16 by default), on whatever device JAX has.
"""
from __future__ import annotations

import dataclasses

import numpy as np

INF = np.iinfo(np.int32).max


@dataclasses.dataclass(frozen=True)
class WindowEdges:
    """The window's edges, sorted by destination, with the segment starts
    of every destination that has an in-edge."""

    n_vertices: int
    window: tuple
    src: np.ndarray
    dst: np.ndarray
    ts: np.ndarray
    te: np.ndarray
    heads: np.ndarray       # destinations with at least one in-edge
    starts: np.ndarray      # first sorted edge of each of them

    def seg_min(self, values: np.ndarray) -> np.ndarray:
        """Per-destination min of ``values`` (one per sorted edge); INF
        where a vertex has no in-edge."""
        out = np.full(self.n_vertices, INF, np.int64)
        if self.heads.size:
            out[self.heads] = np.minimum.reduceat(values, self.starts)
        return out


def window_edges(cols, window) -> WindowEdges:
    """Select and sort one window's edges from host columns ``cols``
    (attributes ``src``, ``dst``, ``t_start``, ``t_end``, ``n_vertices``)."""
    ta, tb = int(window[0]), int(window[1])
    ok = (cols.t_start >= ta) & (cols.t_end <= tb)
    dst = cols.dst[ok]
    order = np.argsort(dst, kind="stable")
    dst = dst[order].astype(np.int64)
    heads, starts = np.unique(dst, return_index=True)
    return WindowEdges(
        n_vertices=int(cols.n_vertices), window=(ta, tb),
        src=cols.src[ok][order].astype(np.int64), dst=dst,
        ts=cols.t_start[ok][order].astype(np.int64),
        te=cols.t_end[ok][order].astype(np.int64),
        heads=heads, starts=starts)


def earliest_arrival(we: WindowEdges, source: int) -> np.ndarray:
    """Earliest arrival from ``source`` departing at ``ta``: an edge
    continues a path that arrived at its source by its start time."""
    arr = np.full(we.n_vertices, INF, np.int64)
    arr[source] = we.window[0]
    while True:
        a = arr[we.src]
        cand = np.where((a < INF) & (a <= we.ts), we.te, INF)
        new = np.minimum(arr, we.seg_min(cand))
        if np.array_equal(new, arr):
            return arr
        arr = new


def bfs(we: WindowEdges, source: int):
    """(hops, arrival): a vertex's hop count is the first round in which
    it is reached; arrival is the earliest arrival, as above."""
    arr = np.full(we.n_vertices, INF, np.int64)
    hops = np.full(we.n_vertices, INF, np.int64)
    arr[source] = we.window[0]
    hops[source] = 0
    rnd = 0
    while True:
        rnd += 1
        a = arr[we.src]
        cand = np.where((a < INF) & (a <= we.ts), we.te, INF)
        new = np.minimum(arr, we.seg_min(cand))
        improved = new < arr
        if not improved.any():
            return hops, arr
        hops[improved & (hops == INF)] = rnd
        arr = new


def reachability(we: WindowEdges, source: int):
    """Overlaps reachability, round-synchronous from the vertices that
    improved in the previous round: a vertex keeps the lexicographically
    least (end, start) of the last edge of a chain reaching it, where
    consecutive edges satisfy start(A) <= start(B) and end(A) <= end(B).
    Returns (reachable, last start, last end), 0 where unreached."""
    V = we.n_vertices
    ta = we.window[0]
    end = np.full(V, INF, np.int64)
    start = np.full(V, INF, np.int64)
    end[source] = start[source] = ta
    frontier = np.zeros(V, bool)
    frontier[source] = True
    while frontier.any():
        pe, ps = end[we.src], start[we.src]
        ok = frontier[we.src] & (pe < INF) & (ps <= we.ts) & (pe <= we.te)
        min_end = we.seg_min(np.where(ok, we.te, INF))
        achieves = ok & (we.te == min_end[we.dst])
        min_start = we.seg_min(np.where(achieves, we.ts, INF))
        better = (min_end < end) | ((min_end == end) & (min_start < start))
        end = np.where(better, min_end, end)
        start = np.where(better, min_start, start)
        frontier = better
    reached = end < INF
    return reached, np.where(reached, start, 0), np.where(reached, end, 0)


def connected_components(we: WindowEdges) -> np.ndarray:
    """Weak components of the window's edges, each vertex labelled with
    the smallest vertex id of its component."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components as components

    V = we.n_vertices
    adj = coo_matrix((np.ones(we.src.size, np.int8), (we.src, we.dst)),
                     shape=(V, V))
    _, comp = components(adj, directed=True, connection="weak")
    least = np.full(comp.max() + 1, V, np.int64)
    np.minimum.at(least, comp, np.arange(V))
    return least[comp]


def pagerank(we: WindowEdges, n_iters: int = 100,
             damping: float = 0.85) -> np.ndarray:
    """Damped power iteration from the uniform vector; the rank of
    vertices with no out-edge in the window is spread evenly."""
    V = we.n_vertices
    out_deg = np.bincount(we.src, minlength=V).astype(np.float64)
    inv = np.where(out_deg > 0, 1.0 / np.maximum(out_deg, 1.0), 0.0)
    dangling = out_deg == 0
    pr = np.full(V, 1.0 / V)
    for _ in range(n_iters):
        agg = np.bincount(we.dst, weights=pr[we.src] * inv[we.src],
                          minlength=V)
        pr = (1 - damping) / V + damping * (agg + pr[dangling].sum() / V)
    return pr


def pagerank_lowp(we: WindowEdges, n_iters: int = 100, damping: float = 0.85,
                  dtype: str = "bfloat16") -> np.ndarray:
    """The control: ``pagerank`` with every array and every sum held in
    ``dtype``."""
    import jax
    import jax.numpy as jnp

    V = we.n_vertices
    dt = jnp.dtype(dtype)

    @jax.jit
    def run(src, dst):
        ones = jnp.ones(src.shape, dt)
        out_deg = jax.ops.segment_sum(ones, src, V)
        inv = jnp.where(out_deg > 0, 1 / jnp.maximum(out_deg, 1), 0).astype(dt)
        dangling = out_deg == 0

        def body(pr, _):
            agg = jax.ops.segment_sum(pr[src] * inv[src], dst, V)
            lost = jnp.sum(jnp.where(dangling, pr, 0)) / V
            return ((1 - damping) / V + damping * (agg + lost)).astype(dt), None

        pr, _ = jax.lax.scan(body, jnp.full(V, 1 / V, dt), None,
                             length=n_iters)
        return pr

    out = run(jnp.asarray(we.src, jnp.int32), jnp.asarray(we.dst, jnp.int32))
    return np.asarray(out.astype(jnp.float32), np.float64)


def solve(we: WindowEdges, algorithm: str, source, params: dict):
    """The reference answer of one served row, as a tuple of arrays in the
    order the program returns them."""
    if algorithm == "earliest_arrival":
        return (earliest_arrival(we, source),)
    if algorithm == "bfs":
        return bfs(we, source)
    if algorithm == "reachability":
        return reachability(we, source)
    if algorithm == "cc":
        return (connected_components(we),)
    if algorithm == "pagerank":
        return (pagerank(we, **params),)
    raise ValueError(f"no reference for {algorithm!r}")
