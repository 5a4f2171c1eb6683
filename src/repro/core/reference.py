"""Pure-numpy reference oracles for every temporal algorithm.

Slow, obviously-correct implementations used by the test suite and by the
estimator-accuracy benchmark as ground truth ("the oracle with the actual
selectivity of the query", paper §6.5).
"""
from __future__ import annotations

import numpy as np

INT_INF = np.iinfo(np.int32).max
INT_NEG_INF = np.iinfo(np.int32).min


def _edges(g):
    return (
        np.asarray(g.src), np.asarray(g.dst),
        np.asarray(g.t_start), np.asarray(g.t_end), np.asarray(g.weight),
    )


def _follows(pred, src_end, ts):
    if pred == "succeeds":
        return src_end <= ts
    if pred == "strictly_succeeds":
        return src_end < ts
    raise ValueError(pred)


def earliest_arrival_ref(g, source, window, pred="succeeds"):
    """Round-synchronous relaxation to the fixpoint: every round relaxes
    all window edges whose source arrival precedes them (``np.minimum.at``
    is the per-destination min), until no arrival improves."""
    src, dst, ts, te, _ = _edges(g)
    ta, tb = window
    ok = (ts >= ta) & (te <= tb)
    src, dst, ts, te = src[ok], dst[ok], ts[ok], te[ok]
    arr = np.full(g.n_vertices, INT_INF, np.int64)
    arr[source] = ta
    for _ in range(g.n_vertices + 1):
        a = arr[src]
        relax = (a < INT_INF) & _follows(pred, a, ts)
        new = arr.copy()
        np.minimum.at(new, dst[relax], te[relax])
        if (new == arr).all():
            break
        arr = new
    return arr


def latest_departure_ref(g, target, window, pred="succeeds"):
    src, dst, ts, te, _ = _edges(g)
    ta, tb = window
    ok = (ts >= ta) & (te <= tb)
    ld = np.full(g.n_vertices, INT_NEG_INF, np.int64)
    ld[target] = tb
    for _ in range(g.n_vertices + 1):
        changed = False
        cont = ld[dst]
        if pred == "succeeds":
            relax = ok & (cont > INT_NEG_INF) & (te <= cont)
        else:
            relax = ok & (cont > INT_NEG_INF) & (te < cont)
        for e in np.nonzero(relax)[0]:
            if ts[e] > ld[src[e]]:
                ld[src[e]] = ts[e]
                changed = True
        if not changed:
            break
    return ld


def _all_paths_relax(g, source, window, pred):
    """Exact Pareto relaxation: per-vertex set of nondominated
    (arrival, duration_sum) pairs.  Exponential-safe for test-size graphs."""
    src, dst, ts, te, _ = _edges(g)
    ta, tb = window
    ok = (ts >= ta) & (te <= tb)
    eids = np.nonzero(ok)[0]
    pareto = [dict() for _ in range(g.n_vertices)]  # arrival -> min dur
    pareto[source][ta] = 0.0
    frontier = {source}
    for _ in range(g.n_vertices * 4 + 4):
        new_frontier = set()
        for e in eids:
            u, v = src[e], dst[e]
            if u not in frontier and not pareto[u]:
                continue
            for arr_u, dur_u in list(pareto[u].items()):
                if u == source:
                    feasible = ts[e] >= ta if pred == "succeeds" else ts[e] >= ta
                else:
                    feasible = _follows(pred, arr_u, ts[e])
                if not feasible:
                    continue
                cand_arr, cand_dur = te[e], dur_u + (te[e] - ts[e])
                cur = pareto[v].get(cand_arr)
                dominated = any(
                    a <= cand_arr and d <= cand_dur
                    for a, d in pareto[v].items()
                    if (a, d) != (cand_arr, cand_dur)
                )
                if not dominated and (cur is None or cand_dur < cur):
                    pareto[v][cand_arr] = cand_dur
                    # prune newly dominated entries
                    for a in list(pareto[v]):
                        if a != cand_arr and a >= cand_arr and pareto[v][a] >= cand_dur:
                            del pareto[v][a]
                    new_frontier.add(v)
        if not new_frontier:
            break
        frontier = new_frontier
    return pareto


def shortest_duration_ref(g, source, window, pred="succeeds"):
    pareto = _all_paths_relax(g, source, window, pred)
    out = np.full(g.n_vertices, np.inf)
    for v, d in enumerate(pareto):
        if d:
            out[v] = min(d.values())
    out[source] = 0.0
    return out


def fastest_ref(g, source, window, pred="succeeds"):
    """min over departure times d of EA([d, tb]) - d."""
    src, _, ts, te, _ = _edges(g)
    ta, tb = window
    departs = np.unique(ts[(src == source) & (ts >= ta) & (te <= tb)])
    out = np.full(g.n_vertices, INT_INF, np.int64)
    for d in departs:
        arr = earliest_arrival_ref(g, source, (d, tb), pred)
        dur = np.where(arr < INT_INF, arr - d, INT_INF)
        out = np.minimum(out, dur)
    out[source] = 0
    return out


def temporal_bfs_ref(g, source, window, pred="succeeds"):
    src, dst, ts, te, _ = _edges(g)
    ta, tb = window
    ok = (ts >= ta) & (te <= tb)
    arr = np.full(g.n_vertices, INT_INF, np.int64)
    hops = np.full(g.n_vertices, INT_INF, np.int64)
    arr[source] = ta
    hops[source] = 0
    for rnd in range(1, g.n_vertices + 2):
        relax = ok & (arr[src] < INT_INF) & _follows(pred, arr[src], ts)
        new_arr = arr.copy()
        for e in np.nonzero(relax)[0]:
            if te[e] < new_arr[dst[e]]:
                new_arr[dst[e]] = te[e]
        changed = new_arr < arr
        if not changed.any():
            break
        hops[changed & (hops == INT_INF)] = rnd
        arr = new_arr
    return hops, arr


def temporal_cc_ref(g, window):
    """Min-label propagation over the window's edges, undirected: every
    vertex ends labelled with the smallest vertex id of its component."""
    src, dst, ts, te, _ = _edges(g)
    ta, tb = window
    ok = (ts >= ta) & (te <= tb)
    src, dst = src[ok], dst[ok]
    labels = np.arange(g.n_vertices)
    for _ in range(g.n_vertices + 1):
        m = np.minimum(labels[src], labels[dst])
        new = labels.copy()
        np.minimum.at(new, src, m)
        np.minimum.at(new, dst, m)
        new = new[new]  # pointer jump: a label is a vertex of the component
        if (new == labels).all():
            break
        labels = new
    return labels


def temporal_kcore_ref(g, k, window):
    src, dst, ts, te, _ = _edges(g)
    ta, tb = window
    ok = (ts >= ta) & (te <= tb)
    alive = np.ones(g.n_vertices, bool)
    while True:
        deg = np.zeros(g.n_vertices, np.int64)
        live = ok & alive[src] & alive[dst]
        np.add.at(deg, src[live], 1)
        np.add.at(deg, dst[live], 1)
        new_alive = alive & (deg >= k)
        if (new_alive == alive).all():
            return alive
        alive = new_alive


def temporal_pagerank_ref(g, window, damping=0.85, n_iters=100):
    src, dst, ts, te, _ = _edges(g)
    ta, tb = window
    ok = (ts >= ta) & (te <= tb)
    V = g.n_vertices
    out_deg = np.zeros(V)
    np.add.at(out_deg, src[ok], 1.0)
    pr = np.full(V, 1.0 / V)
    for _ in range(n_iters):
        agg = np.zeros(V)
        contrib = np.where(out_deg[src] > 0, pr[src] / np.maximum(out_deg[src], 1), 0.0)
        np.add.at(agg, dst[ok], contrib[ok])
        dangling = pr[out_deg == 0].sum() / V
        pr = (1 - damping) / V + damping * (agg + dangling)
    return pr


def temporal_betweenness_ref(g, sources, window, pred="strictly_succeeds"):
    """Brandes over EA-optimal DAG, dst processed in ascending arrival order."""
    src, dst, ts, te, _ = _edges(g)
    ta, tb = window
    ok = (ts >= ta) & (te <= tb)
    V = g.n_vertices
    bc = np.zeros(V)
    for s in np.atleast_1d(sources):
        t = earliest_arrival_ref(g, s, window, pred)
        opt = (
            ok & (t[src] < INT_INF) & _follows(pred, t[src], ts)
            & (te == t[dst]) & (dst != s)
        )
        order = np.argsort(t, kind="stable")
        order = order[t[order] < INT_INF]
        sigma = np.zeros(V)
        sigma[s] = 1.0
        for v in order:
            if v == s:
                continue
            ine = np.nonzero(opt & (dst == v))[0]
            sigma[v] = sigma[src[ine]].sum()
        delta = np.zeros(V)
        for v in order[::-1]:
            if sigma[v] == 0:
                continue
            ine = np.nonzero(opt & (dst == v))[0]
            for e in ine:
                delta[src[e]] += sigma[src[e]] / sigma[v] * (1 + delta[v])
        delta[s] = 0
        bc += delta
    return bc


def overlaps_reachability_ref(g, source, window):
    """Exhaustive overlaps-chain reachability: per-vertex set of
    nondominated (start, end) last-edge intervals."""
    src, dst, ts, te, _ = _edges(g)
    ta, tb = window
    ok = (ts >= ta) & (te <= tb)
    eids = np.nonzero(ok)[0]
    states = [set() for _ in range(g.n_vertices)]
    states[source].add((ta, ta))
    for _ in range(g.n_vertices + 1):
        changed = False
        for e in eids:
            u, v = src[e], dst[e]
            for (s0, e0) in list(states[u]):
                if s0 <= ts[e] and e0 <= te[e]:
                    cand = (int(ts[e]), int(te[e]))
                    if cand not in states[v]:
                        dominated = any(
                            s1 <= cand[0] and e1 <= cand[1]
                            for (s1, e1) in states[v]
                        )
                        if not dominated:
                            states[v].add(cand)
                            changed = True
        if not changed:
            break
    reach = np.zeros(g.n_vertices, bool)
    for v, st in enumerate(states):
        reach[v] = len(st) > 0
    return reach


def count_window_edges_ref(g, window):
    """Exact selectivity oracle for the estimator benchmark."""
    _, _, ts, te, _ = _edges(g)
    ta, tb = window
    return int(((ts >= ta) & (te <= tb)).sum())
