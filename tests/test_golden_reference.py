"""Golden-reference tests: every algorithm, executed through REAL plans
(index and hybrid — the paths the edgemap-level parity tests only compared
against scan), checked against the pure-Python oracles in
``core/reference.py`` on small seeded random temporal graphs
(``data/generators``).  Batched [W, V] sweeps are checked row-by-row
against the same oracles.
"""
import numpy as np
import pytest

from repro.core import reference as R
from repro.core.algorithms import (
    earliest_arrival,
    earliest_arrival_batched,
    fastest,
    latest_departure,
    overlaps_reachability,
    overlaps_reachability_batched,
    shortest_duration,
    temporal_bfs,
    temporal_cc,
    temporal_kcore,
    temporal_pagerank,
    temporal_pagerank_batched,
    temporal_betweenness,
)
from repro.core.tger import build_tger
from repro.data.generators import synthetic_temporal_graph
from repro.engine import make_plan, per_vertex_window_budget

SEEDS = [5, 19]

_CASES = {}


def _case(seed):
    """graph + TGER + window + the three covering plans, cached per seed."""
    if seed not in _CASES:
        g = synthetic_temporal_graph(36, 240, seed=seed)
        idx = build_tger(g, degree_cutoff=8, n_time_buckets=8)
        ts = np.asarray(g.t_start)
        win = (int(np.quantile(ts, 0.3)), int(np.asarray(g.t_end).max()))
        in_win = int(((ts >= win[0]) & (ts <= win[1])).sum())
        budget = max(64, 1 << in_win.bit_length())
        kb = per_vertex_window_budget(g, idx, win)
        plans = {
            "scan": make_plan("scan"),
            "index": make_plan("index", budget=budget),
            "hybrid": make_plan("hybrid", per_vertex_budget=kb),
        }
        src = int(np.asarray(g.src)[seed % g.n_edges])
        _CASES[seed] = (g, idx, win, plans, src)
    return _CASES[seed]


@pytest.mark.parametrize("seed", SEEDS)
def test_earliest_arrival_all_plans_vs_oracle(seed):
    g, idx, win, plans, src = _case(seed)
    ref = R.earliest_arrival_ref(g, src, win)
    for name, plan in plans.items():
        got = np.asarray(earliest_arrival(g, src, win, idx, plan=plan))
        assert (got == ref).all(), f"{name} diverges from the oracle"


@pytest.mark.parametrize("seed", SEEDS)
def test_latest_departure_all_plans_vs_oracle(seed):
    g, idx, win, plans, src = _case(seed)
    ref = R.latest_departure_ref(g, src, win)
    for name, plan in plans.items():
        got = np.asarray(latest_departure(g, src, win, idx, plan=plan))
        assert (got == ref).all(), f"{name} diverges from the oracle"


@pytest.mark.parametrize("seed", SEEDS)
def test_bfs_all_plans_vs_oracle(seed):
    g, idx, win, plans, src = _case(seed)
    h_ref, a_ref = R.temporal_bfs_ref(g, src, win)
    for name, plan in plans.items():
        hops, arr = temporal_bfs(g, src, win, idx, plan=plan)
        assert (np.asarray(hops) == h_ref).all(), name
        assert (np.asarray(arr) == a_ref).all(), name


@pytest.mark.parametrize("seed", SEEDS)
def test_fastest_all_plans_vs_oracle(seed):
    g, idx, win, plans, src = _case(seed)
    ref = R.fastest_ref(g, src, win)
    for name, plan in plans.items():
        got = np.asarray(
            fastest(g, src, win, idx, plan=plan, n_departures=256))
        assert (got == ref).all(), f"{name} diverges from the oracle"


@pytest.mark.parametrize("seed", SEEDS)
def test_shortest_duration_all_plans_vs_oracle(seed):
    g, idx, win, plans, src = _case(seed)
    ref = R.shortest_duration_ref(g, src, win)
    finite = np.isfinite(ref)
    for name, plan in plans.items():
        got = np.asarray(
            shortest_duration(g, src, win, idx, plan=plan, n_buckets=256))
        assert (np.isfinite(got) == finite).all(), name
        assert (got[finite] == ref[finite]).all(), name


@pytest.mark.parametrize("seed", SEEDS)
def test_cc_all_plans_vs_oracle(seed):
    g, idx, win, plans, _ = _case(seed)
    ref = R.temporal_cc_ref(g, win)
    for name, plan in plans.items():
        got = np.asarray(temporal_cc(g, win, idx, plan=plan))
        assert (got == ref).all(), f"{name} diverges from the oracle"


@pytest.mark.parametrize("seed", SEEDS)
def test_kcore_all_plans_vs_oracle(seed):
    g, idx, win, plans, _ = _case(seed)
    for k in (2, 3):
        ref = R.temporal_kcore_ref(g, k, win)
        for name, plan in plans.items():
            got = np.asarray(temporal_kcore(g, k, win, idx, plan=plan))
            assert (got == ref).all(), f"{name} k={k} diverges"


@pytest.mark.parametrize("seed", SEEDS)
def test_pagerank_all_plans_vs_oracle(seed):
    g, idx, win, plans, _ = _case(seed)
    ref = R.temporal_pagerank_ref(g, win, n_iters=40)
    for name, plan in plans.items():
        got = np.asarray(temporal_pagerank(g, win, idx, n_iters=40, plan=plan))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-7,
                                   err_msg=f"{name} diverges from the oracle")


@pytest.mark.parametrize("seed", SEEDS)
def test_betweenness_all_plans_vs_oracle(seed):
    g, idx, win, plans, src = _case(seed)
    ref = R.temporal_betweenness_ref(g, [src], win)
    for name, plan in plans.items():
        got = np.asarray(
            temporal_betweenness(g, [src], win, idx, plan=plan, n_buckets=512))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5,
                                   err_msg=f"{name} diverges from the oracle")


@pytest.mark.parametrize("seed", SEEDS)
def test_reachability_all_plans_vs_oracle(seed):
    g, idx, win, plans, src = _case(seed)
    ref = R.overlaps_reachability_ref(g, src, win)
    for name, plan in plans.items():
        reach, _, _ = overlaps_reachability(g, src, win, idx, plan=plan)
        got = np.asarray(reach)
        # reported set is sound (subset of the oracle), exact when the
        # lexicographic min loses no needed start (see reachability.py)
        assert (got <= ref).all(), f"{name} reports an unreachable vertex"
        assert got[src], name


# ---------------------------------------------------------------------------
# batched sweeps, row-by-row against the oracles
# ---------------------------------------------------------------------------

def _windows_for(g, count=4):
    ts = np.asarray(g.t_start)
    t_max = int(np.asarray(g.t_end).max())
    return np.asarray(
        [(int(np.quantile(ts, q)), t_max) for q in np.linspace(0, 0.6, count)],
        np.int32,
    )


@pytest.mark.parametrize("seed", SEEDS)
def test_batched_earliest_arrival_vs_oracle(seed):
    g, idx, _, _, src = _case(seed)
    wins = _windows_for(g)
    union = (int(wins[:, 0].min()), int(wins[:, 1].max()))
    ts = np.asarray(g.t_start)
    in_union = int(((ts >= union[0]) & (ts <= union[1])).sum())
    plans = {
        "scan": make_plan("scan", n_windows=len(wins)),
        "index": make_plan("index", budget=max(64, 1 << in_union.bit_length()),
                           n_windows=len(wins)),
        "hybrid": make_plan(
            "hybrid", per_vertex_budget=per_vertex_window_budget(g, idx, union),
            n_windows=len(wins)),
    }
    for name, plan in plans.items():
        got = np.asarray(earliest_arrival_batched(g, src, wins, idx, plan=plan))
        for i, w in enumerate(wins):
            ref = R.earliest_arrival_ref(g, src, (int(w[0]), int(w[1])))
            assert (got[i] == ref).all(), f"{name} window {i} diverges"


def test_batched_pagerank_and_reachability_vs_oracle():
    g, idx, win, plans, src = _case(SEEDS[0])
    wins = _windows_for(g)
    pr = np.asarray(temporal_pagerank_batched(g, wins, idx, n_iters=40))
    for i, w in enumerate(wins):
        ref = R.temporal_pagerank_ref(g, (int(w[0]), int(w[1])), n_iters=40)
        np.testing.assert_allclose(pr[i], ref, rtol=1e-5, atol=1e-7)
    reach, _, _ = overlaps_reachability_batched(g, src, wins, idx)
    for i, w in enumerate(wins):
        ref = R.overlaps_reachability_ref(g, src, (int(w[0]), int(w[1])))
        assert (np.asarray(reach)[i] <= ref).all()


def _earliest_arrival_loop(g, source, window):
    """The per-edge loop oracle the vectorised ``earliest_arrival_ref``
    replaced (in-place relaxation inside a round)."""
    src, dst, ts, te = (np.asarray(a) for a in (g.src, g.dst, g.t_start,
                                                 g.t_end))
    ok = (ts >= window[0]) & (te <= window[1])
    arr = np.full(g.n_vertices, R.INT_INF, np.int64)
    arr[source] = window[0]
    for _ in range(g.n_vertices + 1):
        relax = ok & (arr[src] < R.INT_INF) & (arr[src] <= ts)
        changed = False
        for e in np.nonzero(relax)[0]:
            if te[e] < arr[dst[e]]:
                arr[dst[e]] = te[e]
                changed = True
        if not changed:
            break
    return arr


def _cc_loop(g, window):
    """The per-edge union-by-min-label loop oracle the vectorised
    ``temporal_cc_ref`` replaced."""
    src, dst, ts, te = (np.asarray(a) for a in (g.src, g.dst, g.t_start,
                                                 g.t_end))
    ok = (ts >= window[0]) & (te <= window[1])
    labels = np.arange(g.n_vertices)
    for _ in range(g.n_vertices + 1):
        changed = False
        for e in np.nonzero(ok)[0]:
            a, b = labels[src[e]], labels[dst[e]]
            m = min(a, b)
            if a != m or b != m:
                labels[labels == a] = m
                labels[labels == b] = m
                changed = True
        if not changed:
            break
    return labels


@pytest.mark.parametrize("seed", [3, 11, 29])
@pytest.mark.parametrize("oracle", ["earliest_arrival", "cc"])
def test_vectorised_oracles_match_loop_oracles(oracle, seed):
    """Same integer arithmetic, another order: results must be equal."""
    g = synthetic_temporal_graph(60, 400, seed=seed)
    ts = np.asarray(g.t_start)
    win = (int(np.quantile(ts, 0.2)), int(np.asarray(g.t_end).max()))
    if oracle == "cc":
        assert np.array_equal(R.temporal_cc_ref(g, win), _cc_loop(g, win))
    else:
        for src in range(0, 60, 7):
            assert np.array_equal(R.earliest_arrival_ref(g, src, win),
                                  _earliest_arrival_loop(g, src, win))
