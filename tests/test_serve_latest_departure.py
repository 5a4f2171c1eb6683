"""Latest departure ("arrive by") served beside earliest arrival ("depart
at"): the batched in-direction fixpoint over a view, and mixed batches of
per-row windows through ``serve_batch`` / ``GraphBatchServer.advance``,
cold and then fused, every row new on every advance.  Each row is
compared with the plain oracles of ``core/reference.py``, on seeded
power-law graphs and on a small timetable whose connections each take at
least a second."""
import numpy as np
import pytest

import repro.core.reference as R
from repro import telemetry
from repro.core.algorithms import latest_departure, latest_departure_over_view
from repro.core.edgemap import union_window, view_for_plan
from repro.core.temporal_graph import from_edges
from repro.core.tger import build_tger
from repro.data.generators import power_law_temporal_graph
from repro.engine import QueryBatch, QuerySpec, plan_query
from repro.serve import serve_batch
from repro.serve import window_sweep as ws
from repro.serve.engine import GraphBatchServer

ALGS = ("earliest_arrival", "latest_departure")
ORACLE = {"earliest_arrival": R.earliest_arrival_ref,
          "latest_departure": R.latest_departure_ref}


def _timetable(seed, n_stops=60, lines=6, per_line=12, trips=30):
    """Lines of ``per_line`` stops (shared stops are the transfers), each
    run both ways ``trips`` times a day; a hop takes 30-90 s and a vehicle
    stands 10 s at a stop."""
    rng = np.random.default_rng(seed)
    src, dst, ts, te = [], [], [], []
    for _ in range(lines):
        stops = rng.choice(n_stops, per_line, replace=False)
        hop = rng.integers(30, 91, per_line - 1)
        for seq, h in ((stops, hop), (stops[::-1], hop[::-1])):
            off = np.concatenate([[0], np.cumsum(h + 10)[:-1]])
            for t0 in np.sort(rng.integers(0, 4 * 3600, trips)):
                src += list(seq[:-1])
                dst += list(seq[1:])
                ts += list(t0 + off)
                te += list(t0 + off + h)
    g = from_edges(np.asarray(src), np.asarray(dst), np.asarray(ts),
                   np.asarray(te), n_vertices=n_stops)
    return g, build_tger(g, degree_cutoff=16)


def _power_law(seed):
    g = power_law_temporal_graph(150, 4000, seed=seed)
    return g, build_tger(g, degree_cutoff=40)


GRAPHS = {"power-law-3": lambda: _power_law(3),
          "power-law-11": lambda: _power_law(11),
          "timetable": lambda: _timetable(5)}
_BUILT = {}


def _graph(name):
    if name not in _BUILT:
        g, idx = GRAPHS[name]()
        _BUILT[name] = (g, idx, int(np.asarray(g.t_start).min()),
                        int(np.asarray(g.t_end).max()))
    return _BUILT[name]


def _rows(rng, n, t_lo, t_hi, width, n_vertices):
    """``n`` per-row windows of ``width`` starting uniformly in
    [t_lo, t_hi), with uniform stops."""
    starts = rng.integers(t_lo, t_hi, n)
    wins = np.stack([starts, starts + width], axis=1).astype(np.int32)
    return wins, rng.integers(0, n_vertices, n).astype(np.int32)


def test_timetable_connections_take_a_second():
    g = _graph("timetable")[0]
    assert (np.asarray(g.t_end) - np.asarray(g.t_start) >= 1).all()


@pytest.mark.parametrize("method", ["scan", "index", "hybrid"])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_over_view_rows_match_single_solves(graph, method):
    """Row q of the batched solve is ``latest_departure(g, sources[q],
    windows[q])`` bit for bit, and the oracle's answer."""
    g, idx, t0, t1 = _graph(graph)
    span = t1 - t0
    wins, targets = _rows(np.random.default_rng(1), 5, t0, t0 + span // 2,
                          span // 3, g.n_vertices)
    plan = plan_query(g, idx, windows=wins, access=method)
    edges = view_for_plan(g, idx, union_window(wins), plan)
    rows, rounds = latest_departure_over_view(
        edges, wins, plan=plan, n_vertices=g.n_vertices, sources=targets,
        with_rounds=True)
    assert int(rounds) >= 1
    for q, (w, t) in enumerate(zip(wins, targets)):
        win = (int(w[0]), int(w[1]))
        single = latest_departure(g, int(t), win, idx, plan=plan)
        np.testing.assert_array_equal(np.asarray(rows[q]), np.asarray(single))
        np.testing.assert_array_equal(
            np.asarray(rows[q]), R.latest_departure_ref(g, int(t), win))


def _batch(rng, k, g, t0, width, n=6):
    """A mixed batch: ``n`` rows alternating EA and LD, each its own
    window of ``width``, the first starting at ``t0 + k * width / 8`` and
    the others uniformly up to ``width / 2`` later (so the union slides
    forward)."""
    base = t0 + k * (width // 8)
    wins, stops = _rows(rng, n, base, base + width // 2, width, g.n_vertices)
    wins[0] = (base, base + width)
    specs = [QuerySpec.make(ALGS[i % 2], tuple(map(int, wins[i])),
                            sources=int(stops[i])) for i in range(n)]
    return QueryBatch.make(specs), specs


def _horizon_plan(g, idx, t0, width, advances):
    """One index plan whose ring covers every union of ``advances``
    batches, so that no advance falls cold for coverage."""
    last = t0 + advances * (width // 8) + width // 2 + width
    return plan_query(g, idx, windows=[(t0, last)], access="index")


def _assert_rows(g, batch, results):
    groups = batch.groups()
    assert [k[0] for k in groups] == list(ALGS)
    for (key, rows), res in zip(groups.items(), results):
        for j, row in enumerate(rows):
            want = ORACLE[key[0]](g, row.source, row.window)
            np.testing.assert_array_equal(np.asarray(res[j]), want,
                                          err_msg=f"{key[0]} row {j}")


@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_mixed_batches_cold_then_fused_every_row_new(graph):
    """Mixed EA + LD batches with per-row windows: the first advance is
    cold, the rest fused, every row new each time; every row equals the
    oracle, ``last_rounds`` is a tuple in group order, and from the
    second fused advance on nothing traces or compiles."""
    g, idx, t0, t1 = _graph(graph)
    width = (t1 - t0) // 10
    rng = np.random.default_rng(7)
    plan = _horizon_plan(g, idx, t0, width, 5)
    state, traces = None, None
    for k in range(5):
        batch, _ = _batch(rng, k, g, t0, width)
        with ws.dispatch_log() as log:
            results, state = serve_batch(g, batch, idx, state=state,
                                         plan=plan)
        _assert_rows(g, batch, results)
        assert isinstance(state.last_rounds, tuple)
        assert len(state.last_rounds) == 2
        assert all(int(r) >= 1 for r in state.last_rounds)
        assert state.n_solved == state.n_solved_unique == 6
        if k == 0:
            assert state.last_advance == "cold"
            continue
        assert log == ["fused:index"], log
        if k == 1:
            traces = ws.fused_trace_count()
        assert ws.fused_trace_count() == traces


def test_second_advance_of_a_shape_compiles_nothing():
    """Counted by JAX's own compile events: once a shape has been served
    fused, another advance of it (new rows, new windows) compiles
    nothing."""
    import jax

    g, idx, t0, t1 = _graph("timetable")
    width = (t1 - t0) // 10
    rng = np.random.default_rng(3)
    plan = _horizon_plan(g, idx, t0, width, 3)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, d, **_: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    state = None
    for k in range(3):
        batch, _ = _batch(rng, k, g, t0, width)
        n0 = len(compiles)
        results, state = serve_batch(g, batch, idx, state=state,
                                     plan=plan)
        _assert_rows(g, batch, results)
    assert state.last_advance == "delta"
    assert len(compiles) == n0, "the third advance compiled"
    assert isinstance(state.last_rounds, tuple)


def test_server_counts_rows_solved_per_request():
    """``serve.rows_solved`` adds the rows that ran a fixpoint in the
    request: all six when every row is new (cold, then fused), none when
    the same batch is asked again."""
    g, idx, t0, t1 = _graph("power-law-3")
    width = (t1 - t0) // 10
    rng = np.random.default_rng(9)
    server = GraphBatchServer(g, idx, plan=_horizon_plan(g, idx, t0, width, 2))
    for k in range(2):
        batch, _ = _batch(rng, k, g, t0, width)
        _assert_rows(g, batch, server.advance(batch))
        (rec,) = telemetry.recent(1)
        assert rec.counters["serve.rows_solved"] == 6
        assert isinstance(server.state.last_rounds, tuple)
    server.advance(batch)
    (rec,) = telemetry.recent(1)
    assert server.state.last_advance == "noop"
    assert rec.counters["serve.rows_solved"] == 0


def test_latest_departure_refuses_warm_starts():
    g, idx, t0, t1 = _graph("power-law-3")
    width = (t1 - t0) // 10
    w = (t0, t0 + width)
    wide = (t0, t0 + 2 * width)
    _, state = serve_batch(g, QueryBatch.make([QuerySpec.make(
        "latest_departure", w, sources=1)]), idx, access="index",
        warm_start=True)
    res, state = serve_batch(g, QueryBatch.make([QuerySpec.make(
        "latest_departure", wide, sources=1)]), idx, state=state,
        access="index", warm_start=True)
    assert state.warm_applied is False
    np.testing.assert_array_equal(np.asarray(res[0][0]),
                                  R.latest_departure_ref(g, 1, wide))
