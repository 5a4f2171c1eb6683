"""The in-program recorder (``repro.telemetry``) and the spans, counters and
named scopes of the serving path."""
import collections
import sys
import threading

import numpy as np
import pytest

from repro import telemetry
from repro.core.algorithms import paths
from repro.core.coldstore import ColdStore
from repro.core.tger import build_tger
from repro.data.generators import power_law_temporal_graph
from repro.engine import QueryBatch, QuerySpec
from repro.serve import GraphBatchServer
from repro.serve import window_sweep as ws


@pytest.fixture
def ring(monkeypatch):
    """A fresh, empty ring of completed requests."""
    fresh = collections.deque(maxlen=telemetry.RING_REQUESTS)
    monkeypatch.setattr(telemetry, "_ring", fresh)
    return fresh


# ---------------------------------------------------------------------------
# 1. the recorder
# ---------------------------------------------------------------------------


def test_spans_nest_under_the_request_and_carry_its_id(ring):
    with telemetry.request("req") as root:
        with telemetry.span("a") as a:
            with telemetry.span("a.1"):
                pass
        with telemetry.span("b"):
            telemetry.end("b")              # ended early: exit leaves it
    (rec,) = telemetry.recent(1)
    assert rec.ok and rec.root is root and rec.name == "req"
    assert [s.name for s in rec.spans] == ["req", "a", "a.1", "b"]
    assert [s.id for s in rec.spans] == [0, 1, 2, 3]
    assert [s.parent for s in rec.spans] == [None, 0, 1, 0]
    assert {s.request for s in rec.spans} == {rec.request}
    assert [s.name for s in rec.children(root)] == ["a", "b"]
    assert [s.name for s in rec.children(a)] == ["a.1"]
    for s in rec.spans:
        assert s.end is not None and s.start <= s.end
        if s.parent is not None:
            p = rec.spans[s.parent]
            assert p.start <= s.start and s.end <= p.end
    with telemetry.request("req"):
        pass
    first, second = telemetry.recent(2)
    assert first is rec and second.request > rec.request


def test_counters_are_kept_per_request(ring):
    telemetry.count("bytes", 5)             # outside a request: dropped
    with telemetry.request("req"):
        telemetry.count("bytes", 3)
        with telemetry.span("inner"):
            telemetry.count("bytes", 4)
            telemetry.count("rows", 1)
    with telemetry.request("req"):
        telemetry.count("rows", 2)
    a, b = telemetry.recent(2)
    assert a.counters == {"bytes": 7, "rows": 1}
    assert b.counters == {"rows": 2}


def test_nested_request_is_a_child_span(ring):
    with telemetry.request("outer"):
        with telemetry.request("inner"):
            pass
    (rec,) = telemetry.recent(5)
    assert [(s.name, s.parent) for s in rec.spans] == [
        ("outer", None), ("inner", 0)]


def test_spans_outside_a_request_are_not_recorded(ring):
    with telemetry.span("loose"):
        with telemetry.span("inner"):
            pass
    s = telemetry.begin("phase")
    telemetry.end("phase")
    assert s.end is not None and s.request is None
    assert telemetry.recent(10) == []


def test_failed_requests_are_kept_but_not_recent(ring):
    with telemetry.request("req"):
        telemetry.count("n", 1)
    with pytest.raises(KeyError):
        with telemetry.request("req"):
            with telemetry.span("open"):
                raise KeyError("x")
    with telemetry.request("req"):
        telemetry.count("n", 3)
    assert len(ring) == 3 and not ring[1].ok
    assert all(s.end is not None for s in ring[1].spans)
    assert [r.counters["n"] for r in telemetry.recent(5)] == [1, 3]
    assert [r.counters["n"] for r in telemetry.recent(1)] == [3]
    assert telemetry.recent(0) == []


def test_ring_is_bounded(ring):
    n = telemetry.RING_REQUESTS + 10
    for i in range(n):
        with telemetry.request("req"):
            telemetry.count("i", i)
    got = telemetry.recent(n)
    assert len(got) == telemetry.RING_REQUESTS
    assert got[0].counters["i"] == 10 and got[-1].counters["i"] == n - 1


def test_threads_keep_their_requests_apart(ring):
    n_threads, n_requests = 16, 100
    errors = []

    def work(k):
        try:
            for i in range(n_requests):
                with telemetry.request("req"):
                    telemetry.count("thread", k)
                    with telemetry.span("a"):
                        with telemetry.span("b"):
                            pass
                    telemetry.count("i", i)
        except Exception as e:               # surfaced by the assert below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    recs = telemetry.recent(n_threads * n_requests)
    assert len(recs) == n_threads * n_requests
    assert len({r.request for r in recs}) == len(recs)
    per_thread = collections.Counter(r.counters["thread"] for r in recs)
    assert set(per_thread.values()) == {n_requests}
    for r in recs:
        assert [(s.name, s.parent) for s in r.spans] == [
            ("req", None), ("a", 0), ("b", 1)]
        assert {s.request for s in r.spans} == {r.request}


def test_decorated_function_is_a_span_per_call(ring):
    @telemetry.span("work")
    def work(k):
        return k + 1

    with telemetry.request("req"):
        assert work(1) == 2 and work(2) == 3
    (rec,) = telemetry.recent(1)
    assert [s.name for s in rec.spans] == ["req", "work", "work"]


# ---------------------------------------------------------------------------
# 2. the serving path
# ---------------------------------------------------------------------------

_CASE = {}


def _case():
    if not _CASE:
        g = power_law_temporal_graph(200, 5000, seed=8)
        idx = build_tger(g, degree_cutoff=48)
        ts = np.asarray(g.t_start)
        t_min, span = int(ts.min()), int(ts.max() - ts.min())
        _CASE["v"] = (g, idx, t_min, span)
    return _CASE["v"]


def _batch(t, width):
    return QueryBatch.make([
        QuerySpec.make("earliest_arrival", (t - width, t), sources=3),
        QuerySpec.make("cc", (t - width, t)),
        QuerySpec.make("bfs", (t - width, t), sources=(5, 9))])


def test_advance_records_plan_dispatch_wait_and_copy():
    g, idx, t_min, span = _case()
    width, stride = span // 40, span // 200
    server = GraphBatchServer(g, idx, access="index")
    base = t_min + span // 2
    server.advance(_batch(base, width))
    server.advance(_batch(base + stride, width))
    assert server.state.last_advance == "delta"
    (rec,) = telemetry.recent(1)
    assert rec.ok and rec.name == "serve.advance"
    kids = rec.children(rec.root)
    assert [s.name for s in kids] == (
        ["serve.plan", "serve.dispatch"] + ["serve.wait", "serve.copy"] * 3)
    assert all(s.request == rec.request for s in rec.spans)
    assert sum(s.duration for s in kids) <= rec.root.duration
    assert len(rec.spans) == len(kids) + 1      # a fused serve plans nothing


def test_cold_query_counts_its_upload_and_stitches_once():
    g, idx, t_min, span = _case()
    width, stride = span // 40, span // 200
    store = ColdStore(g, idx, chunk_slots=256)
    server = GraphBatchServer(g, idx, access="index", coldstore=store)
    base = t_min + span // 2
    for k in range(3):
        server.advance(_batch(base + k * stride, width))
    past = _batch(t_min + span // 8, width)
    server.advance(past)
    assert server.state.plan.tier == "cold"
    (rec,) = telemetry.recent(1)
    assert len(rec.named("cold.stitch")) == 1
    assert len(rec.named("cold.upload")) == 1
    assert len(rec.named("serve.dispatch")) == len(past.groups())
    plan = server.state.plan
    fields, mask, _, _ = store.ring_stitch(
        past.union(), plan.ring_capacity or plan.budget)
    assert rec.counters["cold.upload_bytes"] == (
        mask.nbytes + sum(a.nbytes for a in fields))
    assert [s.name for s in rec.children(rec.named("serve.plan")[0])] == [
        "plan.build"]


def test_failed_advance_is_recorded_not_ok():
    g, idx, t_min, span = _case()
    server = GraphBatchServer(g, idx, access="index")
    bad = QueryBatch.make([QuerySpec.make("kcore", (t_min, t_min + span))])
    before = telemetry.recent(1)
    with pytest.raises(ValueError):
        server.advance(bad)                 # kcore without k=
    assert telemetry.recent(1) == before
    assert not telemetry._ring[-1].ok


# ---------------------------------------------------------------------------
# 3. named scopes in the programs
# ---------------------------------------------------------------------------


def test_named_scopes_in_fused_step_and_cold_solve(monkeypatch):
    g, idx, t_min, span = _case()
    width, stride = span // 40, span // 200
    lowered = []
    real = ws._call_donating

    def spy(fn, *args, **kwargs):
        lowered.append(fn.lower(*args, **kwargs).as_text(debug_info=True))
        return real(fn, *args, **kwargs)

    monkeypatch.setattr(ws, "_call_donating", spy)
    server = GraphBatchServer(g, idx, access="index")
    base = t_min + span // 2
    server.advance(_batch(base, width))
    st = server.state
    cold = paths._earliest_arrival_over_view_dense.lower(
        st.edges, np.asarray([[base - width, base]], np.int32),
        plan=st.plan, n_vertices=g.n_vertices,
        sources=np.asarray([3], np.int32), with_rounds=True,
    ).as_text(debug_info=True)
    server.advance(_batch(base + stride, width))
    (fused,) = lowered
    for scope in ("fixpoint.earliest_arrival", "fixpoint.cc",
                  "fixpoint.bfs", "combine", "ring.advance", "assemble"):
        assert scope in fused, scope
    assert "fixpoint.earliest_arrival" in cold and "combine" in cold
    assert "ring.advance" not in cold
