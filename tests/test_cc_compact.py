"""Connected components in the view's own vertex space (DESIGN.md §7.4).

A cold cc solve over a view whose 2·E' endpoints are at most a quarter of
V runs hash-min label propagation over the vertices the view touches,
numbered in vertex order, instead of all V vertices.  Every case here holds the compact
solve to the dense solve over the same view bit for bit; the dense solve
is forced by an identity ``init``, which starts from the same labels as a
cold solve but is never compacted.
"""
import collections
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core.algorithms import connectivity as cc_mod
from repro.core.algorithms import temporal_cc_over_view
from repro.core.coldstore import ColdStore
from repro.core.edgemap import EdgeView
from repro.core.tger import build_tger
from repro.data.generators import power_law_temporal_graph
from repro.engine import QueryBatch, QuerySpec, make_plan
from repro.serve import serve_batch

_PLAN = make_plan("index")


def _dense(edges, windows, n_vertices):
    windows = jnp.asarray(windows, jnp.int32)
    ident = jnp.broadcast_to(jnp.arange(n_vertices, dtype=jnp.int32),
                             (windows.shape[0], n_vertices))
    return cc_mod._temporal_cc_over_view_dense(
        edges, windows, plan=_PLAN, n_vertices=n_vertices, init=ident)


def _view(rng, n_vertices, n_slots, t_max, *, live=1.0):
    """A view drawn with the deployments' degree law, (rank + 1)^-0.6 over
    randomly ranked vertices, so most of V is never touched.  A share
    ``1 - live`` of slots is masked off but keeps stale, in-range
    endpoints and times, as ``ColdStore.ring_stitch`` leaves them."""
    w = (np.arange(n_vertices) + 1.0) ** -0.6
    rank = rng.permutation(n_vertices)
    draw = lambda: rank[rng.choice(n_vertices, n_slots, p=w / w.sum())]
    ts = rng.integers(0, t_max, n_slots)
    return EdgeView(
        jnp.asarray(draw(), jnp.int32), jnp.asarray(draw(), jnp.int32),
        jnp.asarray(ts, jnp.int32),
        jnp.asarray(ts + rng.integers(0, 50, n_slots), jnp.int32),
        jnp.ones(n_slots, jnp.float32),
        jnp.asarray(rng.random(n_slots) < live))


def _power_law(rng):
    return _view(rng, 20_000, 1024, 1000), [[0, 1000]], 20_000


def _rows(rng):
    wins = [[0, 1000], [100, 400], [350, 900], [0, 150], [600, 610]]
    return _view(rng, 8192, 512, 1000), wins, 8192


def _stale(rng):
    return _view(rng, 8192, 512, 1000, live=0.5), [[0, 1000], [200, 700]], 8192


def _all_masked(rng):
    return _view(rng, 4096, 256, 1000, live=0.0), [[0, 1000], [10, 20]], 4096


def _stitched():
    """A cold-tier view stitched from the cold store's chunks and served
    through ``serve_batch(..., coldstore=...)``: its labels, and the
    dense solve over the view the state keeps."""
    g = power_law_temporal_graph(8000, 6000, seed=8)
    idx = build_tger(g, degree_cutoff=48)
    ts = np.asarray(g.t_start)
    t_min, span = int(ts.min()), int(ts.max() - ts.min())
    width, stride = span // 40, span // 200
    base, state = t_min + span // 2, None
    store = ColdStore(g, idx, chunk_slots=128)
    for k in range(8):
        t = base + k * stride
        _, state = serve_batch(g, QueryBatch.make([QuerySpec.make(
            "earliest_arrival", (t - width, t), sources=3)]), idx,
            state=state, access="index", coldstore=store)
    lo = t_min + span // 8
    wins = [[lo, lo + width], [lo + width // 3, lo + width]]
    batch = QueryBatch.make([QuerySpec.make("cc", tuple(w)) for w in wins])
    with telemetry.request("test.history"):
        (labels,), hstate = serve_batch(g, batch, idx, access="index",
                                        coldstore=store)
    (rec,) = telemetry.recent(1)
    assert hstate.plan.tier == "cold"
    assert rec.counters.get("cc.compact_solves") == 1
    return labels, _dense(hstate.edges, wins, g.n_vertices)


_CASES = {"power_law": _power_law, "rows": _rows, "stale_slots": _stale,
          "all_masked": _all_masked}


@pytest.mark.parametrize("case", [*_CASES, "stitched_cold_view"])
def test_compact_labels_equal_dense_bit_for_bit(case):
    if case == "stitched_cold_view":
        got, want = _stitched()
    else:
        edges, wins, V = _CASES[case](np.random.default_rng(14))
        assert cc_mod._cc_compacts(edges, _PLAN, V, None)
        got = temporal_cc_over_view(
            edges, jnp.asarray(wins, jnp.int32), plan=_PLAN, n_vertices=V)
        want = _dense(edges, wins, V)
        if case == "all_masked":
            assert (np.asarray(got) == np.arange(V)).all()
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert (got == want).all()
    # the windows merge something: the case is not all singletons
    assert case == "all_masked" or (got != np.arange(got.shape[1])).any()


@pytest.mark.parametrize("n_slots, n_vertices, init, sharded, compacts", [
    (512, 4096, False, False, True),        # 2·E' = V/4: compact
    (513, 4096, False, False, False),       # 2·E' > V/4: dense
    (512, 4096, True, False, False),        # warm start: dense
    (512, 4096, False, True, False),        # edge-sharded: dense
    (64, 200, False, False, False),         # small graph: dense
])
def test_shape_rule(n_slots, n_vertices, init, sharded, compacts):
    edges = EdgeView(*(jnp.zeros(n_slots, jnp.int32),) * 4,
                     jnp.ones(n_slots, jnp.float32),
                     jnp.ones(n_slots, bool))
    plan = dataclasses.replace(_PLAN, edge_axis="e") if sharded else _PLAN
    warm = jnp.zeros((1, n_vertices), jnp.int32) if init else None
    assert cc_mod._cc_compacts(edges, plan, n_vertices, warm) is compacts


@pytest.mark.parametrize("n_vertices, counted", [(4096, 1), (1024, 0)])
def test_compact_solves_counter(n_vertices, counted, monkeypatch):
    monkeypatch.setattr(telemetry, "_ring", collections.deque(
        maxlen=telemetry.RING_REQUESTS))
    edges, _, _ = _all_masked(np.random.default_rng(3))
    wins = jnp.asarray([[0, 1000]], jnp.int32)
    with telemetry.request("test.cc"):
        temporal_cc_over_view(edges, wins, plan=_PLAN,
                              n_vertices=n_vertices)
        temporal_cc_over_view(
            edges, wins, plan=_PLAN, n_vertices=n_vertices,
            init=jnp.arange(n_vertices, dtype=jnp.int32)[None])
    (rec,) = telemetry.recent(1)
    assert rec.counters.get("cc.compact_solves", 0) == counted
