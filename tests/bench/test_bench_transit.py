"""The london-transit deployment and its ldn-journeys-q8-h60 cell: the
timetable keeps the published counts, its columns are pinned, the
Connection Scan reference agrees with the round-based oracles of the
program's ``core/reference.py``, and the cell runs end to end on the CPU
at a small size: sound runs are correct, the stale control and a planted
wrong latest-departure row are not, and the traced run reads the cell's
own per-layer metrics."""
import hashlib
import types

import numpy as np
import pytest

import cellcheck
from bench import harness

ROOT = cellcheck.ROOT
WORKLOAD = "ldn-journeys-q8-h60"
CFG = harness.load_config("london-transit", ROOT)
GEN = harness.load_module(CFG["generator"], ROOT)
REF = harness.load_module(CFG["reference"], ROOT)


def tiny() -> dict:
    """The deployment at 300 stops, stop density and trip length kept."""
    return dict(CFG, n_stops=300, n_routes=30, n_trips=900,
                n_connections=27000, area_km2=23, tger_degree_cutoff=34)


def test_full_size_keeps_the_published_counts():
    src, dst, ts, te, trip_hops = GEN.draw(CFG)
    pub = CFG["published"]
    assert src.size == CFG["n_connections"] == pub["n_connections"]
    assert trip_hops.size == CFG["n_trips"] == pub["n_trips"]
    assert (trip_hops >= 1).all() and trip_hops.sum() == src.size
    assert CFG["n_stops"] == pub["n_stops"]
    assert np.union1d(src, dst).size == CFG["n_stops"]   # every stop served
    assert (te - ts >= 1).all() and (src != dst).all()


# SHA-256 of the five columns at the small size, as first drawn
PINNED = {
    5: "9bbb2dae9654f29054eec6934e1b0ab784aabd761b3e0c20842097e5e6911fcf",
    2**31 + 7:
        "d1b3677ba8510d2315cdd7daa7ce67e3bd114ca158e935abcc81a7e73f64f343",
}


@pytest.mark.parametrize("seed", PINNED)
def test_timetable_draws_the_pinned_columns(seed):
    cols = GEN.generate(tiny(), seed)
    h = hashlib.sha256()
    for f in ("src", "dst", "t_start", "t_end", "rank_to_id"):
        a = np.ascontiguousarray(getattr(cols, f))
        h.update(f.encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PINNED[seed]


def test_every_seed_serves_one_timetable_under_other_labels():
    a, b = GEN.generate(tiny(), 5), GEN.generate(tiny(), 6)
    assert not np.array_equal(a.src, b.src)
    ranks = []
    for cols in (a, b):
        back = np.argsort(cols.rank_to_id)
        ranks.append((back[cols.src], back[cols.dst]))
    assert all(np.array_equal(x, y) for x, y in zip(*ranks))
    assert np.array_equal(a.t_start, b.t_start)
    assert np.array_equal(a.t_end, b.t_end)


@pytest.mark.parametrize("start_h", [7, 12])
def test_connection_scan_equals_the_round_based_oracles(start_h):
    import repro.core.reference as R

    cols = GEN.generate(tiny(), 11)
    g = types.SimpleNamespace(
        src=cols.src, dst=cols.dst, t_start=cols.t_start, t_end=cols.t_end,
        weight=np.ones(cols.n_edges, np.float32), n_vertices=cols.n_vertices)
    window = (start_h * 3600, start_h * 3600 + 3600)
    we = REF.window_edges(cols, window)
    reached = 0
    for stop in np.random.default_rng(start_h).integers(0, 300, 4):
        (ea,) = REF.solve(we, "earliest_arrival", int(stop), {})
        (ld,) = REF.solve(we, "latest_departure", int(stop), {})
        np.testing.assert_array_equal(ea, R.earliest_arrival_ref(
            g, int(stop), window))
        np.testing.assert_array_equal(ld, R.latest_departure_ref(
            g, int(stop), window))
        reached += (ea < REF.INF).sum() + (ld > REF.NEG_INF).sum()
    assert reached > 4 * 2 * 10


def _run(seed, trace=False, **kw):
    return harness.run(WORKLOAD, seed, 0.5, trace, config=tiny(), **kw)


def test_sound_run_is_correct_and_the_stale_control_is_not():
    out = _run(2**31 + 101, control=True)
    cellcheck.assert_sound(out)
    assert out["checks"]["rows_checked"]["value"] == 8
    assert set(out["metrics"]) == {"setup_s", "advance_s"}
    cellcheck.assert_control_fails(out)


def test_a_wrong_latest_departure_row_is_not_correct(monkeypatch):
    from repro.serve.engine import GraphBatchServer

    real = GraphBatchServer.advance

    def advance(self, batch):
        out = real(self, batch)
        (ea_key, ld_key) = batch.groups()
        assert ld_key[0] == "latest_departure"
        ld = out[1].copy()
        ld[0, 0] ^= 1
        return [out[0], ld]

    monkeypatch.setattr(GraphBatchServer, "advance", advance)
    out = _run(2**31 + 103)
    assert out["correct"] is False
    assert out["checks"]["rows_differ"]["value"] == 1


def test_traced_run_reads_the_cells_metrics(tmp_path):
    out = _run(2**31 + 105, trace=True, trace_dir=str(tmp_path))
    cellcheck.assert_sound(out)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["rows_solved.advance"] == 8
    assert m["ld_rounds.advance"] >= 2 and m["ea_rounds.advance"] >= 2
    assert m["prep_s.advance"] > 0 and m["copy_s.advance"] > 0
