"""Long-horizon incremental serving soak (the PR's acceptance property).

Drives ``sweep_incremental`` through hundreds of stride advances — mixed
stride multiples, multiple full ring wrap-arounds, periodic backward jumps
that trigger the cold fallback — asserting at EVERY step that the fused
one-dispatch advance stays bit-identical to the cold batched sweep under
the same plan, for all three access methods.  After warmup the jit cache is
pinned: advances must stop tracing (the whole point of the ring-capacity /
delta-budget rungs in the static signature).

Also here: the one-dispatch property itself (the steady-state advance logs
exactly one fused dispatch site), the explicit ``warm_start=`` semantics
(sound containment cases fire; unsound cases are refused), and the
``touched``-driven convergence metric against a host-side oracle.

``SOAK_ADVANCES`` defaults to 220 and drops to 60 under CI (the ``CI``
env var GitHub Actions sets; ``scripts/ci.sh`` exports it too) so the tier-1
wall clock stays bounded — override explicitly to soak longer.
"""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.predicates import in_window
from repro.core.reference import overlaps_reachability_ref
from repro.core.tger import build_tger
from repro.core.temporal_graph import from_edges
from repro.data.generators import power_law_temporal_graph
from repro.engine import make_plan
from repro.serve import sliding_windows, sweep, sweep_incremental
from repro.serve import window_sweep as ws

SOAK_ADVANCES = int(os.environ.get(
    "SOAK_ADVANCES", "60" if os.environ.get("CI") else "220"))

_CASE = {}


def _serving_case():
    if not _CASE:
        g = power_law_temporal_graph(200, 5000, seed=8)
        idx = build_tger(g, degree_cutoff=48)
        ts = np.asarray(g.t_start)
        _CASE["v"] = (
            g, idx, int(np.argmax(np.asarray(g.out_degree))),
            int(ts.min()), int(np.asarray(g.t_end).max()),
        )
    return _CASE["v"]


# ---------------------------------------------------------------------------
# the soak
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["index", "hybrid", "scan"])
def test_long_horizon_soak_bit_identical_every_advance(method):
    g, idx, src, t_min, t_max = _serving_case()
    span = t_max - t_min
    width = max(span // 50, 4)
    stride = max(width // 4, 1)
    W = 4
    rng = np.random.default_rng(0)
    base0 = t_min + width + (W + 3) * stride
    base = base0
    state = None
    counts = {"cold": 0, "fused": 0}
    # warmup covers 3/4 of the horizon: the (capacity, delta-rung, n-new)
    # static product saturates slowly under the CI-reduced advance count
    # (a hybrid rung first appears around step 42 of the seeded schedule)
    warmup = (SOAK_ADVANCES * 3) // 4
    traces_at_warmup = None

    for step in range(SOAK_ADVANCES):
        k = int(rng.integers(1, 4))     # mixed strides: 1-3 base strides
        base += k * stride
        wrapped = base > t_max + width
        if wrapped:                     # slid past the data: jump BACK
            base = base0 + int(rng.integers(0, stride))  # (cold trigger)
        wins = sliding_windows(base, width=width, stride=stride, count=W)
        res, state = sweep_incremental(
            g, src, wins, idx, algorithm="earliest_arrival", state=state,
            access=method)
        cold_res = sweep(g, src, wins, idx, plan=state.plan)
        assert (np.asarray(res) == np.asarray(cold_res)).all(), (
            f"{method}: advance {step} diverged from the cold sweep")

        if state.last_advance == "cold":
            counts["cold"] += 1
            assert state.n_solved == W
        else:
            counts["fused"] += 1
            assert state.last_advance == (
                "reuse" if method == "scan" else "delta"), (
                f"{method}: advance {step} took {state.last_advance}")
            if wrapped:
                # a backward jump never matches the previous rows: index
                # and hybrid fall cold (asserted above), scan reuses its
                # full view and re-solves the whole batch in one dispatch
                assert method == "scan" and state.n_solved == W
            else:
                # a k-stride forward slide re-solves exactly the k entering
                # windows; every surviving row is reused
                assert state.n_solved == min(k, W), (
                    f"{method}: advance {step} solved {state.n_solved}, "
                    f"expected {min(k, W)}")
        if step == warmup:
            traces_at_warmup = ws.fused_trace_count()

    assert counts["fused"] > 4 * max(counts["cold"], 1), (
        f"{method}: the steady state must be fused, got {counts}")
    # retrace pinning: the (capacity, delta-rung, n-new) static signatures
    # are a small closed set — after warmup, NOTHING new may trace.
    assert ws.fused_trace_count() == traces_at_warmup, (
        f"{method}: fused steps kept tracing after warmup "
        f"({traces_at_warmup} -> {ws.fused_trace_count()})")


# ---------------------------------------------------------------------------
# one-dispatch property
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["index", "hybrid", "scan"])
def test_steady_state_advance_is_one_dispatch(method):
    """The acceptance criterion: a steady-state advance goes through exactly
    ONE device-dispatch site — the fused step (view slide + fixpoint solve +
    row assembly in a single jitted program)."""
    g, idx, src, t_min, t_max = _serving_case()
    span = t_max - t_min
    width, stride, W = max(span // 50, 4), max(span // 200, 1), 5
    base = t_max - 10 * stride
    _, state = sweep_incremental(
        g, src, sliding_windows(base, width=width, stride=stride, count=W),
        idx, access=method)
    # warm the advance program itself before observing dispatch sites
    _, state = sweep_incremental(
        g, src,
        sliding_windows(base + stride, width=width, stride=stride, count=W),
        idx, state=state, access=method)

    ws._DISPATCH_LOG = log = []
    try:
        res, state = sweep_incremental(
            g, src,
            sliding_windows(base + 2 * stride, width=width, stride=stride,
                            count=W),
            idx, state=state, access=method)
    finally:
        ws._DISPATCH_LOG = None
    expected = "fused:scan" if method == "scan" else f"fused:{method}"
    assert log == [expected], (
        f"steady-state advance dispatched {log}, expected [{expected!r}]")
    assert state.last_advance == ("reuse" if method == "scan" else "delta")
    cold = sweep(g, src, state.windows, idx, plan=state.plan)
    assert (np.asarray(res) == np.asarray(cold)).all()


def test_identical_windows_are_a_noop():
    g, idx, src, t_min, t_max = _serving_case()
    span = t_max - t_min
    wins = sliding_windows(t_max, width=max(span // 40, 4),
                           stride=max(span // 80, 1), count=3)
    res0, state = sweep_incremental(g, src, wins, idx, access="index")
    ws._DISPATCH_LOG = log = []
    try:
        res1, state = sweep_incremental(g, src, wins, idx, state=state,
                                        access="index")
    finally:
        ws._DISPATCH_LOG = None
    assert log == [] and state.last_advance == "noop" and state.n_solved == 0
    assert res1 is res0 or (np.asarray(res1) == np.asarray(res0)).all()


def test_reordered_windows_reuse_all_rows():
    g, idx, src, t_min, t_max = _serving_case()
    span = t_max - t_min
    wins = sliding_windows(t_max, width=max(span // 40, 4),
                           stride=max(span // 80, 1), count=4)
    _, state = sweep_incremental(g, src, wins, idx, access="index")
    perm = np.asarray([2, 0, 3, 1])
    res, state = sweep_incremental(g, src, wins[perm], idx, state=state,
                                   access="index")
    assert state.last_advance == "reorder" and state.n_solved == 0
    cold = sweep(g, src, wins[perm], idx, plan=state.plan)
    assert (np.asarray(res) == np.asarray(cold)).all()


def test_consumed_state_is_moved_from():
    """The donation contract (DESIGN.md §7.3): a state passed to an advance
    is single-use — its buffers are donated to the fused step, and reusing
    it raises rather than silently serving stale data."""
    g, idx, src, t_min, t_max = _serving_case()
    span = t_max - t_min
    width, stride, W = max(span // 50, 4), max(span // 200, 1), 3
    base = t_max - 10 * stride
    _, state = sweep_incremental(
        g, src, sliding_windows(base, width=width, stride=stride, count=W),
        idx, access="index")
    wins1 = sliding_windows(base + stride, width=width, stride=stride,
                            count=W)
    _, _ = sweep_incremental(g, src, wins1, idx, state=state, access="index")
    wins2 = sliding_windows(base + 2 * stride, width=width, stride=stride,
                            count=W)
    # the exact layer that notices varies ("Array has been deleted" from
    # the array guard, "buffer has been deleted or donated" from the
    # runtime) — both name deletion
    with pytest.raises(Exception, match="deleted"):
        sweep_incremental(g, src, wins2, idx, state=state, access="index")


# ---------------------------------------------------------------------------
# explicit warm_start= semantics (DESIGN.md §7.2)
# ---------------------------------------------------------------------------

def _widening_case():
    """wins0 then wins1 where wins1's second window strictly CONTAINS a
    previously-answered window (the sound containment case)."""
    g, idx, src, t_min, t_max = _serving_case()
    span = t_max - t_min
    lo, mid = t_min, t_min + span // 2
    wins0 = np.asarray([[lo, mid], [lo + span // 4, mid]], np.int32)
    wins1 = np.asarray(
        [[lo, mid], [lo + span // 8, mid + span // 8]], np.int32)
    return g, idx, src, wins0, wins1


def test_warm_start_defaults_off():
    g, idx, src, wins0, wins1 = _widening_case()
    _, state = sweep_incremental(g, src, wins0, idx, access="index")
    _, state = sweep_incremental(g, src, wins1, idx, state=state,
                                 access="index")
    assert not state.warm_applied


def test_warm_start_reachability_sound_containment():
    """Reachability warm starts (opt-in) seed from contained windows; the
    result must match the exhaustive overlaps oracle per solved window —
    warm labels are sound AND complete on these sizes."""
    g, idx, src, wins0, wins1 = _widening_case()
    _, state = sweep_incremental(g, src, wins0, idx,
                                 algorithm="reachability", access="index",
                                 warm_start=True)
    res, state = sweep_incremental(g, src, wins1, idx,
                                   algorithm="reachability", state=state,
                                   access="index", warm_start=True)
    assert state.warm_applied and state.n_solved == 1
    reach = np.asarray(res[0])
    for i, w in enumerate(wins1):
        oracle = overlaps_reachability_ref(g, src, (int(w[0]), int(w[1])))
        assert (reach[i] == oracle).all(), f"window {i} disagrees with oracle"


def test_warm_start_refused_for_pagerank():
    """Pagerank warm starts would change the finite-iteration output — the
    request is refused and the result still matches the cold sweep."""
    g, idx, src, wins0, wins1 = _widening_case()
    kw = dict(n_iters=12)
    _, state = sweep_incremental(g, src, wins0, idx, algorithm="pagerank",
                                 access="index", warm_start=True, **kw)
    res, state = sweep_incremental(g, src, wins1, idx, algorithm="pagerank",
                                   state=state, access="index",
                                   warm_start=True, **kw)
    assert not state.warm_applied
    cold = sweep(g, src, wins1, idx, algorithm="pagerank", plan=state.plan,
                 **kw)
    np.testing.assert_allclose(np.asarray(res), np.asarray(cold),
                               rtol=1e-5, atol=1e-7)


def test_warm_start_refused_under_visit_once():
    """visit_once EA marks warm finite-label vertices visited, blocking
    re-expansion — the unsound case: refused, and still bit-identical to
    the cold visit_once sweep."""
    g, idx, src, wins0, wins1 = _widening_case()
    kw = dict(visit_once=True)
    _, state = sweep_incremental(g, src, wins0, idx, access="index",
                                 warm_start=True, **kw)
    res, state = sweep_incremental(g, src, wins1, idx, state=state,
                                   access="index", warm_start=True, **kw)
    assert not state.warm_applied
    cold = sweep(g, src, wins1, idx, plan=state.plan, **kw)
    assert (np.asarray(res) == np.asarray(cold)).all()


# ---------------------------------------------------------------------------
# touched-driven convergence metric (FixpointRunner export)
# ---------------------------------------------------------------------------

def _ea_oracle(g, source, window):
    """Host-side reference loop mirroring the runner's round structure:
    returns (rounds, touched_total) where a round's touched set is the
    vertices receiving >= 1 valid contribution, and the loop runs until a
    round improves nothing (that final round is counted, matching the
    while-loop's body-execution count)."""
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    ts, te = np.asarray(g.t_start), np.asarray(g.t_end)
    win = (ts >= window[0]) & (te <= window[1])
    INT_INF = np.iinfo(np.int32).max
    arrival = np.full(g.n_vertices, INT_INF, np.int64)
    arrival[source] = window[0]
    frontier = np.zeros(g.n_vertices, bool)
    frontier[source] = True
    rounds = touched_total = 0
    while frontier.any():
        ok = win & frontier[src] & (arrival[src] <= ts)
        touched_total += np.unique(dst[ok]).size
        new_arrival = arrival.copy()
        np.minimum.at(new_arrival, dst[ok], te[ok])
        frontier = new_arrival < arrival
        arrival = new_arrival
        rounds += 1
        if rounds > g.n_vertices + 1:
            raise AssertionError("oracle failed to converge")
    return rounds, touched_total


@pytest.mark.parametrize("seed", [0, 7, 19])
def test_fixpoint_metrics_match_oracle(seed):
    from repro.core.algorithms import earliest_arrival

    rng = np.random.default_rng(seed)
    n_v, n_e = 35, 300
    g = from_edges(
        rng.integers(0, n_v, n_e), rng.integers(0, n_v, n_e),
        rng.integers(0, 200, n_e), None, n_vertices=n_v,
        rng=np.random.default_rng(seed),
    )
    win = (20, 180)
    source = int(rng.integers(0, n_v))
    arr, metrics = earliest_arrival(
        g, source, win, plan=make_plan("scan"), with_metrics=True)
    rounds_o, touched_o = _ea_oracle(g, source, win)
    assert int(metrics.rounds) == rounds_o
    assert int(metrics.touched_total) == touched_o


def test_sweep_incremental_reports_rounds():
    """The fused EA step exports the runner's round count into the state
    (a tuple of lazy device scalars, one per group: no per-advance host
    sync)."""
    g, idx, src, t_min, t_max = _serving_case()
    span = t_max - t_min
    width, stride = max(span // 40, 4), max(span // 80, 1)
    wins = sliding_windows(t_max - stride, width=width, stride=stride, count=3)
    _, state = sweep_incremental(g, src, wins, idx, access="index")
    wins = sliding_windows(t_max, width=width, stride=stride, count=3)
    _, state = sweep_incremental(g, src, wins, idx, state=state,
                                 access="index")
    assert state.last_advance == "delta"
    (rounds,) = state.last_rounds          # one tuple entry per group
    assert int(rounds) >= 1
