"""Serving-daemon tests (DESIGN.md §7.6): bucketed admission on
``serve_batch``, the ``GraphBatchServer`` submit/retire/tick loop under
Poisson tenant churn, the re-entrant dispatch log, and the
exception/invalidate donation contract.

Four layers:

1. **Bucketed serve_batch** — padded result buffers (slice to the real
   row count), bit-identity vs plain serves, zero fused-step retraces for
   within-bucket admission/retirement, exactly one rebucket + one retrace
   on a bucket transition, the admission-toggle state gate (falls cold
   WITHOUT consuming the mismatched state), bucketed×mesh composition
   (DESIGN.md §7.7), the unsupported-combination errors (which fire
   BEFORE the carried state can be consumed), and the daemon's
   arrival-rate EWMA bucket headroom (a forecasted burst admits with
   zero rebuckets).
2. **dispatch_log re-entrancy** — nested scopes stack (both logs observe
   the inner extent's tags) and the legacy ``ws._DISPATCH_LOG`` module
   global still receives tags without double-counting.
3. **The churn soak** (the PR's acceptance property) — ``DAEMON_SOAK``
   ticks of a live daemon under seeded Poisson arrivals/departures across
   all five cost-classed algorithms: per-tenant results bit-identical
   (floats allclose) to cold ``serve_batch`` serves of the instantaneous
   specs at EVERY tick, ZERO fused retraces and ZERO cold advances on
   ticks whose churn stays inside the admission buckets (after warmup),
   and GraphServeStats accounting that adds up exactly.
4. **Invalidate-on-exception** — an advance that raises mid-flight
   force-colds the carried state (batch mode AND the daemon's per-class
   chains); the retry succeeds cold instead of crashing on donated
   buffers.

``DAEMON_SOAK`` defaults to 80 ticks and drops to 24 under CI (the ``CI``
env var; ``scripts/ci.sh`` exports it) to bound tier-1 wall clock.
"""
import os

import numpy as np
import pytest

from repro import telemetry
from repro.core.tger import build_tger
from repro.data.generators import power_law_temporal_graph
from repro.engine import (
    DEFAULT_COST_CLASS,
    QueryBatch,
    QuerySpec,
    bucket_capacity,
    plan_batch,
)
from repro.serve import GraphBatchServer, serve_batch
from repro.serve import window_sweep as ws

DAEMON_SOAK = int(os.environ.get(
    "DAEMON_SOAK", "24" if os.environ.get("CI") else "80"))

_CASE = {}


def _case():
    if not _CASE:
        g = power_law_temporal_graph(200, 5000, seed=8)
        idx = build_tger(g, degree_cutoff=48)
        ts = np.asarray(g.t_start)
        _CASE["v"] = (
            g, idx, int(ts.min()), int(np.asarray(g.t_end).max()),
        )
    return _CASE["v"]


_ALGS = ("earliest_arrival", "reachability", "bfs", "cc", "pagerank")
_FLOAT_ALGS = ("pagerank", "betweenness")


def _spec(alg, i, window):
    if alg == "cc":
        return QuerySpec.make(alg, window)
    if alg == "pagerank":
        return QuerySpec.make(alg, window, n_iters=6)
    return QuerySpec.make(alg, window, sources=(7 * i + 1) % 200)


def _assert_rows_match(got, want, alg, ctx):
    """got/want: one group's result (array or tuple), same row count."""
    got = got if isinstance(got, tuple) else (got,)
    want = want if isinstance(want, tuple) else (want,)
    assert len(got) == len(want), ctx
    for oi, (a, b) in enumerate(zip(got, want)):
        a, b = np.asarray(a), np.asarray(b)
        if alg in _FLOAT_ALGS:
            np.testing.assert_allclose(
                a, b, rtol=1e-5, atol=1e-7, err_msg=f"{ctx} output {oi}")
        else:
            assert (a == b).all(), f"{ctx} output {oi} diverged"


# ---------------------------------------------------------------------------
# 1. bucketed serve_batch
# ---------------------------------------------------------------------------

def _ea_batch(b, width, n):
    return QueryBatch.make([
        QuerySpec.make("earliest_arrival", (b - width, b), sources=1 + 3 * i)
        for i in range(n)
    ])


def test_bucketed_results_are_padded_to_the_bucket_capacity():
    g, idx, t_min, t_max = _case()
    span = t_max - t_min
    b, width = t_min + span // 2, span // 8
    batch = _ea_batch(b, width, 3)
    res_b, state = serve_batch(g, batch, idx, access="index",
                               admission="bucketed")
    assert state.group_caps == (bucket_capacity(3),) == (4,)
    assert res_b[0].shape[0] == 4          # padded buffer: slice to 3 rows
    res_p, _ = serve_batch(g, batch, idx, access="index", plan=state.plan)
    _assert_rows_match(res_b[0][:3], res_p[0], "earliest_arrival", "bucketed-cold")


def test_bucketed_composes_with_mesh_and_rejects_bad_combos():
    """Since DESIGN.md §7.7 bucketed admission COMPOSES with the query
    mesh (it used to be mutually exclusive); the still-unsupported
    combinations raise a ValueError that lists the supported ones."""
    g, idx, t_min, t_max = _case()
    batch = _ea_batch(t_max, (t_max - t_min) // 8, 1)
    # bucketed × mesh now serves (D=1 drives the full sharded path)
    res, st = serve_batch(g, batch, idx, access="index",
                          admission="bucketed", mesh=1)
    assert st.mesh is not None and st.group_caps
    with pytest.raises(ValueError, match="warm_start"):
        serve_batch(g, batch, idx, admission="bucketed", warm_start=True)
    with pytest.raises(ValueError, match="supported serve_batch"):
        serve_batch(g, batch, idx, admission="sorted")
    with pytest.raises(ValueError, match="admission"):
        serve_batch(g, batch, idx, admission="sorted")


def test_unsupported_combo_error_path_does_not_consume_state():
    """The donation contract on the ERROR path: an unsupported-combination
    ValueError fires before the fused step can consume the carried state,
    so the same state object serves fine immediately afterwards."""
    g, idx, t_min, t_max = _case()
    span = t_max - t_min
    width, stride = max(span // 20, 4), max(span // 160, 1)
    mk = lambda k: _ea_batch(t_max - (4 - k) * stride, width, 2)
    _, state = serve_batch(g, mk(0), idx, access="index")
    for kw in (
        dict(admission="rate-limited"),
        dict(admission="bucketed", warm_start=True),
        dict(mesh=(2, 2), access="scan"),
        dict(mesh=(2, 2), tger_none=True),
    ):
        tger = None if kw.pop("tger_none", False) else idx
        with pytest.raises(ValueError):
            serve_batch(g, mk(1), tger, state=state, **kw)
    # the carried state is untouched: the next good serve delta-advances
    res, s2 = serve_batch(g, mk(1), idx, state=state, access="index")
    assert s2.last_advance in ("delta", "noop")


def test_within_bucket_admission_is_a_cache_hit():
    """Admitting/retiring rows INSIDE a bucket across slid advances never
    retraces the fused step and never falls cold — the §7.6 claim — and
    every advance stays row-identical to a plain serve."""
    g, idx, t_min, t_max = _case()
    span = t_max - t_min
    width = max(span // 20, 4)
    stride = max(width // 8, 1)
    base = t_min + span // 2
    # pin the plan over the whole slid horizon so ring coverage never
    # lapses mid-chain (a replan would be a cold advance, not admission)
    horizon = QueryBatch.make([QuerySpec.make(
        "earliest_arrival",
        (base - 2 * width, base + 16 * stride), sources=1)])
    pin = plan_batch(g, idx, horizon, access="index")

    state = None
    # row counts 3,4,3,4,3: all inside the 4-bucket (hysteresis holds the
    # shrink); the first two advances warm the has-new/noop variants
    counts = (3, 4, 3, 4, 3, 4)
    t0 = None
    for k, n in enumerate(counts):
        batch = _ea_batch(base + k * stride, width, n)
        results, state = serve_batch(
            g, batch, idx, state=state, access="index", plan=pin,
            admission="bucketed")
        assert state.group_caps == (4,)
        ref, _ = serve_batch(g, batch, idx, access="index", plan=pin)
        _assert_rows_match(results[0][:n], ref[0], "earliest_arrival",
                           f"adv {k} (n={n})")
        if k == 2:
            t0 = ws.fused_trace_count()
        if k > 2:
            assert state.last_advance == "delta", (k, state.last_advance)
            assert ws.fused_trace_count() == t0, (
                f"within-bucket admission retraced at advance {k}")


def test_bucket_transition_rebuckets_once_then_pins():
    """Growing past the bucket edge costs exactly one host rebucket gather
    + one retrace; the next within-bucket advance is a cache hit again."""
    g, idx, t_min, t_max = _case()
    span = t_max - t_min
    width = max(span // 20, 4)
    stride = max(width // 8, 1)
    base = t_min + span // 2
    horizon = QueryBatch.make([QuerySpec.make(
        "earliest_arrival",
        (base - 2 * width, base + 16 * stride), sources=1)])
    pin = plan_batch(g, idx, horizon, access="index")

    state = None
    for k, n in enumerate((4, 4)):         # warm the cap-4 variants
        _, state = serve_batch(
            g, _ea_batch(base + k * stride, width, n), idx, state=state,
            access="index", plan=pin, admission="bucketed")
    t0 = ws.fused_trace_count()
    with ws.dispatch_log() as log:
        batch = _ea_batch(base + 2 * stride, width, 5)   # 4-bucket -> 8
        results, state = serve_batch(
            g, batch, idx, state=state, access="index", plan=pin,
            admission="bucketed")
    assert state.group_caps == (8,)
    assert log.count("rebucket") == 1, log
    assert ws.fused_trace_count() == t0 + 1
    ref, _ = serve_batch(g, batch, idx, access="index", plan=pin)
    _assert_rows_match(results[0][:5], ref[0], "earliest_arrival", "grow 4->8")
    # back inside the 8-bucket: cache hit, no rebucket
    t1 = ws.fused_trace_count()
    with ws.dispatch_log() as log:
        batch = _ea_batch(base + 3 * stride, width, 6)
        results, state = serve_batch(
            g, batch, idx, state=state, access="index", plan=pin,
            admission="bucketed")
    assert state.group_caps == (8,) and "rebucket" not in log
    assert ws.fused_trace_count() == t1
    ref, _ = serve_batch(g, batch, idx, access="index", plan=pin)
    _assert_rows_match(results[0][:6], ref[0], "earliest_arrival", "within 8")


def test_admission_toggle_falls_cold_without_consuming():
    """A bucketed state offered to a plain serve (and vice versa) is
    refused — the serve falls cold and the carried state is NOT consumed,
    so it still advances on its own side of the toggle."""
    g, idx, t_min, t_max = _case()
    span = t_max - t_min
    b, width = t_min + span // 2, span // 8
    batch = _ea_batch(b, width, 3)
    _, st_b = serve_batch(g, batch, idx, access="index", admission="bucketed")
    _, st_p = serve_batch(g, batch, idx, access="index")
    # plain serve refuses the bucketed state...
    _, s2 = serve_batch(g, batch, idx, state=st_b, access="index")
    assert s2.last_advance == "cold" and not s2.group_caps
    # ...and bucketed refuses the plain state...
    _, s3 = serve_batch(g, batch, idx, state=st_p, access="index",
                        admission="bucketed")
    assert s3.last_advance == "cold" and s3.group_caps
    # ...neither original state was consumed: both still serve
    _, s4 = serve_batch(g, batch, idx, state=st_b, access="index",
                        admission="bucketed")
    assert s4.last_advance == "noop"
    _, s5 = serve_batch(g, batch, idx, state=st_p, access="index")
    assert s5.last_advance == "noop"


def test_sticky_group_order_returns_results_in_batch_order():
    """Resident groups keep the carried schedule's position (no retrace
    under group-order churn), but results come back in THIS batch's group
    order."""
    g, idx, t_min, t_max = _case()
    span = t_max - t_min
    width = max(span // 20, 4)
    stride = max(width // 8, 1)
    base = t_min + span // 2

    def mk(b, cc_first):
        ea = QuerySpec.make("earliest_arrival", (b - width, b), sources=1)
        cc = QuerySpec.make("cc", (b - width, b))
        return QueryBatch.make([cc, ea] if cc_first else [ea, cc])

    _, state = serve_batch(g, mk(base, False), idx, access="index",
                           admission="bucketed")
    assert [k[0] for k in state.group_keys] == ["earliest_arrival", "cc"]
    b2 = base + stride
    results, state = serve_batch(g, mk(b2, True), idx, state=state,
                                 access="index", admission="bucketed")
    # schedule order stayed sticky; results follow the NEW batch order
    assert [k[0] for k in state.group_keys] == ["earliest_arrival", "cc"]
    ref, _ = serve_batch(g, mk(b2, True), idx, access="index",
                         plan=state.plan)
    _assert_rows_match(results[0][:1], ref[0], "cc", "sticky cc group")
    _assert_rows_match(results[1][:1], ref[1], "earliest_arrival",
                       "sticky ea group")


# ---------------------------------------------------------------------------
# 2. dispatch_log re-entrancy
# ---------------------------------------------------------------------------

def test_dispatch_log_nested_scopes_both_observe():
    with ws.dispatch_log() as outer:
        ws._note("a")
        with ws.dispatch_log() as inner:
            ws._note("b")
        ws._note("c")
    assert outer == ["a", "b", "c"]
    assert inner == ["b"]
    ws._note("after")                       # no active scope: a no-op
    assert outer == ["a", "b", "c"]


def test_dispatch_log_legacy_global_still_receives():
    ws._DISPATCH_LOG = legacy = []
    try:
        with ws.dispatch_log() as log:
            ws._note("x")
        ws._note("y")
    finally:
        ws._DISPATCH_LOG = None
    assert log == ["x"] and legacy == ["x", "y"]
    # and no double-append when the global IS an active scope's list
    ws._DISPATCH_LOG = shared = []
    try:
        token = ws._DISPATCH_LOG_VAR.set(
            ws._DISPATCH_LOG_VAR.get() + (shared,))
        try:
            ws._note("z")
        finally:
            ws._DISPATCH_LOG_VAR.reset(token)
    finally:
        ws._DISPATCH_LOG = None
    assert shared == ["z"]


# ---------------------------------------------------------------------------
# 3. the churn soak (acceptance property)
# ---------------------------------------------------------------------------

def test_daemon_churn_soak():
    """DAEMON_SOAK ticks of live submit/retire/tick churn: bit-identity vs
    cold serves every tick, zero retraces and zero cold advances on ticks
    whose churn stays inside the admission buckets (after warmup), and
    stats that add up.

    The tick clock LAPS (t_now wraps every ``lap`` ticks, the multi-tenant
    soak's short-lap idiom): the first lap visits the whole position range
    so every delta-rung variant warms before the zero-retrace assertions
    bite, and the wrap tick's backward slide is the known cold trigger
    (excluded from the accounting, like the mt soak's wrap cold)."""
    g, idx, t_min, t_max = _case()
    span = t_max - t_min
    width = max(span // 20, 4)
    stride = max(width // 8, 1)
    lap = max(DAEMON_SOAK // 3, 8)
    base = t_max - (lap + 2) * stride
    # pin the union plan over the whole tick horizon: ring coverage never
    # lapses, so any cold advance the soak sees IS a bucket/schedule event
    horizon = QueryBatch.make([QuerySpec.make(
        "earliest_arrival",
        (base - 2 * width, base + (lap + 2) * stride), sources=1)])
    pin = plan_batch(g, idx, horizon, access="index")

    server = GraphBatchServer(g, idx, access="index", plan=pin)
    rng = np.random.default_rng(11)
    live, n_spawned = [], 0

    def fresh():
        nonlocal n_spawned
        s = _spec(_ALGS[n_spawned % len(_ALGS)], n_spawned, (0, width))
        n_spawned += 1
        return s

    # 5 tenants per algorithm: every group starts mid-bucket (cap 8, real
    # rows 5), so balanced Poisson churn mostly stays INSIDE the buckets —
    # the steady state the zero-retrace assertions are about
    for _ in range(25):
        live.append(server.submit(fresh()))

    expected_advances = 0
    caps_sig = None
    last_sig_change = 0
    stable_ticks = 0
    for k in range(DAEMON_SOAK):
        if k:                                # Poisson churn (queued async,
            for _ in range(rng.poisson(0.5)):     # applied by this tick)
                live.append(server.submit(fresh()))
            for _ in range(rng.poisson(0.5)):
                if len(live) > 2:
                    server.retire(live.pop(int(rng.integers(len(live)))))
        t_now = base + (k % lap) * stride
        traces0 = ws.fused_trace_count()
        cold0 = server.stats.cold_advances
        rep = server.tick(t_now)
        assert rep.tick == k + 1 and rep.t_now == t_now
        expected_advances += len(rep.classes_served)
        # the class-split contract: the cheap class serves every tick it
        # has tenants; exactly one deep class serves when any are live
        classes_live = {s.resolved_cost_class
                        for s in server.tenants.values()}
        if DEFAULT_COST_CLASS in classes_live:
            assert DEFAULT_COST_CLASS in rep.classes_served, rep
        deep_served = [c for c in rep.classes_served
                       if c != DEFAULT_COST_CLASS]
        assert len(deep_served) == (
            1 if classes_live - {DEFAULT_COST_CLASS} else 0), rep
        # -- bit-identity: every served tenant vs a cold serve of its
        # instantaneous spec under the same plan
        for tid, got in rep.results.items():
            spec = server.tenants[tid]
            w = int(spec.window[1]) - int(spec.window[0])
            inst = QuerySpec.make(
                spec.algorithm, (t_now - w, t_now),
                sources=spec.sources or None,
                **dict(spec.params))
            ref, _ = serve_batch(g, QueryBatch.make([inst]), idx,
                                 access="index", plan=pin)
            _assert_rows_match(got, ref[0], spec.algorithm,
                               f"tick {k} tenant {tid} ({spec.algorithm})")
        # -- retrace accounting keyed on the per-class bucket structure
        # (group schedule + capacities): once the structure has been
        # stable for a FULL LAP (every (schedule, delta-rung) variant of
        # this structure warmed on the previous lap) and the tick is not
        # the wrap's backward slide, the churn is pure within-bucket
        # admission/retirement -> zero retraces, zero cold advances
        sig = tuple(sorted(
            (cls, st.group_keys, st.group_caps)
            for cls, st in server._class_states.items()))
        if sig != caps_sig:
            last_sig_change = k
        if k - last_sig_change > lap and k % lap != 0:
            stable_ticks += 1
            assert ws.fused_trace_count() == traces0, (
                f"tick {k}: within-bucket churn retraced the fused step")
            assert server.stats.cold_advances == cold0, (
                f"tick {k}: within-bucket churn fell cold")
        caps_sig = sig

    # the soak must actually exercise the steady state it asserts on
    assert stable_ticks >= DAEMON_SOAK // 8, (
        f"only {stable_ticks} stable ticks — churn thrashed every bucket")
    s = server.stats
    assert s.ticks == DAEMON_SOAK
    assert s.advances == expected_advances
    assert s.admissions == n_spawned
    assert s.retirements == n_spawned - len(live)
    assert len(server.tenants) == len(live)
    class_serves = [sp for r in telemetry.recent(s.ticks)
                    for sp in r.named("serve.class")]
    assert len(class_serves) == s.advances
    assert s.dispatches >= s.advances        # >= one dispatch-site per serve
    assert s.fused_dispatches + s.cold_advances <= s.dispatches


def test_tick_round_robins_multiple_deep_classes():
    """Two deep classes (pagerank + an explicit cost_class override)
    alternate one per tick while the cheap class serves every tick; a
    skipped class's tenants keep their previous answer (absent from the
    tick's results)."""
    g, idx, t_min, t_max = _case()
    span = t_max - t_min
    width = max(span // 10, 4)
    server = GraphBatchServer(g, idx, access="index")
    t_cheap = server.submit(QuerySpec.make("cc", (0, width)))
    t_pr = server.submit(QuerySpec.make("pagerank", (0, width), n_iters=4))
    t_slow = server.submit(QuerySpec.make(
        "bfs", (0, width), sources=3, cost_class="slow-bfs"))
    base = t_min + span // 2
    seen = []
    for k in range(4):
        rep = server.tick(base + k)
        assert DEFAULT_COST_CLASS in rep.classes_served
        assert t_cheap in rep.results
        deep = [c for c in rep.classes_served if c != DEFAULT_COST_CLASS]
        assert len(deep) == 1
        seen.append(deep[0])
        if deep[0] == "deep":
            assert t_pr in rep.results and t_slow not in rep.results
        else:
            assert t_slow in rep.results and t_pr not in rep.results
    assert set(seen) == {"deep", "slow-bfs"} and seen[:2] * 2 == seen


def test_rr_survives_deep_class_retirement_mid_rotation():
    """The round-robin churn bugfix: retiring every tenant of a deep class
    mid-rotation must not skip or double-serve a surviving class (the old
    bare counter indexed into the SHRUNK class list and replayed a lap).
    Classes a, b, c: after serving a then b, retiring a means the next
    deep serve is c — then the rotation wraps fairly over the survivors."""
    g, idx, t_min, t_max = _case()
    span = t_max - t_min
    width = max(span // 10, 4)
    base = t_min + span // 2
    server = GraphBatchServer(g, idx, access="index")
    tids = {c: server.submit(QuerySpec.make(
        "bfs", (0, width), sources=1, cost_class=c)) for c in "abc"}
    served = []
    for k in range(2):
        served += list(server.tick(base + k).classes_served)
    assert served == ["a", "b"]
    server.retire(tids["a"])
    rep = server.tick(base + 2)
    assert list(rep.classes_served) == ["c"], (
        f"retired-class rotation double-served {rep.classes_served}")
    assert list(server.tick(base + 3).classes_served) == ["b"]
    assert list(server.tick(base + 4).classes_served) == ["c"]


def test_admission_forecast_clears_when_class_empties():
    """The stale-forecast bugfix: ``_admit_ewma``/``_admit_hr`` entries
    must not survive a class's last retirement — a tenant re-admitted
    after a quiet gap starts from baseline headroom instead of inheriting
    the old burst's inflated sticky forecast (which would oversize its
    first bucket)."""
    g, idx, t_min, t_max = _case()
    span = t_max - t_min
    width = max(span // 20, 4)
    base = t_min + span // 2
    server = GraphBatchServer(g, idx, access="index")
    burst = [server.submit(_spec("earliest_arrival", i, (0, width)))
             for i in range(6)]
    server.tick(base)
    assert server.bucket_headroom(DEFAULT_COST_CLASS) >= 6
    for t in burst:
        server.retire(t)
    server.tick(base + 1)                       # the class empties HERE
    assert server.bucket_headroom(DEFAULT_COST_CLASS) == 0
    assert DEFAULT_COST_CLASS not in server._admit_ewma
    # quiet gap, then one tenant re-admits: baseline headroom, not the
    # burst-era forecast
    server.tick(base + 2)
    server.submit(_spec("earliest_arrival", 0, (0, width)))
    server.tick(base + 3)
    assert server.bucket_headroom(DEFAULT_COST_CLASS) <= 2


def test_arrival_rate_headroom_absorbs_forecasted_bursts():
    """DESIGN.md §7.7 arrival-rate bucket sizing: a SURPRISE burst of B
    tenants lands with at most ONE rebucket (admission is batched at the
    tick boundary, so all B land in a single bucket transition), and once
    the per-class admission EWMA has learned the burst rate the bucket
    already carries headroom for the next one — sustained same-size
    bursts admit with ZERO rebuckets."""
    g, idx, t_min, t_max = _case()
    span = t_max - t_min
    width = max(span // 20, 4)
    stride = max(width // 8, 1)
    base = t_min + span // 2
    server = GraphBatchServer(g, idx, access="index")
    for i in range(2):
        server.submit(_spec("earliest_arrival", i, (0, width)))
    tick = 0

    def run_tick():
        nonlocal tick
        with ws.dispatch_log() as log:
            server.tick(base + tick * stride)
        tick += 1
        return log

    for _ in range(5):                      # settle the base load
        run_tick()
    assert server.bucket_headroom(DEFAULT_COST_CLASS) <= 2

    # surprise burst: 6 tenants queued async, admitted by ONE tick
    burst = [server.submit(_spec("earliest_arrival", 10 + i, (0, width)))
             for i in range(6)]
    log = run_tick()
    assert log.count("rebucket") <= 1, log
    assert server.bucket_headroom(DEFAULT_COST_CLASS) >= 6, (
        "the EWMA forecast should now cover a whole burst")

    # sustained churn at the burst rate: the EWMA converges, the bucket
    # (sized real rows + forecast headroom) stops moving, and bursts
    # become pure within-bucket admission
    rebuckets = []
    for k in range(7):
        for tid in burst:
            server.retire(tid)
        burst = [server.submit(
            _spec("earliest_arrival", 20 + 10 * k + i, (0, width)))
            for i in range(6)]
        rebuckets.append(run_tick().count("rebucket"))
    assert sum(rebuckets[:3]) <= 1, rebuckets   # one growth while learning
    assert rebuckets[3:] == [0] * 4, rebuckets  # forecasted: zero rebuckets
    assert server.bucket_headroom(DEFAULT_COST_CLASS) >= 6


def test_retired_tenant_leaves_the_batch():
    g, idx, t_min, t_max = _case()
    span = t_max - t_min
    width = max(span // 10, 4)
    server = GraphBatchServer(g, idx, access="index")
    t1 = server.submit(QuerySpec.make("cc", (0, width)))
    t2 = server.submit(QuerySpec.make(
        "earliest_arrival", (0, width), sources=1))
    base = t_min + span // 2
    rep = server.tick(base)
    assert set(rep.results) == {t1, t2} and set(rep.admitted) == {t1, t2}
    server.retire(t2)
    server.retire(999)                       # unknown id: ignored
    rep = server.tick(base + 1)
    assert rep.retired == (t2,)
    assert set(rep.results) == {t1}
    assert set(server.tenants) == {t1}
    assert server.stats.retirements == 1


# ---------------------------------------------------------------------------
# 4. invalidate-on-exception (the donation-contract bugfix)
# ---------------------------------------------------------------------------

def test_advance_invalidates_state_when_serve_raises(monkeypatch):
    """If serve_batch raises mid-advance the carried state may already be
    moved-from — advance() must force-cold it so the RETRY works instead
    of crashing on donated buffers (the regression this PR fixes)."""
    g, idx, t_min, t_max = _case()
    span = t_max - t_min
    b, width = t_min + span // 2, span // 8
    batch = _ea_batch(b, width, 2)
    server = GraphBatchServer(g, idx, access="index")
    server.advance(batch)
    assert server.state is not None

    real = ws.serve_batch

    def consuming_boom(g_, batch_, tger_, **kw):
        real(g_, batch_, tger_, **kw)        # consumes the donated state
        raise RuntimeError("post-consumption failure")

    monkeypatch.setattr(ws, "serve_batch", consuming_boom)
    with pytest.raises(RuntimeError, match="post-consumption"):
        server.advance(batch)
    assert server.state is None              # invalidated, not stale
    monkeypatch.undo()

    results = server.advance(batch)          # retry: clean cold serve
    assert server.state.last_advance == "cold"
    ref, _ = serve_batch(g, batch, idx, access="index",
                         plan=server.state.plan)
    _assert_rows_match(results[0], ref[0], "earliest_arrival", "retry")


def test_tick_invalidates_class_state_when_serve_raises(monkeypatch):
    """The daemon analogue: a class serve that raises drops that class's
    chain; the next tick runs that class cold and keeps serving."""
    g, idx, t_min, t_max = _case()
    span = t_max - t_min
    width = max(span // 10, 4)
    server = GraphBatchServer(g, idx, access="index")
    server.submit(QuerySpec.make("cc", (0, width)))
    base = t_min + span // 2
    server.tick(base)
    assert "cheap" in server._class_states

    real = ws.serve_batch

    def consuming_boom(g_, batch_, tger_, **kw):
        real(g_, batch_, tger_, **kw)
        raise RuntimeError("tick failure")

    monkeypatch.setattr(ws, "serve_batch", consuming_boom)
    with pytest.raises(RuntimeError, match="tick failure"):
        server.tick(base + 1)
    assert "cheap" not in server._class_states
    monkeypatch.undo()

    rep = server.tick(base + 2)              # recovers cold
    assert rep.results
    assert server._class_states["cheap"].last_advance == "cold"
