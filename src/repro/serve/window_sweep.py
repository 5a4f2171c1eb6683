"""Multi-tenant window-query serving: one plan, one ring advance, ONE fused
dispatch — a whole batch of (algorithm × source × window) queries.

The serving workload Kairos's selective indexing exists for is *temporal
window queries* — "earliest arrival over each of the last 24 sliding
windows", "reachability per day this month", and (since the multi-tenant
refactor, DESIGN.md §7.4) MANY tenants' worth of those at once.  The unit
of work is a :class:`~repro.engine.queries.QueryBatch`: a set of
``QuerySpec(algorithm, sources, window, params)`` entries, expanded into
(algorithm × source × window) rows and bucketed into per-``(algorithm,
params)`` groups, each of which solves as one batched ``*_over_view``
fixpoint with the source axis vmapped alongside the window axis.

  * ``sweep`` / ``sweep_looped`` — the cold batched path (DESIGN.md §6)
    and its W-independent-runs reference, now dispatch-table-driven over
    all seven algorithm modules.
  * ``serve_batch`` — the multi-tenant entry point: answer a whole
    QueryBatch over ONE union plan (`engine.plan_batch`; the batch shape
    signature rides the cache key) and carry a :class:`SweepState` so the
    next batch advances incrementally.
  * ``sweep_incremental`` — the single-tenant wrapper (one algorithm, one
    source, W sliding windows) over the same engine; its legacy
    state-compatibility gate (same algorithm/source/kwargs or fall cold)
    is preserved.

The steady-state advance is ONE jitted dispatch for the WHOLE batch
(DESIGN.md §7.3–§7.4): ring delta scatter + every group's solve of only
its genuinely-new rows + per-group [Q, V] row assembly run in the same
program, with the ring-view and result buffers DONATED (SweepState is
single-use / moved-from).  Row reuse is per (algorithm, params, source,
window) row; identical rows across tenants DEDUP to one solved row and
fan out at assembly; warm starts sit behind the explicit ``warm_start=``
flag with per-algorithm soundness (EA and cc exact, reachability sound,
bfs/pagerank/kcore/betweenness refused — DESIGN.md §7.4 soundness table).

``serve_batch(..., mesh=D)`` SHARDS the batch's row axis across a query
mesh (DESIGN.md §7.5): ring view and carried results replicated per
device, each device solving only its contiguous (padded) row chunk under
its own convergence loop inside the same fused SPMD program — one
dispatch per device per advance, rows still bit-identical to the
single-device engine.

``serve_batch(..., mesh=(E, D))`` composes that with EDGE sharding
(DESIGN.md §7.7): the ring view itself partitions into contiguous slot
chunks over the mesh's edge axis (the delta scatter lands only on the
owning shard), every group's solve runs one ``shard_map`` over
``(edges, queries)`` with each per-round edge-wide reduction finished by
ONE collective across the edge axis, and per-device convergence stays
LOCAL on the query axis.  Integer-label rows remain bit-identical to the
unsharded engine; float rows (pagerank, betweenness) cross a psum at
E > 1 and compare allclose.  Bucketed admission composes with any mesh
shape via bucket-aligned row partitions.

Integer-label results are row-identical (bit-exact) to the cold ``sweep``
under the same plan; float rows (pagerank, betweenness) match up to float
reduction order (sums cross edge-view layouts — compare allclose, as
everywhere floats cross views).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import warnings
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.algorithms import (
    earliest_arrival,
    earliest_arrival_batched,
    earliest_arrival_over_view,
    overlaps_reachability,
    overlaps_reachability_batched,
    overlaps_reachability_over_view,
    temporal_bfs,
    temporal_bfs_batched,
    temporal_bfs_over_view,
    temporal_betweenness,
    temporal_betweenness_batched,
    temporal_betweenness_over_view,
    temporal_cc,
    temporal_cc_batched,
    temporal_cc_over_view,
    temporal_kcore,
    temporal_kcore_batched,
    temporal_kcore_over_view,
    temporal_pagerank,
    temporal_pagerank_batched,
    temporal_pagerank_over_view,
)
from repro.core.edgemap import (
    INT_INF,
    EdgeView,
    advance_hybrid_ring_fields,
    advance_index_ring_fields,
    ring_view_for_plan,
)
from repro.core.temporal_graph import TemporalGraph
from repro.core.tger import (
    TGERIndex,
    heavy_window_positions_host,
    window_positions_host,
)
from repro.engine.plan import (
    AccessPlan,
    per_vertex_window_budget,
    plan_batch,
    plan_query,
    rung,
)
from repro import telemetry
from repro.distributed.compat import shard_map as _compat_shard_map
from repro.distributed.query_shard import (
    query_mesh,
    replicate,
    replicated_arrays,
    row_partition,
    serve_mesh,
)
from repro.engine.queries import (
    QueryBatch,
    QuerySpec,
    bucket_capacity,
    dedup_rows,
)

# the calibrated crossover of BENCH_fixpoint.json part 2 row 1: at ring
# capacities at or below this rung the fused incremental advance LOSES to a
# cold re-solve (0.69x at b64/W=8 — the per-advance fixed costs dwarf the
# delta scatter's savings), so ``sweep_incremental(tiny_budget_gate=True)``
# routes such chains cold.  Opt-in: the default keeps the fused one-dispatch
# contract for tests and daemons that assert on it.
TINY_BUDGET_RING = 64


# ---------------------------------------------------------------------------
# the algorithm dispatch table (DESIGN.md §7.4)
# ---------------------------------------------------------------------------

class _Algo(NamedTuple):
    """One algorithm's serving contract.

    ``solve(edges, windows, sources, plan, n_vertices, init, kwargs)`` runs
    the group's rows over a prebuilt (ring) view and returns ``(result,
    rounds)`` — ``rounds`` is the runner's convergence metric for EA and -1
    for the vmapped/fixed-iteration algorithms.  ``warm`` builds a
    containment warm init for new rows (None = warm starts REFUSED — the
    per-algorithm soundness table of DESIGN.md §7.4).  ``n_outputs`` is the
    result-tuple arity (1 = bare [Q, V] array)."""

    solve: Callable
    batched: Callable               # cold batched entry (sweep)
    single: Callable                # cold single-window entry (sweep_looped)
    n_outputs: int
    source_free: bool
    warm: Optional[Callable]


def _solve_ea(edges, windows, sources, plan, n_vertices, init, kwargs):
    return earliest_arrival_over_view(
        edges, windows, sources=sources, plan=plan, n_vertices=n_vertices,
        init=init, with_rounds=True, **kwargs)


def _solve_reach(edges, windows, sources, plan, n_vertices, init, kwargs):
    res = overlaps_reachability_over_view(
        edges, windows, sources=sources, plan=plan, n_vertices=n_vertices,
        init=init, **kwargs)
    return res, jnp.int32(-1)


def _solve_pagerank(edges, windows, sources, plan, n_vertices, init, kwargs):
    res = temporal_pagerank_over_view(
        edges, windows, plan=plan, n_vertices=n_vertices, init=init, **kwargs)
    return res, jnp.int32(-1)


def _solve_bfs(edges, windows, sources, plan, n_vertices, init, kwargs):
    res = temporal_bfs_over_view(
        edges, windows, sources=sources, plan=plan, n_vertices=n_vertices,
        init=init, **kwargs)
    return res, jnp.int32(-1)


def _solve_cc(edges, windows, sources, plan, n_vertices, init, kwargs):
    res = temporal_cc_over_view(
        edges, windows, plan=plan, n_vertices=n_vertices, init=init, **kwargs)
    return res, jnp.int32(-1)


def _solve_kcore(edges, windows, sources, plan, n_vertices, init, kwargs):
    k, kwargs = _require_k(kwargs)
    res = temporal_kcore_over_view(
        edges, windows, plan=plan, n_vertices=n_vertices, k=k, init=init,
        **kwargs)
    return res, jnp.int32(-1)


def _solve_betweenness(edges, windows, sources, plan, n_vertices, init, kwargs):
    res = temporal_betweenness_over_view(
        edges, windows, sources=sources, plan=plan, n_vertices=n_vertices,
        init=init, **kwargs)
    return res, jnp.int32(-1)


# ---- containment warm starts (DESIGN.md §7.2 / §7.4) -----------------------

def _containment_spans(windows_new, prev_windows):
    """Shared warm-start precheck: span arrays, or None when no previous
    window can be strictly contained in any new window.  Equal-span
    containment is equality, which row matching already consumed — so the
    steady sliding loop (all widths equal) early-outs here without scanning
    pairs or building any arrays."""
    new_spans = windows_new[:, 1].astype(np.int64) - windows_new[:, 0]
    prev_spans = prev_windows[:, 1].astype(np.int64) - prev_windows[:, 0]
    if prev_spans.size == 0 or int(prev_spans.min()) >= int(new_spans.max()):
        return None
    return new_spans, prev_spans


def _best_contained(w, span, source, prev_windows, prev_spans, prev_sources):
    """Widest previous SAME-SOURCE row whose window is STRICTLY contained
    in ``w`` (None if none).  ``source`` is None for source-free rows, where
    any previous row of the group is eligible."""
    best, best_span = None, -1
    for p, wp in enumerate(prev_windows):
        if (prev_sources[p] == source and prev_spans[p] < span
                and wp[0] >= w[0] and wp[1] <= w[1]
                and int(prev_spans[p]) > best_span):
            best, best_span = p, int(prev_spans[p])
    return best


def _ea_warm(new_sources, new_windows, prev_sources, prev_windows,
             prev_results, n_vertices):
    """[Qn, V] EA warm start: each new row seeded from a previous SAME-source
    row it STRICTLY contains (labels witnessed by paths in the contained
    window remain witnessed, and EA's monotone min fixpoint is unique — so
    the warm run converges to exactly the cold answer; DESIGN.md §7.2).
    Returns None when no containment exists (the cold init path is then
    taken)."""
    spans = _containment_spans(new_windows, prev_windows)
    if spans is None:
        return None
    new_spans, prev_spans = spans
    rows, any_warm = [], False
    for s, w, span in zip(new_sources, new_windows, new_spans):
        cold = jnp.full(n_vertices, INT_INF, jnp.int32).at[s].set(int(w[0]))
        best = _best_contained(w, span, s, prev_windows, prev_spans,
                               prev_sources)
        if best is None:
            rows.append(cold)
        else:
            any_warm = True
            rows.append(jnp.minimum(cold, prev_results[best]))
    return jnp.stack(rows) if any_warm else None


def _reach_warm(new_sources, new_windows, prev_sources, prev_windows,
                prev_results, n_vertices):
    """([Qn, V] end, [Qn, V] start) overlaps-reachability warm start from
    contained same-source rows: every warm (end, start) pair is the
    last-edge interval of a REAL overlaps chain inside the containing new
    window, so every reported vertex stays truly reachable (sound).  The
    lexicographic heuristic may settle a different witness pair than a cold
    run, so this is opt-in behind ``warm_start=`` (DESIGN.md §7.2)."""
    spans = _containment_spans(new_windows, prev_windows)
    if spans is None:
        return None
    new_spans, prev_spans = spans
    reach_p, start_p, end_p = prev_results
    e_rows, s_rows, any_warm = [], [], False
    for s, w, span in zip(new_sources, new_windows, new_spans):
        ta = int(w[0])
        ce = jnp.full(n_vertices, INT_INF, jnp.int32).at[s].set(ta)
        cs = jnp.full(n_vertices, INT_INF, jnp.int32).at[s].set(ta)
        best = _best_contained(w, span, s, prev_windows, prev_spans,
                               prev_sources)
        if best is None:
            e_rows.append(ce)
            s_rows.append(cs)
        else:
            any_warm = True
            pe = jnp.where(reach_p[best], end_p[best], INT_INF)
            ps = jnp.where(reach_p[best], start_p[best], INT_INF)
            better = (pe < ce) | ((pe == ce) & (ps < cs))
            e_rows.append(jnp.where(better, pe, ce))
            s_rows.append(jnp.where(better, ps, cs))
    if not any_warm:
        return None
    return jnp.stack(e_rows), jnp.stack(s_rows)


def _cc_warm(new_sources, new_windows, prev_sources, prev_windows,
             prev_results, n_vertices):
    """[Qn, V] hash-min label warm start from contained rows: a contained
    window's components are SUB-components of the new window's, so its
    converged labels are member-vertex ids bounding each sub-component's
    minimum — min-label propagation from them converges to exactly the
    per-component minimum, i.e. the cold answer (EXACT; DESIGN.md §7.4).
    Rows without a contained predecessor start from the identity labels."""
    spans = _containment_spans(new_windows, prev_windows)
    if spans is None:
        return None
    new_spans, prev_spans = spans
    base = jnp.arange(n_vertices, dtype=jnp.int32)
    rows, any_warm = [], False
    for s, w, span in zip(new_sources, new_windows, new_spans):
        best = _best_contained(w, span, s, prev_windows, prev_spans,
                               prev_sources)
        if best is None:
            rows.append(base)
        else:
            any_warm = True
            rows.append(prev_results[best])
    return jnp.stack(rows) if any_warm else None


def _b_ea(g, s, w, t, plan, kw):
    return earliest_arrival_batched(g, s, w, t, plan=plan, **kw)


def _b_reach(g, s, w, t, plan, kw):
    return overlaps_reachability_batched(g, s, w, t, plan=plan, **kw)


def _b_pagerank(g, s, w, t, plan, kw):
    return temporal_pagerank_batched(g, w, t, plan=plan, **kw)


def _b_bfs(g, s, w, t, plan, kw):
    return temporal_bfs_batched(g, s, w, t, plan=plan, **kw)


def _b_cc(g, s, w, t, plan, kw):
    return temporal_cc_batched(g, w, t, plan=plan, **kw)


def _require_k(kw):
    if "k" not in kw:
        raise ValueError("algorithm='kcore' requires the k= parameter")
    kw = dict(kw)
    return kw.pop("k"), kw


def _b_kcore(g, s, w, t, plan, kw):
    k, kw = _require_k(kw)
    return temporal_kcore_batched(g, k, w, t, plan=plan, **kw)


def _b_betweenness(g, s, w, t, plan, kw):
    return temporal_betweenness_batched(g, s, w, t, plan=plan, **kw)


def _s_ea(g, s, w, t, plan, kw):
    return earliest_arrival(g, s, w, t, plan=plan, **kw)


def _s_reach(g, s, w, t, plan, kw):
    return overlaps_reachability(g, s, w, t, plan=plan, **kw)


def _s_pagerank(g, s, w, t, plan, kw):
    return temporal_pagerank(g, w, t, plan=plan, **kw)


def _s_bfs(g, s, w, t, plan, kw):
    return temporal_bfs(g, s, w, t, plan=plan, **kw)


def _s_cc(g, s, w, t, plan, kw):
    return temporal_cc(g, w, t, plan=plan, **kw)


def _s_kcore(g, s, w, t, plan, kw):
    k, kw = _require_k(kw)
    return temporal_kcore(g, k, w, t, plan=plan, **kw)


def _s_betweenness(g, s, w, t, plan, kw):
    return temporal_betweenness(g, jnp.asarray([s]), w, t, plan=plan, **kw)


_ALGOS = {
    "earliest_arrival": _Algo(_solve_ea, _b_ea, _s_ea, 1, False, _ea_warm),
    "reachability": _Algo(_solve_reach, _b_reach, _s_reach, 3, False,
                          _reach_warm),
    "pagerank": _Algo(_solve_pagerank, _b_pagerank, _s_pagerank, 1, True,
                      None),
    "bfs": _Algo(_solve_bfs, _b_bfs, _s_bfs, 2, False, None),
    "cc": _Algo(_solve_cc, _b_cc, _s_cc, 1, True, _cc_warm),
    "kcore": _Algo(_solve_kcore, _b_kcore, _s_kcore, 1, True, None),
    "betweenness": _Algo(_solve_betweenness, _b_betweenness, _s_betweenness,
                         1, False, None),
}

ALGORITHMS = tuple(_ALGOS)


def _algo(algorithm: str) -> _Algo:
    try:
        return _ALGOS[algorithm]
    except KeyError:
        raise ValueError(
            f"algorithm must be one of {ALGORITHMS}, got {algorithm!r}")


def sliding_windows(t_end: int, width: int, stride: int, count: int) -> np.ndarray:
    """The serving shape: ``count`` windows of ``width`` ending at
    ``t_end``, sliding back by ``stride`` — windows[0] is the most recent.
    Returns i32[count, 2]."""
    if count <= 0 or width <= 0 or stride <= 0:
        raise ValueError("count, width and stride must be positive")
    ends = t_end - stride * np.arange(count, dtype=np.int64)
    wins = np.stack([ends - width, ends], axis=1)
    return wins.astype(np.int32)


def sweep(
    g: TemporalGraph,
    source,
    windows,
    tger: Optional[TGERIndex] = None,
    *,
    algorithm: str = "earliest_arrival",
    access: str = "auto",
    backend: str = "xla_segment",
    plan: Optional[AccessPlan] = None,
    **kwargs,
):
    """Answer one query over W windows in a single batched execution.

    Returns [W, V] (or a tuple of [W, V] arrays for the multi-output
    algorithms: reachability, bfs).  ``plan`` defaults to
    ``plan_query(..., windows=windows)`` — the union-window plan whose
    budgets cover every member window; pass an explicit plan to pin the
    method/backend.  ``source`` is ignored by the source-free algorithms
    (pagerank, cc, kcore)."""
    entry = _algo(algorithm)
    windows = np.asarray(windows, np.int32).reshape(-1, 2)
    if plan is None:
        plan = plan_query(g, tger, windows=windows, access=access,
                          backend=backend)
    return entry.batched(g, source, windows, tger, plan, kwargs)


def sweep_looped(
    g: TemporalGraph,
    source,
    windows,
    tger: Optional[TGERIndex] = None,
    *,
    algorithm: str = "earliest_arrival",
    access: str = "auto",
    backend: str = "xla_segment",
    plan: Optional[AccessPlan] = None,
    **kwargs,
):
    """Reference execution: W independent single-window runs under the SAME
    union plan (so batched-vs-looped differ only in amortization, not in
    budgets).  Returns the same [W, ...] stacking as :func:`sweep`."""
    entry = _algo(algorithm)
    windows = np.asarray(windows, np.int32).reshape(-1, 2)
    if plan is None:
        plan = plan_query(g, tger, windows=windows, access=access,
                          backend=backend)
    rows = []
    for w in windows:
        win = (int(w[0]), int(w[1]))
        rows.append(entry.single(g, source, win, tger, plan, kwargs))
    if entry.n_outputs > 1:
        return tuple(
            jnp.stack([r[i] for r in rows]) for i in range(entry.n_outputs)
        )
    return jnp.stack(rows)


# ---------------------------------------------------------------------------
# Incremental serving (DESIGN.md §7.2–§7.4)
# ---------------------------------------------------------------------------

# trace-time events of the fused steps: incremented ONLY when jax traces a
# new (static-signature) variant.  The soak tests pin this after warmup —
# steady-state advances must not retrace.
_TRACE_COUNTS: dict = {}

# dispatch-site log: tests install a list here and every device-dispatch
# site in the incremental path appends a tag — the steady-state advance
# must log exactly one "fused:<method>" entry (the acceptance property),
# no matter how many tenants the batch carries.
#
# Two handles coexist.  The module global is the legacy test hook
# (``ws._DISPATCH_LOG = log = []``); the contextvar is the REENTRANT
# handle :func:`dispatch_log` manages — nested scopes (a GraphBatchServer
# tick inside a test that also reads the log) each observe every tag
# without the save/swap/restore dance that used to clobber concurrent
# readers, and contextvars give each thread/async context its own stack.
_DISPATCH_LOG: Optional[list] = None

_DISPATCH_LOG_VAR: "contextvars.ContextVar[tuple]" = contextvars.ContextVar(
    "repro_serve_dispatch_logs", default=())


@contextlib.contextmanager
def dispatch_log():
    """Collect dispatch-site tags for the enclosed calls: ``with
    dispatch_log() as log: ...``.  Re-entrant — nested scopes STACK, every
    enclosing log receives the tags of its whole extent (an outer observer
    is not blinded by an inner scope the way the old module-global swap
    blinded it), and the contextvar scoping keeps concurrent servers on
    different threads from clobbering each other's logs."""
    log: list = []
    token = _DISPATCH_LOG_VAR.set(_DISPATCH_LOG_VAR.get() + (log,))
    try:
        yield log
    finally:
        _DISPATCH_LOG_VAR.reset(token)


def fused_trace_count() -> int:
    """Total fused-step traces so far (one per new static signature)."""
    return sum(_TRACE_COUNTS.values())


def _trace_event(tag) -> None:
    _TRACE_COUNTS[tag] = _TRACE_COUNTS.get(tag, 0) + 1


def _note(tag: str) -> None:
    logs = _DISPATCH_LOG_VAR.get()
    for log in logs:
        log.append(tag)
    if _DISPATCH_LOG is not None and all(
            _DISPATCH_LOG is not log for log in logs):
        _DISPATCH_LOG.append(tag)


def _call_donating(fn, *args, **kwargs):
    """Invoke a buffer-donating jitted step with jax's "donated buffers
    were not usable" UserWarning suppressed FOR THIS CALL ONLY (XLA
    declines to alias some leaves — expected residue, not actionable; a
    process-wide filter would swallow real donation diagnostics from user
    code).  The steps donate their view/result buffers so the steady state
    reallocs nothing where XLA can alias; the carried state is single-use
    (DESIGN.md §7.3)."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable",
            category=UserWarning)
        return fn(*args, **kwargs)


@dataclasses.dataclass
class SweepState:
    """The carry between consecutive incremental advances: the answered
    (algorithm × source × window) rows — bucketed into (algorithm, params)
    groups — their [Q, V] answers (row reuse), the RING-buffer union edge
    view shared by every tenant (positionally stable across advances —
    DESIGN.md §7.3), and the host-side position bookkeeping the delta
    scatter needs.

    ``last_advance`` records how the view was obtained — ``cold`` (full
    plan + ring build, no reuse), ``delta`` (fused one-dispatch ring
    advance; index AND hybrid), ``reuse`` (scan view, untouched),
    ``noop``/``reorder`` (row set unchanged / permuted) — and ``n_solved``
    how many ROWS actually ran a fixpoint across all groups.

    Donation contract (DESIGN.md §7.3): passing a state to an advance
    DONATES its view and result buffers to the fused step — the state is
    MOVED-FROM, single-use.  Reusing a consumed state, or reading result
    arrays returned before the advance that consumed them, raises jax's
    "buffer has been deleted or donated" error.  Copy rows out
    (``np.asarray``) before the next advance if retention is needed."""

    group_keys: tuple            # ((algorithm, params_token), ...) per group
    group_sources: tuple         # per group: tuple of source ids (None = source-free)
    group_windows: tuple         # per group: i32[Qg, 2] (host)
    plan: AccessPlan
    edges: EdgeView              # ring-layout union view (device)
    union: Tuple[int, int]
    lo: int                      # first resident time-first position (index:
                                 # global order; hybrid: heavy order; -1 scan)
    hi: int                      # end of the VALID position range [lo, hi)
    capacity: int                # ring slot count C (0 for scan)
    results: tuple               # per-group [Qg, V] array / tuple (device)
    graph_ref: Any               # strong ref to g.src — pins identity (no id reuse)
    last_advance: str = "cold"
    n_solved: int = 0
    warm_applied: bool = False   # an explicit warm_start= actually seeded rows
    last_rounds: Any = None      # i32 device scalar(s) (EA groups; lazy, no sync)
    mesh: Any = None             # query Mesh of a SHARDED stream (DESIGN.md §7.5)
    n_solved_unique: int = 0     # rows that actually ran a fixpoint after dedup
    group_caps: tuple = ()       # per-group BUCKETED row capacity (§7.6;
                                 # empty = exact-shape static schedule mode)
    last_schedule: Any = None    # static schedule of the last fused advance
                                 # (None after cold/noop/reorder) — the churn
                                 # soak keys retrace accounting on it

    # -- single-tenant back-compat views ------------------------------------

    @property
    def algorithm(self) -> str:
        """The algorithm of a single-group state (the ``sweep_incremental``
        wrapper's view; ambiguous — and an error — on multi-group states)."""
        if len(self.group_keys) != 1:
            raise ValueError("algorithm is ambiguous on a multi-group state")
        return self.group_keys[0][0]

    @property
    def windows(self) -> np.ndarray:
        """i32[W, 2] windows of a single-group state."""
        if len(self.group_keys) != 1:
            raise ValueError("windows is ambiguous on a multi-group state")
        return self.group_windows[0]


def _assemble(prev, sub, row_map, new_pos, n_outputs: int):
    """Row assembly: copy reused rows from the previous sweep (static
    gather), scatter the freshly-solved rows into their positions."""
    rm = jnp.asarray(row_map, jnp.int32)
    npos = jnp.asarray(new_pos, jnp.int32)

    def one(p, s):
        return p[rm].at[npos].set(s)

    with jax.named_scope("assemble"):
        if n_outputs == 1:
            return one(prev, sub)
        return tuple(one(prev[i], sub[i]) for i in range(n_outputs))


def _gather_rows(prev, row_map, n_outputs: int):
    """Reused-rows-only groups: a static gather (or the buffer untouched
    when the map is the FULL identity — the steady multi-tenant case).
    The identity shortcut must also match the previous row COUNT: a new
    row set that is a strict prefix of the previous one has an identity
    row_map but needs the gather to drop the trailing rows."""
    n_prev = prev.shape[0] if n_outputs == 1 else prev[0].shape[0]
    if len(row_map) == n_prev and row_map == tuple(range(len(row_map))):
        return prev
    rm = jnp.asarray(row_map, jnp.int32)
    with jax.named_scope("assemble"):
        if n_outputs == 1:
            return prev[rm]
        return tuple(p[rm] for p in prev)


def _gather_solved(sub, solve_map, n_outputs: int):
    """Dedup/padding fan-out: map the solved UNIQUE (and, sharded, padded)
    rows back onto the full new-row axis — one static gather inside the
    fused program.  Identity maps are short-circuited to ``solve_map is
    None`` at schedule build, so the steady no-duplicate batch pays
    nothing."""
    sm = jnp.asarray(solve_map, jnp.int32)
    with jax.named_scope("assemble"):
        if n_outputs == 1:
            return sub[sm]
        return tuple(s[sm] for s in sub)


def _mesh_shape(mesh) -> Tuple[int, int]:
    """The serving mesh's ``(E, D)`` shape: a 1-D query mesh is ``(1, D)``
    (the row axis is always the LAST mesh axis, the edge axis — when the
    mesh has one — the first), ``None`` is ``(1, 1)``."""
    if mesh is None:
        return 1, 1
    names = mesh.axis_names
    d = int(mesh.shape[names[-1]])
    e = int(mesh.shape[names[0]]) if len(names) > 1 else 1
    return e, d


def _place_ring(edges, mesh):
    """Device placement of the ring view under a serving mesh: replicated
    on a 1-D query mesh (§7.5); on a 2-D edge×query mesh (§7.7) sharded
    along the slot axis over the EDGE axis — contiguous chunks, so edge
    shard e owns global slots [e*C/E, (e+1)*C/E) and the positionally
    stable ring slot order is the shard boundary."""
    e_sh, _ = _mesh_shape(mesh)
    if e_sh == 1:
        return replicate(edges, mesh)
    C = edges.src.shape[0]
    if C % e_sh:
        raise ValueError(
            f"ring capacity {C} does not divide across {e_sh} edge shards "
            f"— capacity rungs are powers of two, so use a power-of-two "
            f"edge-shard count")
    return jax.device_put(
        edges, NamedSharding(mesh, PartitionSpec(mesh.axis_names[0])))


def _solve_rows_sharded(entry, params, plan, n_vertices, mesh, edges,
                        windows, sources, init):
    """One group's new-row solve with the (padded) row axis SHARDED over
    the mesh's query axis (DESIGN.md §7.5): each device runs the group
    fixpoint over ONLY its contiguous row chunk — its own while_loop, so a
    device whose rows converge early exits early instead of idling in a
    joint loop until the globally deepest row settles — then the solved
    rows are constrained back to replicated (the per-advance gather),
    keeping row reuse and assembly on later advances device-local.

    Under a 2-D edge×query mesh (DESIGN.md §7.7) the VIEW is additionally
    sharded along its slot axis: each (edge, query) device relaxes only
    its slot chunk, and the plan's ``edge_axis`` — set HERE, at trace
    time, inside the shard_map body — makes every per-round edge-wide
    segment combine finish with ONE collective (pmin/pmax/psum) across
    the edge axis.  The post-collective vertex state is replicated along
    that axis, so the edge shards of one row chunk stay in lockstep
    through every convergence cond while the query axis keeps LOCAL
    convergence; the row-sharded out_specs below (which omit the edge
    axis) are exactly that replication invariant."""
    row_ax = mesh.axis_names[-1]
    row, rep = PartitionSpec(row_ax), PartitionSpec()
    edge_ax = mesh.axis_names[0] if len(mesh.axis_names) > 1 else None
    edge_spec = rep if edge_ax is None else PartitionSpec(edge_ax)
    has_src, has_init = sources is not None, init is not None
    args, specs = [windows], [row]
    if has_src:
        args.append(sources)
        specs.append(row)
    if has_init:
        args.append(init)
        specs.append(row)
    args.append(edges)
    specs.append(edge_spec)

    def body(*a):
        it = iter(a)
        w_l = next(it)
        s_l = next(it) if has_src else None
        i_l = next(it) if has_init else None
        e_l = next(it)
        p_l = (plan if edge_ax is None
               else dataclasses.replace(plan, edge_axis=edge_ax))
        sub, rounds = entry.solve(e_l, w_l, s_l, p_l, n_vertices, i_l,
                                  dict(params))
        sub = sub if isinstance(sub, tuple) else (sub,)
        # per-device round counts concatenate along the row axis; the max
        # restores the joint-loop scalar semantics of `last_rounds`
        return sub, jnp.reshape(jnp.asarray(rounds, jnp.int32), (1,))

    f = _compat_shard_map(body, mesh=mesh, in_specs=tuple(specs),
                          out_specs=(row, row))
    sub, rounds = f(*args)
    sub = jax.lax.with_sharding_constraint(
        sub, NamedSharding(mesh, PartitionSpec()))
    return (sub[0] if entry.n_outputs == 1 else sub), jnp.max(rounds)


def _solve_new_rows(algorithm, params, plan, n_vertices, mesh, edges,
                    windows, sources, init):
    """One group's solve of its new rows, named ``fixpoint.<algorithm>``
    in the program; row-sharded over the query axis under a ``mesh``."""
    entry = _ALGOS[algorithm]
    with jax.named_scope(f"fixpoint.{algorithm}"):
        if mesh is None:
            return entry.solve(edges, windows, sources, plan, n_vertices,
                               init, dict(params))
        return _solve_rows_sharded(entry, params, plan, n_vertices, mesh,
                                   edges, windows, sources, init)


def _solve_groups(edges, plan, n_vertices, schedule, prev_results,
                  new_windows, new_sources, inits, maps=None, mesh=None):
    """The dispatch-table core of the fused step: every group's solve (of
    only its genuinely-new rows) + row assembly, traced into ONE program
    over the just-advanced view.  ``schedule`` is static — (algorithm,
    params, row_map, new_pos, solve_map) per group — so the group
    structure specializes the compilation exactly like the budget rungs
    do.  ``solve_map`` (None = identity) maps the full new-row axis onto
    the deduplicated (and, under a query mesh, padded) solved rows; with a
    ``mesh`` the solve itself row-shards across devices.

    A group may instead carry a BUCKETED entry ``(algorithm, params,
    "bucket", cap, n_new_cap)`` (the §7.6 admission ladder): its row maps
    are DYNAMIC i32[cap] arrays in ``maps`` rather than static schedule
    fields, so the trace signature keys only the padded capacities — a
    tenant admitted or retired inside the bucket changes no static shape.
    Assembly is one gather over the concatenated (previous-buffer ‖
    freshly-solved) row pool; pad slots replicate the last real row."""
    out, rounds_out = [], []
    for gi, entry_s in enumerate(schedule):
        algorithm, params = entry_s[0], entry_s[1]
        entry = _ALGOS[algorithm]
        prev = prev_results[gi]
        if entry_s[2] == "bucket":
            n_new_cap = entry_s[4]
            sel = jnp.asarray(maps[gi], jnp.int32)
            if n_new_cap:
                # bucketed × mesh (§7.7): the solve capacity is padded to a
                # bucket-aligned multiple of the query-axis size at
                # schedule build, so the bucketed rows shard exactly like
                # exact-schedule rows do
                sub, rounds = _solve_new_rows(
                    algorithm, params, plan, n_vertices, mesh, edges,
                    new_windows[gi], new_sources[gi], inits[gi])
                subs = sub if isinstance(sub, tuple) else (sub,)
                if prev is None:
                    pool = subs
                else:
                    prevs = prev if isinstance(prev, tuple) else (prev,)
                    with jax.named_scope("assemble"):
                        pool = tuple(
                            jnp.concatenate([p, s], axis=0)
                            for p, s in zip(prevs, subs))
            else:
                rounds = jnp.int32(-1)
                pool = prev if isinstance(prev, tuple) else (prev,)
            with jax.named_scope("assemble"):
                picked = tuple(p[sel] for p in pool)
            out.append(picked[0] if entry.n_outputs == 1 else picked)
            rounds_out.append(rounds)
            continue
        row_map, new_pos, solve_map = entry_s[2], entry_s[3], entry_s[4]
        if new_pos:
            sub, rounds = _solve_new_rows(
                algorithm, params, plan, n_vertices, mesh, edges,
                new_windows[gi], new_sources[gi], inits[gi])
            if solve_map is not None:
                sub = _gather_solved(sub, solve_map, entry.n_outputs)
            res = sub if prev is None else _assemble(
                prev, sub, row_map, new_pos, entry.n_outputs)
        else:
            res = _gather_rows(prev, row_map, entry.n_outputs)
            rounds = jnp.int32(-1)
        out.append(res)
        rounds_out.append(rounds)
    return tuple(out), tuple(rounds_out)


# ---------------------------------------------------------------------------
# fused one-dispatch advance steps (DESIGN.md §7.3–§7.4): ring advance + ALL
# groups' fixpoint solves + row assembly in ONE jitted program, with the
# ring and result buffers donated so a steady-state advance reallocates
# nothing.
# ---------------------------------------------------------------------------

# NB: the fused steps take the five raw edge arrays + the relevant
# permutation rather than the TemporalGraph/TGERIndex pytrees — per-call
# pytree flattening of ~24 leaves is measurable dispatch latency at small
# serving budgets, and the step needs nothing else from either structure.

_ADVANCE_RING = {
    "index": advance_index_ring_fields,
    "hybrid": advance_hybrid_ring_fields,
}


def _advance_ring_sharded(mesh, fields, perm, edges, positions, *,
                          capacity: int, delta_budget: int):
    """Edge-sharded index-ring delta advance (DESIGN.md §7.7): edge shard
    e of the 2-D mesh owns the contiguous slot chunk [e*C/E, (e+1)*C/E),
    so the entering scatter lands ONLY on the owning shard.  Every shard
    gathers the same delta-budget entering positions from the replicated
    time-first permutation (O(delta) work), maps them to LOCAL slots, and
    drops the out-of-chunk ones; the validity mask is recomputed from the
    shard's global slot offset.  Per slot this is bit-identical to the
    unsharded ``advance_index_ring_fields`` — the slot identity
    ``slot(p) = p mod C`` is layout-stable, the chunking only decides
    which device materializes which slot."""
    ax_e = mesh.axis_names[0]
    n_e = int(mesh.shape[ax_e])
    c_local = capacity // n_e

    def body(fields_l, perm_l, edges_l, pos_l):
        base = jax.lax.axis_index(ax_e) * c_local
        lo_prev, lo_new, hi_new = pos_l[0], pos_l[1], pos_l[2]
        enter = lo_prev + capacity + jnp.arange(delta_budget,
                                                dtype=jnp.int32)
        ok = enter < lo_new + capacity
        eids = perm_l[jnp.minimum(enter, perm_l.shape[0] - 1)]
        gslot = jnp.mod(enter, capacity)
        lslot = jnp.where(
            ok & (gslot >= base) & (gslot < base + c_local),
            gslot - base, c_local)                       # OOB -> dropped
        new = [
            p.at[lslot].set(f[eids], mode="drop")
            for p, f in zip(edges_l[:5], fields_l)
        ]
        pos = base + jnp.arange(c_local, dtype=jnp.int32)
        pos = lo_new + jnp.mod(pos - lo_new, capacity)
        return EdgeView(*new, pos < hi_new)

    rep, shard = PartitionSpec(), PartitionSpec(ax_e)
    f = _compat_shard_map(
        body, mesh=mesh, in_specs=(rep, rep, shard, rep), out_specs=shard)
    return f(fields, perm, edges, positions)


@functools.partial(
    jax.jit,
    static_argnames=("method", "n_vertices", "capacity", "delta_budget",
                     "schedule", "mesh"),
    donate_argnames=("edges", "prev_results"),
)
def _fused_step_ring(
    fields,                         # (src, dst, t_start, t_end, weight)
    perm,                           # time-first permutation (global | heavy)
    plan: AccessPlan,
    edges: EdgeView,
    prev_results,                   # tuple per group (None = new group)
    new_windows,                    # tuple per group: i32[Qn, 2] | None
    new_sources,                    # tuple per group: i32[Qn] | None
    inits,                          # tuple per group: warm init pytree | None
    maps,                           # tuple per group: i32[cap] sel | None
    positions,                      # i32[3]: (lo_prev, lo_new, hi_new) packed
    *,
    method: str,
    n_vertices: int,
    capacity: int,
    delta_budget: int,
    schedule: tuple,
    mesh: Optional[Mesh] = None,
):
    _trace_event((method, capacity, delta_budget, schedule, mesh))
    with jax.named_scope("ring.advance"):
        if mesh is not None and len(mesh.axis_names) > 1:
            # 2-D edge×query mesh (§7.7): the ring is sharded along its
            # slot axis, so the delta scatter runs shard-local (only the
            # owning edge shard lands each entering slot) — with the
            # solves below it is still ONE SPMD program, one dispatch per
            # device per advance
            edges = _advance_ring_sharded(
                mesh, fields, perm, edges, positions,
                capacity=capacity, delta_budget=delta_budget)
        else:
            # under a 1-D query mesh the inputs are replicated, so the
            # delta scatter runs per device on that device's whole ring
            # replica — the SPMD program is still ONE dispatch per device
            # per advance (§7.5)
            edges = _ADVANCE_RING[method](
                fields, perm, edges, positions[0], positions[1],
                positions[2], capacity=capacity, delta_budget=delta_budget)
    results, rounds = _solve_groups(
        edges, plan, n_vertices, schedule, prev_results, new_windows,
        new_sources, inits, maps=maps, mesh=mesh)
    return results, edges, rounds


# NB: the scan step does NOT donate the view — the scan view aliases the
# graph's own edge arrays, which must outlive every advance.
@functools.partial(
    jax.jit,
    static_argnames=("n_vertices", "schedule", "mesh"),
    donate_argnames=("prev_results",),
)
def _fused_step_scan(
    fields,                         # (src, dst, t_start, t_end, weight)
    plan: AccessPlan,
    prev_results,
    new_windows,
    new_sources,
    inits,
    maps,                           # tuple per group: i32[cap] sel | None
    *,
    n_vertices: int,
    schedule: tuple,
    mesh: Optional[Mesh] = None,
):
    _trace_event(("scan", schedule, mesh))
    edges = EdgeView(*fields, jnp.ones(fields[0].shape[0], dtype=bool))
    results, rounds = _solve_groups(
        edges, plan, n_vertices, schedule, prev_results, new_windows,
        new_sources, inits, maps=maps, mesh=mesh)
    return results, rounds


# ---------------------------------------------------------------------------
# the shared advance engine
# ---------------------------------------------------------------------------

def _match_rows(new_sources, new_windows, prev_sources, prev_windows):
    """Vectorized (source, window) row matching within one group: returns
    per-new-row previous indices (None = row needs solving).  The source
    mask is skipped when every row on both sides shares one source (the
    single-tenant steady state — per-advance host latency matters at
    serving budgets, DESIGN.md §7.3)."""
    if len(prev_sources) == 0:
        return [None] * len(new_sources)
    eq = (new_windows[:, None, :] == prev_windows[None, :, :]).all(axis=2)
    src_set = set(new_sources)
    if not (src_set == set(prev_sources) and len(src_set) == 1):
        ns = np.asarray([-1 if s is None else s for s in new_sources])
        ps = np.asarray([-1 if s is None else s for s in prev_sources])
        eq &= ns[:, None] == ps[None, :]
    has = eq.any(axis=1)
    arg = eq.argmax(axis=1)
    return [int(arg[i]) if has[i] else None for i in range(len(new_sources))]


def _plan_covers(g, tger, p: AccessPlan, union) -> bool:
    """May a fallback REUSE the previous plan for this union?  Keeping the
    plan (and hence the ring-capacity rung) stable across cold fallbacks is
    what pins the fused step's jit cache over a long serving horizon —
    replan only when coverage actually lapsed."""
    if p.method == "scan":
        return True
    if tger is None:
        return False
    if p.method == "index":
        lo, hi = window_positions_host(tger, union)
        return hi - lo <= (p.ring_capacity or p.budget)
    lo, hi = heavy_window_positions_host(tger, union)
    if p.ring_capacity and hi - lo > p.ring_capacity:
        return False
    return per_vertex_window_budget(g, tger, union) <= p.per_vertex_budget


def _group_warm(key, warm_start, new_sources, new_windows, prev, n_vertices):
    """The explicit ``warm_start=`` gate (DESIGN.md §7.2/§7.4): EA and cc
    warm starts are exact, reachability's is sound-but-not-bit-stable
    (opt-in is the consent to that); bfs (round-indexed hops), pagerank
    (finite-iteration drift), kcore (peeling cannot resurrect) and
    betweenness (not a monotone fixpoint) are REFUSED — the caller
    observes refusals via ``state.warm_applied``."""
    algorithm, params = key
    entry = _ALGOS[algorithm]
    if not warm_start or entry.warm is None or prev is None:
        return None
    if algorithm == "earliest_arrival" and dict(params).get("visit_once"):
        return None  # visited-blocking breaks re-expansion: unsound
    prev_sources, prev_windows, prev_results = prev
    return entry.warm(new_sources, new_windows, prev_sources, prev_windows,
                      prev_results, n_vertices)


def _advance(
    g: TemporalGraph,
    tger: Optional[TGERIndex],
    groups,                 # [(key, sources list, i32[Qg,2] windows), ...]
    state: Optional[SweepState],
    *,
    plan_arg: Optional[AccessPlan],
    plan_builder: Callable[[], AccessPlan],
    warm_start: bool,
    mesh: Optional[Mesh] = None,
    bucketed: bool = False,
    bucket_headroom: int = 0,
    coldstore=None,
    tier: str = "hot",
):
    """The incremental advance shared by ``serve_batch`` (multi-tenant) and
    ``sweep_incremental`` (single-tenant wrapper): match every group's rows
    against the carried state, then answer everything in ONE fused jitted
    dispatch (ring delta + per-group solves + row assembly), falling back
    to a cold plan+build+solve only when coverage or direction force it.
    With a query ``mesh`` the fused step row-shards every group's solve
    across the mesh devices (DESIGN.md §7.5) — still one dispatch per
    device per advance.

    ``bucketed=True`` is the §7.6 admission-ladder mode the serving daemon
    drives: every group's result buffer is PADDED to its power-of-two
    :func:`~repro.engine.queries.bucket_capacity` (pad slots replicate the
    last real row) and the fused schedule carries only the padded
    capacities statically — row assignment travels as dynamic i32[cap]
    gather maps — so tenant churn inside a bucket is a jit-cache HIT that
    consumes the donated state warm."""
    union = (
        min(int(w[:, 0].min()) for _, _, w in groups),
        max(int(w[:, 1].max()) for _, _, w in groups),
    )
    n_rows_total = sum(len(s) for _, s, _ in groups)

    caps: tuple = ()
    if bucketed:
        prev_caps = (
            {} if state is None
            else dict(zip(state.group_keys, state.group_caps))
        )
        # ``bucket_headroom`` (the daemon's EWMA arrival-rate forecast)
        # sizes the bucket for the rows EXPECTED next tick, not just the
        # rows present now — a forecasted burst admits without a single
        # rebucket; the 4x shrink hysteresis still applies on top
        caps = tuple(
            bucket_capacity(len(s) + max(0, int(bucket_headroom)),
                            prev_caps.get(key, 0))
            for key, s, _ in groups
        )

    def freeze(plan, edges, lo, hi, capacity, results, advance, n_solved,
               warm_applied, rounds, n_unique=0, last_schedule=None):
        return SweepState(
            group_keys=tuple(k for k, _, _ in groups),
            group_sources=tuple(tuple(s) for _, s, _ in groups),
            group_windows=tuple(w.copy() for _, _, w in groups),
            plan=plan, edges=edges, union=union, lo=lo, hi=hi,
            capacity=capacity, results=results, graph_ref=g.src,
            last_advance=advance, n_solved=n_solved,
            warm_applied=warm_applied,
            last_rounds=rounds[0] if len(rounds) == 1 else rounds,
            mesh=mesh, n_solved_unique=n_unique, group_caps=caps,
            last_schedule=last_schedule,
        )

    def cold(prev_plan=None):
        p = plan_arg
        if p is None and prev_plan is not None and _plan_covers(
                g, tger, prev_plan, union):
            p = prev_plan
        if p is None:
            with telemetry.span("plan.build"):
                p = plan_builder()
        telemetry.end("serve.plan")
        if tier != "hot":
            # tiered rebuild (DESIGN.md §7.8): the view is stitched
            # host-side from the cold store's compacted chunks — plus the
            # host-mirror gather for the pending tail and a split window's
            # hot suffix — in EXACT index-ring slot order, so every group
            # solve below is bit-identical to a cold index build over the
            # same plan.  The carried hot ring (if any) is never consumed.
            _note("cold:stitch")
            capacity = p.ring_capacity or p.budget
            fields_np, mask_np, lo, hi = coldstore.ring_stitch(
                union, capacity)
            with telemetry.span("cold.upload"):
                edges = EdgeView(
                    *(jnp.asarray(a) for a in fields_np),
                    jnp.asarray(mask_np))
            telemetry.count("cold.upload_bytes", mask_np.nbytes + sum(
                a.nbytes for a in fields_np))
        else:
            _note("cold:view")
            edges, lo, hi, capacity = ring_view_for_plan(g, tger, union, p)
            if coldstore is not None and p.method == "index" and lo > 0:
                # everything below the fresh ring's low watermark is
                # history: seal it into the cold store (host-side, off the
                # dispatch path — the first note backfills from position 0)
                coldstore.note_eviction(lo)
        if mesh is not None and p.method != "scan":
            # place the ring ONCE at the cold build — replicated (1-D) or
            # edge-sharded (2-D): every later fused input/output keeps the
            # layout (sharding-stable jit cache from the first sharded
            # advance).  The scan view aliases the graph arrays and is
            # never delta-advanced, so it stays wherever the graph lives.
            edges = _place_ring(edges, mesh)
        results, rounds, n_unique = [], [], 0
        for gi, (key, sources, wins) in enumerate(groups):
            entry = _ALGOS[key[0]]
            _note("cold:solve")
            u_sources, u_windows, inverse = dedup_rows(sources, wins)
            n_unique += len(u_sources)
            with telemetry.span("serve.dispatch"):
                src_dev = (
                    None if entry.source_free
                    else jnp.asarray(u_sources, jnp.int32)
                )
                res, rnd = entry.solve(
                    edges, jnp.asarray(u_windows), src_dev, p, g.n_vertices,
                    None, dict(key[1]))
            out_map = tuple(inverse)
            if bucketed:
                # pad the buffer to the bucket capacity, replicating the
                # last real row (pad rows converge identically — they ARE
                # a real row — and never surface: the daemon slices)
                out_map = out_map + (out_map[-1],) * (caps[gi] - len(out_map))
            if out_map != tuple(range(len(u_sources))):
                res = _gather_solved(res, out_map, entry.n_outputs)
            results.append(res)
            rounds.append(rnd)
        if mesh is not None:
            results = [replicate(r, mesh) for r in results]
        return tuple(results), freeze(
            p, edges, lo, hi, capacity, tuple(results), "cold",
            n_rows_total, False, rounds, n_unique=n_unique)

    if state is None:
        return cold()
    p = state.plan

    # ---- match rows against the previous advance's answered groups --------
    prev_idx = {key: i for i, key in enumerate(state.group_keys)}
    matched = []                # per group: list of prev-row idx | None
    for key, sources, wins in groups:
        pi = prev_idx.get(key)
        if pi is None:
            matched.append([None] * len(sources))
        else:
            matched.append(_match_rows(
                sources, wins, state.group_sources[pi],
                state.group_windows[pi]))
    total_new = sum(sum(m is None for m in ms) for ms in matched)

    if total_new == 0:
        # noop only when every group's rows are the FULL identity of the
        # previous group's rows — matching a strict prefix (fewer rows
        # than answered) must take the reorder gather, not hand back the
        # previous, larger result buffers.
        identical = (
            tuple(k for k, _, _ in groups) == state.group_keys
            and all(
                ms == list(range(len(state.group_sources[pi])))
                for pi, ms in enumerate(matched)
            )
        )
        if identical:
            return state.results, dataclasses.replace(
                state, last_advance="noop", n_solved=0, warm_applied=False,
                n_solved_unique=0)
        # permutation of answered rows: per-group host-level gathers (in
        # bucketed mode the gather maps pad back out to the — possibly
        # hysteresis-shrunk — bucket capacity)
        _note("reorder")
        results = []
        for gi, ((key, _, _), ms) in enumerate(zip(groups, matched)):
            mm = tuple(ms)
            if bucketed:
                mm = mm + (mm[-1],) * (caps[gi] - len(mm))
            results.append(_gather_rows(
                state.results[prev_idx[key]], mm, _ALGOS[key[0]].n_outputs))
        results = tuple(results)
        return results, freeze(
            p, state.edges, state.lo, state.hi, state.capacity, results,
            "reorder", 0, False,
            [jnp.int32(-1)] * len(groups))

    if tier != "hot" or p.tier != "hot":
        # tier serves never delta-advance (historical windows do not
        # slide) and a tier switch must never consume the donated hot
        # state: the tier rides the plan signature, so fall cold — the
        # previous plan stays reusable only within its own tier
        return cold(prev_plan=p if p.tier == tier else None)

    # ---- build the fused schedule -----------------------------------------
    def build_schedule():
        schedule, prev_results, new_windows, new_sources, inits = \
            [], [], [], [], []
        any_warm = False
        n_unique = 0
        for (key, sources, wins), ms in zip(groups, matched):
            entry = _ALGOS[key[0]]
            new_idx = [i for i, m in enumerate(ms) if m is None]
            row_map = tuple(0 if m is None else m for m in ms)
            new_pos = tuple(new_idx)
            pi = prev_idx.get(key)
            prev_res = None if pi is None else state.results[pi]
            solve_map = None
            if new_idx:
                # cross-query dedup: identical (source, window) rows across
                # tenants collapse to ONE solved row; solve_map fans the
                # solved rows back out inside the fused program
                u_sources, u_windows, inverse = dedup_rows(
                    [sources[i] for i in new_idx], wins[new_idx])
                n_unique += len(u_sources)
                prev = (
                    None if pi is None else (
                        state.group_sources[pi], state.group_windows[pi],
                        state.results[pi])
                )
                init = _group_warm(key, warm_start, u_sources, u_windows,
                                   prev, g.n_vertices)
                if init is not None:
                    any_warm = True
                if mesh is not None:
                    # pad-and-mask row partition (DESIGN.md §7.5): pad the
                    # unique rows to cap * D so uneven counts never drop a
                    # row or retrace; real row j keeps global index j, so
                    # `inverse` is layout-oblivious and doubles as the
                    # padding-dropping gather.  D is the QUERY-axis size —
                    # on a 2-D mesh the edge axis replicates rows, it does
                    # not partition them.
                    _, pad_map = row_partition(
                        len(u_sources), _mesh_shape(mesh)[1])
                    u_windows = u_windows[pad_map]
                    u_sources = [u_sources[j] for j in pad_map]
                    if init is not None:
                        init = jax.tree_util.tree_map(
                            lambda a: a[jnp.asarray(pad_map)], init)
                solve_map = inverse
                if solve_map == tuple(range(len(u_sources))):
                    solve_map = None    # identity AND unpadded: no gather
                # host np arrays on purpose: the fused call converts them
                # during jit arg processing — an explicit jnp.asarray here
                # is a separate device_put dispatch per array per advance
                new_windows.append(np.ascontiguousarray(u_windows))
                new_sources.append(
                    None if entry.source_free
                    else np.asarray(u_sources, np.int32))
                inits.append(init)
            else:
                new_windows.append(None)
                new_sources.append(None)
                inits.append(None)
            schedule.append((key[0], key[1], row_map, new_pos, solve_map))
            prev_results.append(prev_res)
        if any_warm:
            _note("warm-init")
        return (tuple(schedule), tuple(prev_results), tuple(new_windows),
                tuple(new_sources), tuple(inits), any_warm, n_unique)

    # ---- the §7.6 bucketed schedule: static capacities, dynamic maps ------
    def build_schedule_bucketed():
        """Admission-ladder variant of ``build_schedule``: the schedule
        entry is ``(algorithm, params, "bucket", cap, K)`` — ONLY the
        padded bucket capacity and the solve-capacity rung are static.
        Row assignment travels as a dynamic i32[cap] gather map over the
        concatenated (previous padded buffer ‖ freshly solved rows) pool,
        so admitting/retiring a tenant inside the bucket reuses the exact
        compiled program and consumes the donated state warm."""
        schedule, prev_results, new_windows, new_sources, inits, maps = \
            [], [], [], [], [], []
        n_unique = 0
        for gi, ((key, sources, wins), ms) in enumerate(zip(groups, matched)):
            entry = _ALGOS[key[0]]
            cap = caps[gi]
            pi = prev_idx.get(key)
            prev_res = None if pi is None else state.results[pi]
            if pi is not None and state.group_caps[pi] != cap:
                # bucket transition: re-pad the carried buffer to the NEW
                # capacity (one host-level gather, only when the bucket
                # itself changes) so the fused step's input shapes key
                # ONLY the current capacities — the transition costs one
                # retrace, every within-bucket advance after it none
                needed = sorted({m for m in ms if m is not None}) or [0]
                remap = {m: j for j, m in enumerate(needed)}
                rm = tuple(needed) + (needed[-1],) * (cap - len(needed))
                _note("rebucket")
                prev_res = _gather_rows(prev_res, rm, entry.n_outputs)
                ms = [None if m is None else remap[m] for m in ms]
            new_idx = [i for i, m in enumerate(ms) if m is None]
            inverse: tuple = ()
            K = 0
            if new_idx:
                u_sources, u_windows, inverse = dedup_rows(
                    [sources[i] for i in new_idx], wins[new_idx])
                m_u = len(u_sources)
                n_unique += m_u
                # the new-row solve pads to the FULL bucket capacity: one
                # has-new-rows variant per capacity ever compiles, so
                # within-bucket churn can never shift a solve rung
                K = cap
                if mesh is not None:
                    # bucket-aligned partition (§7.7): the sharded solve
                    # capacity is chunk * D with chunk snapped up to the
                    # bucket ladder value of ceil(cap / D) — every chunk
                    # boundary lands on a bucket_capacity multiple, and K
                    # depends only on (cap, D), so within-bucket churn
                    # still retraces nothing.  For power-of-two D <= cap
                    # the snap is exact and K == cap.
                    d_sh = _mesh_shape(mesh)[1]
                    chunk, _ = row_partition(
                        cap, d_sh, align=bucket_capacity(-(-cap // d_sh)))
                    K = chunk * d_sh
                if K != m_u:
                    pad_map = list(range(m_u)) + [m_u - 1] * (K - m_u)
                    u_windows = u_windows[pad_map]
                    u_sources = [u_sources[j] for j in pad_map]
                new_windows.append(np.ascontiguousarray(u_windows))
                new_sources.append(
                    None if entry.source_free
                    else np.asarray(u_sources, np.int32))
            else:
                new_windows.append(None)
                new_sources.append(None)
            inits.append(None)      # warm starts are refused in bucketed mode
            offset = 0 if pi is None else cap
            pos = {i: j for j, i in enumerate(new_idx)}
            sel = [
                m if m is not None else offset + inverse[pos[i]]
                for i, m in enumerate(ms)
            ]
            sel.extend([sel[-1]] * (cap - len(sel)))
            maps.append(np.asarray(sel, np.int32))
            schedule.append((key[0], key[1], "bucket", cap, K))
            prev_results.append(prev_res)
        return (tuple(schedule), tuple(prev_results), tuple(new_windows),
                tuple(new_sources), tuple(inits), tuple(maps), n_unique)

    def built():
        if bucketed:
            (schedule, prev_results, new_windows, new_sources, inits,
             maps_t, n_unique) = build_schedule_bucketed()
            return (schedule, prev_results, new_windows, new_sources,
                    inits, maps_t, False, n_unique)
        (schedule, prev_results, new_windows, new_sources, inits,
         any_warm, n_unique) = build_schedule()
        return (schedule, prev_results, new_windows, new_sources, inits,
                None, any_warm, n_unique)

    fields = (g.src, g.dst, g.t_start, g.t_end, g.weight)
    if mesh is not None:
        # identity-cached replication: the graph arrays transfer once per
        # (graph, mesh), and the fused step's input shardings are stable
        # from the first sharded advance
        fields = replicated_arrays(mesh, *fields)
    e_sh, d_sh = _mesh_shape(mesh)
    shard_tag = ("" if mesh is None
                 else f"@q{d_sh}" if e_sh == 1 else f"@e{e_sh}q{d_sh}")

    # ---- fused advance: ring slide + all solves + assembly, one dispatch --
    if p.method == "scan":
        (schedule, prev_results, new_windows, new_sources, inits,
         maps_t, any_warm, n_unique) = built()
        _note(f"fused:scan{shard_tag}")
        telemetry.end("serve.plan")
        with telemetry.span("serve.dispatch"):
            results, rounds = _call_donating(
                _fused_step_scan,
                fields, p, prev_results, new_windows, new_sources, inits,
                maps_t, n_vertices=g.n_vertices, schedule=schedule,
                mesh=mesh)
        return results, freeze(
            p, state.edges, -1, -1, 0, results, "reuse", total_new,
            any_warm, rounds, n_unique=n_unique, last_schedule=schedule)

    if p.method in ("index", "hybrid") and tger is not None:
        positions = (window_positions_host if p.method == "index"
                     else heavy_window_positions_host)
        lo_new, hi_new = positions(tger, union)
        # hybrid parity guard: the ring itself stays exact (its own
        # coverage is the hi-lo <= C check below), but the COLD
        # hybrid_view under this plan would truncate if some vertex's
        # in-window count outgrew the per-vertex budget — replan so parity
        # with `sweep` holds.  The TOTAL heavy count bounds every
        # per-vertex count, so the exact (O(H log E) host) check only runs
        # when that O(1) bound is inconclusive.
        if (p.method == "hybrid"
                and hi_new - lo_new > p.per_vertex_budget
                and per_vertex_window_budget(g, tger, union)
                > p.per_vertex_budget):
            return cold()
        shift = lo_new - state.lo
        C = state.capacity
        if shift < 0 or shift > C or hi_new - lo_new > C:
            # slid backwards or the ring no longer covers; the fallback
            # keeps the plan when it still covers (jit-cache stability)
            return cold(prev_plan=p)
        perm = (tger.perm_by_start if p.method == "index"
                else tger.heavy_perm_by_start)
        if mesh is not None:
            (perm,) = replicated_arrays(mesh, perm)
        (schedule, prev_results, new_windows, new_sources, inits,
         maps_t, any_warm, n_unique) = built()
        _note(f"fused:{p.method}{shard_tag}")
        # delta rung floored at C/8: at most four delta variants per
        # capacity ever compile, pinning the fused cache over long horizons
        delta_budget = min(max(rung(max(shift, 1)), C // 8), C)
        telemetry.end("serve.plan")
        with telemetry.span("serve.dispatch"):
            results, edges, rounds = _call_donating(
                _fused_step_ring,
                fields, perm, p, state.edges, prev_results, new_windows,
                new_sources, inits, maps_t,
                np.asarray([state.lo, lo_new, hi_new], np.int32),
                method=p.method, n_vertices=g.n_vertices, capacity=C,
                delta_budget=delta_budget, schedule=schedule, mesh=mesh)
        if coldstore is not None and p.method == "index":
            # compaction hook (§7.8): strictly AFTER the donated step has
            # returned — the positions this slide evicted
            # ([state.lo, lo_new)) seal host-side from the store's own
            # mirrors, so the fused dispatch path gains zero device work
            # and zero retraces
            coldstore.note_eviction(lo_new)
        return results, freeze(
            p, edges, lo_new, hi_new, C, results, "delta", total_new,
            any_warm, rounds, n_unique=n_unique, last_schedule=schedule)

    return cold()


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

_SERVE_COMBOS = (
    "supported serve_batch combinations — mesh: None | int D | (E, D) "
    "tuple | jax.sharding.Mesh; admission: None | 'bucketed' (composes "
    "with ANY mesh shape); warm_start=True only with admission=None; "
    "edge-sharded meshes (E > 1) require the index access method (a TGER "
    "index and access='auto'|'index' / an index plan=); coldstore= "
    "(tiered history, DESIGN.md §7.8) requires a TGER, and a below-"
    "horizon (cold/split tier) batch additionally requires admission="
    "None, warm_start=False, mesh=None"
)


def _history_tier(tger, union, state, coldstore, plan_arg, access) -> str:
    """Classify the batch union window against the cold store's hot
    horizon (DESIGN.md §7.8).  Returns ``"hot"`` when tiering is
    disengaged: no store, or a scan/hybrid access path — a scan view holds
    the full horizon (nothing is ever evicted) and the hybrid ring
    re-rungs on coverage lapse, so only index plans have a below-horizon
    failure mode to route.  The carried chain's OWN ring low watermark is
    the authoritative horizon when a compatible hot state is passed: a
    forward-sliding chain stays hot even after another chain pushed the
    store's global watermark past its lo."""
    if coldstore is None:
        return "hot"
    if tger is None:
        raise ValueError(
            "coldstore serving requires a TGER index (the time-first "
            "permutation is the compaction domain); " + _SERVE_COMBOS)
    if access in ("scan", "hybrid") or (plan_arg is not None
                                        and plan_arg.method != "index"):
        return "hot"
    hot_lo = coldstore.watermark
    if (state is not None and state.lo >= 0
            and state.plan.method == "index" and state.plan.tier == "hot"):
        hot_lo = state.lo
    return coldstore.classify(union, hot_lo=hot_lo)


# the plan phase (grouping, tier, row matching, dedup, schedule; a cold
# serve's plan) opens with the call and ``_advance`` ends it where it
# dispatches
@telemetry.span("serve.plan")
def serve_batch(
    g: TemporalGraph,
    batch: QueryBatch,
    tger: Optional[TGERIndex] = None,
    *,
    state: Optional[SweepState] = None,
    access: str = "auto",
    backend: str = "xla_segment",
    plan: Optional[AccessPlan] = None,
    warm_start: bool = False,
    mesh: Optional[Any] = None,
    admission: Optional[str] = None,
    bucket_headroom: int = 0,
    coldstore=None,
    ladder: int = 0,
):
    """Serve a whole :class:`~repro.engine.queries.QueryBatch` — the
    multi-tenant entry point (DESIGN.md §7.4).

    Returns ``(results, state)``: ``results`` is a tuple with one entry
    per (algorithm, params) GROUP of the batch (first-appearance order,
    matching ``batch.groups()``), each a [Q_g, V] array (or tuple of
    arrays for the multi-output algorithms), rows in group row order.

    A steady-state advance — same batch shape, windows slid forward — is
    ONE jitted dispatch no matter how many tenants the batch carries: the
    fused step scatters only the entering time-first range into the
    donated ring view, solves only the genuinely-new rows of every group
    (identical (source, window) rows across tenants dedup to one solved
    row and fan out at assembly), and assembles all [Q, V] results in the
    same program.  Integer-label rows are BIT-identical to the
    corresponding cold single-query sweeps under the same plan; float
    rows match allclose.

    ``mesh`` opts into SHARDED batch serving: pass a device count / a
    one-axis ``jax.sharding.Mesh`` (DESIGN.md §7.5) and every group's
    new-row axis partitions across the mesh devices — ring view and
    result rows replicated per device, each device solving only its
    contiguous row chunk under its own convergence loop, results gathered
    (constrained replicated) in the same program.  Pass an ``(E, D)``
    tuple (or a two-axis mesh) for the 2-D edge×query composition
    (DESIGN.md §7.7): the ring view itself shards into contiguous slot
    chunks over the ``E`` edge shards (delta scatter landing only on the
    owning shard) while rows still partition over the ``D`` query shards,
    with one collective per relaxation round combining the edge-partial
    reductions.  Either way the steady-state advance stays ONE fused
    dispatch per device; integer-label results remain row-bit-identical
    to the single-device engine (float rows cross a psum at E > 1 and
    compare allclose).  ``(1, D)`` normalizes to the exact 1-D program.
    Edge sharding requires the index access method (the ring IS the
    sharded structure), so E > 1 demands a TGER and ``access='auto'`` or
    ``'index'``.  A carried state is mesh-shape-bound: switching mesh (or
    toggling sharding) falls cold.

    ``admission="bucketed"`` opts into the §7.6 admission ladder the
    serving daemon drives: every group's result buffer is PADDED to its
    power-of-two :func:`~repro.engine.queries.bucket_capacity` (slice
    each group to its real row count — ``len(batch.groups()[key])`` —
    before reading), resident groups keep the carried state's schedule
    order (sticky ordering; results are returned in THIS batch's group
    order regardless), and row assignment rides dynamic gather maps so
    tenant churn inside a bucket is a jit-cache hit on the fused step.
    Bucketed admission COMPOSES with any mesh shape (bucket-aligned row
    partitions, §7.7); ``bucket_headroom`` (the daemon's EWMA arrival
    forecast) sizes buckets for the rows expected next tick so a
    forecasted burst admits without a rebucket.  ``warm_start`` remains
    unsupported under bucketed admission; unsupported combinations raise
    ``ValueError`` BEFORE any state is consumed (the donation contract:
    a carried state survives the error path untouched).

    ``coldstore`` (a :class:`~repro.core.coldstore.ColdStore`) opts into
    TIERED HISTORY (DESIGN.md §7.8).  Hot serving is unchanged except
    that every index advance/cold build seals the positions leaving the
    ring into the store — host-side, strictly after the donated step
    returns, so the steady state stays one fused dispatch with zero extra
    retraces.  A batch whose union window falls below the hot horizon
    (the carried ring's low watermark, or the store's global watermark
    when no hot state is carried) routes to the COLD TIER instead of
    consuming the hot chain: the window's ring view is stitched host-side
    from the compacted chunks (tier ``"cold"``, or ``"split"`` when the
    window straddles the horizon — cold prefix decoded, hot suffix
    mirror-gathered) and solved through the normal group machinery,
    row-bit-identical to a cold full-history index solve under the same
    plan.  The tier rides the plan signature, so tier switches fall cold
    without consuming donated state; repeated historical queries hit the
    noop path.  The cold tier supports only ``admission=None``,
    ``warm_start=False``, ``mesh=None`` (checked BEFORE any state is
    consumed); scan/hybrid access paths ignore the store (a scan view is
    never evicted; the hybrid ring re-rungs).

    A state from a different graph or an incompatible explicit ``plan``
    falls back to a cold serve (the mismatched state is NOT consumed).
    ``warm_start=True`` opts into the per-algorithm containment warm
    starts (EA/cc exact, reachability sound; refused elsewhere).

    ``ladder`` (DESIGN.md §7.9) sets the frontier-rung cap on the batch
    plan: HOST-LEVEL solves — the cold builds, tier stitches and
    admission solves — then run their fixpoints through the sparse
    frontier ladder (bit-identical results), while the fused steady-state
    advance keeps its dense one-dispatch program (the ladder never
    engages under a trace).  Edge-sharded plans (E > 1 meshes) ignore it
    — the sparse gather is per-shard local.  The value rides the plan
    cache key, so a chain keeps the ladder it cold-started with."""
    if admission not in (None, "bucketed"):
        raise ValueError(
            f"unknown admission mode {admission!r}; " + _SERVE_COMBOS)
    bucketed = admission == "bucketed"
    if bucketed and warm_start:
        raise ValueError(
            "admission='bucketed' with warm_start=True is unsupported: "
            "containment warm inits are exact-shape per new row and would "
            "retrace the bucketed step the ladder exists to pin; "
            + _SERVE_COMBOS)
    if not isinstance(batch, QueryBatch):
        batch = QueryBatch.make(batch)
    for spec in batch.specs:
        _algo(spec.algorithm)       # fail fast on unknown algorithms
    if mesh is not None and not isinstance(mesh, Mesh):
        if isinstance(mesh, (tuple, list)):
            mesh = serve_mesh(int(mesh[0]), int(mesh[1]))
        else:
            mesh = query_mesh(int(mesh))
    e_sh, _ = _mesh_shape(mesh)
    if e_sh > 1:
        # every check here fires BEFORE the carried state can be consumed
        # (donation only happens inside the fused dispatch): an error path
        # must leave the caller's state reusable
        if tger is None:
            raise ValueError(
                "an edge-sharded mesh (E > 1) requires a TGER index — the "
                "ring's slot chunks are the shard boundaries; "
                + _SERVE_COMBOS)
        if plan is not None and plan.method != "index":
            raise ValueError(
                f"an edge-sharded mesh (E > 1) requires an index plan, "
                f"got method={plan.method!r}; " + _SERVE_COMBOS)
        if access not in ("auto", "index"):
            raise ValueError(
                f"an edge-sharded mesh (E > 1) requires access='index', "
                f"got {access!r}; " + _SERVE_COMBOS)
        access = "index"
    groups = [
        (key, [r.source for r in rows],
         np.asarray([r.window for r in rows], np.int32))
        for key, rows in batch.groups().items()
    ]
    if state is not None and (
        state.graph_ref is not g.src
        or state.mesh != mesh
        or bool(state.group_caps) != bucketed
        or (plan is not None and plan.cache_key != state.plan.cache_key)
    ):
        state = None
    tier = _history_tier(tger, batch.union(), state, coldstore, plan, access)
    if tier != "hot":
        # every check fires BEFORE the carried state can be consumed
        if bucketed or warm_start or mesh is not None:
            raise ValueError(
                f"a below-horizon batch (tier={tier!r}) serves through "
                f"the cold tier, which supports only admission=None, "
                f"warm_start=False, mesh=None; " + _SERVE_COMBOS)
        access = "index"
        if state is not None and state.plan.tier != tier:
            # a tier switch never consumes the carried state: the cold
            # rebuild below starts fresh (the old chain's donated buffers
            # stay alive with the caller if they kept a reference)
            state = None
    order = None
    if bucketed and state is not None:
        # sticky group ordering: resident groups keep the carried state's
        # schedule position, new groups append in batch order — a tenant
        # retirement that changes which spec appears FIRST for an
        # algorithm must not permute the static schedule (that would
        # retrace the fused step under pure churn)
        rank = {k: i for i, k in enumerate(state.group_keys)}
        order = sorted(
            range(len(groups)),
            key=lambda i: (rank.get(groups[i][0], len(rank)), i))
        if order == list(range(len(groups))):
            order = None
        else:
            groups = [groups[i] for i in order]
    results, new_state = _advance(
        g, tger, groups, state,
        plan_arg=plan,
        plan_builder=lambda: plan_batch(
            g, tger, batch, access=access, backend=backend,
            shards=None if mesh is None else _mesh_shape(mesh),
            bucketed=bucketed, tier=tier, ladder=int(ladder)),
        warm_start=warm_start,
        mesh=mesh,
        bucketed=bucketed,
        bucket_headroom=bucket_headroom,
        coldstore=coldstore,
        tier=tier,
    )
    if order is not None:
        inv = [0] * len(order)
        for j, i in enumerate(order):
            inv[i] = j
        results = tuple(results[inv[i]] for i in range(len(inv)))
    return results, new_state


def sweep_incremental(
    g: TemporalGraph,
    source,
    windows,
    tger: Optional[TGERIndex] = None,
    *,
    algorithm: str = "earliest_arrival",
    state: Optional[SweepState] = None,
    access: str = "auto",
    backend: str = "xla_segment",
    plan: Optional[AccessPlan] = None,
    warm_start: bool = False,
    coldstore=None,
    ladder: int = 0,
    tiny_budget_gate: bool = False,
    **kwargs,
):
    """Serve ``windows`` reusing the previous sweep's :class:`SweepState` —
    the single-tenant (one algorithm, one source) wrapper over the same
    fused engine ``serve_batch`` drives.

    Returns ``(results, state)`` with ``results`` shaped exactly like
    :func:`sweep`.  Integer-label algorithms are BIT-identical to the cold
    execution under the same plan; float rows (pagerank) are numerically
    identical up to float reduction order.  Pass ``state=None`` (or a
    state from a different graph / source / algorithm / kwargs — the
    legacy single-tenant compatibility gate, under which a mismatched
    state is NOT consumed) for a cold start; pass the returned state back
    on the next advance.

    A steady-state advance (forward slide within the ring's capacity and
    delta rung) is ONE jitted dispatch (DESIGN.md §7.3).  Index AND hybrid
    plans delta-advance; scan plans reuse the full view untouched.

    ``warm_start=True`` explicitly opts into containment warm starts:
    EXACT for the default label-correcting EA (monotone min fixpoint) and
    for cc (hash-min labels), sound-but-not-bit-stable for reachability,
    and REFUSED (cold init, with ``state.warm_applied == False``) for
    pagerank, bfs, kcore, betweenness and for EA under ``visit_once`` —
    the unsound cases of DESIGN.md §7.2/§7.4.

    ``ladder`` (DESIGN.md §7.9) sets the frontier-rung cap on the sweep's
    plan: cold solves run through the sparse frontier ladder
    (bit-identical), the fused advance stays dense.  ``tiny_budget_gate=
    True`` opts into the calibrated crossover gate: when the plan's ring
    capacity is at or below :data:`TINY_BUDGET_RING` the chain serves
    COLD every sweep, statelessly (the returned state is ``None`` — no
    ring/companion buffers are built), instead of carrying the fused
    incremental state —
    BENCH part 2 row 1 measured the fused advance at 0.69x of a cold
    solve in that regime (per-advance fixed costs dominate at tiny
    budgets).  Off by default: the gate trades the one-dispatch contract
    for wall-clock, which soak tests and daemons asserting on dispatch
    counts must not inherit silently.
    """
    entry = _algo(algorithm)
    windows = np.asarray(windows, np.int32).reshape(-1, 2)
    params = tuple(sorted(kwargs.items()))
    if entry.source_free:
        src = None
    else:
        flat = np.asarray(source).reshape(-1)
        if flat.size != 1:
            raise ValueError(
                "serving rows take ONE source each (multi-seed source sets "
                "are not supported); submit separate per-source queries — "
                "e.g. a QueryBatch of one-source rows to serve_batch, whose "
                "rows are independent answers, not a joint multi-seed run")
        src = int(flat[0])
    key = (algorithm, params)
    groups = [(key, [src] * len(windows), windows)]

    # the legacy single-tenant gate: a state from a different single-tenant
    # stream (other algorithm / source / kwargs / graph / plan) is not
    # reused — and, critically, NOT consumed: only a reused state donates
    # its buffers to the fused step.
    reusable = (
        state is not None
        and state.group_keys == (key,)
        and state.graph_ref is g.src      # identity, pinned by the state ref
        and state.mesh is None            # sharded states belong to serve_batch
        and not state.group_caps          # bucketed states: padded buffers
        and all(s == src for s in state.group_sources[0])
        and (plan is None or plan.cache_key == state.plan.cache_key)
    )
    state = state if reusable else None
    union = (int(windows[:, 0].min()), int(windows[:, 1].max()))
    tier = _history_tier(tger, union, state, coldstore, plan, access)
    if tier != "hot":
        if warm_start:
            raise ValueError(
                f"a below-horizon sweep (tier={tier!r}) serves through "
                f"the cold tier, which refuses warm_start; " + _SERVE_COMBOS)
        access = "index"
        if state is not None and state.plan.tier != tier:
            state = None    # tier switches never consume the carried state
    if tiny_budget_gate and tier == "hot":
        p = plan if plan is not None else plan_query(
            g, tger, windows=windows, access=access, backend=backend,
            tier=tier, ladder=int(ladder))
        cap = p.ring_capacity or p.budget
        if p.method in ("index", "hybrid") and cap <= TINY_BUDGET_RING:
            # calibrated crossover (BENCH part 2): at tiny ring capacities
            # the per-advance fixed costs dominate and a STATELESS cold
            # solve wins — serve it directly under the pinned plan.  No
            # SweepState is built or returned (None): the gate re-fires on
            # every sweep of the chain, so carried ring/companion buffers
            # would be rebuilt dead weight, and the rebuild alone costs
            # more than the solve in this regime.
            _note("gate:tiny-budget")
            _note("cold:gated")
            return entry.batched(g, src, windows, tger, p, kwargs), None
    results, new_state = _advance(
        g, tger, groups, state,
        plan_arg=plan,
        plan_builder=lambda: plan_query(
            g, tger, windows=windows, access=access, backend=backend,
            tier=tier, ladder=int(ladder)),
        warm_start=warm_start,
        coldstore=coldstore,
        tier=tier,
    )
    return results[0], new_state


__all__ = [
    "sweep",
    "sweep_looped",
    "sweep_incremental",
    "serve_batch",
    "SweepState",
    "QueryBatch",
    "QuerySpec",
    "query_mesh",
    "sliding_windows",
    "fused_trace_count",
    "dispatch_log",
    "ALGORITHMS",
]
