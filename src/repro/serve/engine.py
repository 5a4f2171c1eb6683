"""Batched serving engines.

``ServeEngine``: LM continuous batching.  Fixed-size slot array; each slot
holds one request's KV state and current length.  Each engine step decodes
every active slot in one fused ``decode_step``; finished slots (EOS or
max-tokens) are refilled from the queue via ``prefill`` into the slot's
cache rows.  This is the standard continuous-batching loop (vLLM-style
scheduling, KV in dense slots rather than paged blocks — paging is
block-table indirection inside the cache, orthogonal to the engine loop).

``GraphBatchServer``: the temporal-graph analogue.  One server holds the
moved-from ``SweepState`` of a :func:`repro.serve.serve_batch` advance
chain (ring-buffer edge view + donated result buffers), the query mesh
when the tenant axis is sharded across devices (DESIGN.md §7.5), and
running advance/dispatch stats.  It owns the donation contract so callers
don't have to: results handed out are host snapshots, safe to keep after
the next advance consumes the device buffers.

Since DESIGN.md §7.6 the graph server is also a long-lived DAEMON:
``submit``/``retire`` queue tenant churn asynchronously, and ``tick``
applies the pending admissions, rebuilds every live tenant's sliding
window at the tick's ``t_now``, and serves the instantaneous batch split
by COST CLASS — the cheap class every tick, the deep classes (pagerank,
betweenness, or any explicit ``cost_class=`` tag) round-robin one per
tick — each class on its own bucketed-admission advance chain, so
within-bucket churn is a jit-cache hit and a deep tenant's long fixpoint
never sits in the dispatch a cheap tenant's latency waits on.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.engine.queries import DEFAULT_COST_CLASS, QueryBatch, QuerySpec
from repro.models.transformer import LMConfig, decode_step, init_cache, prefill


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # [S] token ids
    max_new_tokens: int = 32
    generated: Optional[List[int]] = None


@dataclasses.dataclass
class EngineStats:
    steps: int = 0
    tokens_generated: int = 0
    requests_completed: int = 0


class ServeEngine:
    def __init__(self, params, cfg: LMConfig, batch_slots: int, max_seq: int,
                 eos_id: int = -1):
        self.params = params
        self.cfg = cfg
        self.slots = batch_slots
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.cache = init_cache(cfg, batch_slots, max_seq)
        self.lengths = np.zeros(batch_slots, np.int32)
        self.last_tokens = np.zeros(batch_slots, np.int32)
        self.budget = np.zeros(batch_slots, np.int32)       # remaining new tokens
        self.active: List[Optional[Request]] = [None] * batch_slots
        self.queue: Deque[Request] = deque()
        self.stats = EngineStats()
        self._decode = jax.jit(
            lambda p, c, t, l: decode_step(p, c, t, l, cfg)
        )

    # -- request management ---------------------------------------------------

    def submit(self, req: Request):
        req.generated = []
        self.queue.append(req)

    def _fill_slots(self):
        for s in range(self.slots):
            if self.active[s] is not None:
                continue
            while self.queue:
                req = self.queue.popleft()
                if req.max_new_tokens <= 0:
                    # zero-budget request: completes with no tokens — it
                    # never even prefills, and the slot stays free
                    self.stats.requests_completed += 1
                    continue
                prompt = jnp.asarray(req.prompt)[None, :]
                logits, pcache = prefill(self.params, prompt, self.cfg, max_seq=self.max_seq)
                # copy this request's cache rows into slot s
                for key in ("k", "v"):
                    self.cache[key] = self.cache[key].at[:, s].set(pcache[key][:, 0])
                tok = int(jnp.argmax(logits[0]))
                req.generated.append(tok)
                self.stats.tokens_generated += 1  # first token (from prefill)
                if req.max_new_tokens == 1:
                    # the prefill token IS the whole budget: finish at fill
                    # time — occupying a slot would run a decode step and
                    # emit a second token past max_new_tokens
                    self.stats.requests_completed += 1
                    continue
                self.active[s] = req
                self.lengths[s] = len(req.prompt)
                self.last_tokens[s] = tok
                self.budget[s] = req.max_new_tokens - 1
                break

    # -- engine loop ------------------------------------------------------------

    def step(self) -> int:
        """One decode step over all active slots; returns #active."""
        self._fill_slots()
        active_mask = np.array([r is not None for r in self.active])
        if not active_mask.any():
            return 0
        tokens = jnp.asarray(self.last_tokens)
        lengths = jnp.asarray(self.lengths)
        logits, self.cache = self._decode(self.params, self.cache, tokens, lengths)
        next_tokens = np.asarray(jnp.argmax(logits, axis=-1))

        for s in range(self.slots):
            req = self.active[s]
            if req is None:
                continue
            tok = int(next_tokens[s])
            req.generated.append(tok)
            self.lengths[s] += 1
            self.last_tokens[s] = tok
            self.budget[s] -= 1
            self.stats.tokens_generated += 1
            done = (
                tok == self.eos_id
                or self.budget[s] <= 0
                or self.lengths[s] >= self.max_seq - 1
            )
            if done:
                self.stats.requests_completed += 1
                self.active[s] = None
                self.lengths[s] = 0
        self.stats.steps += 1
        return int(active_mask.sum())

    def run(self, max_steps: int = 10_000) -> EngineStats:
        for _ in range(max_steps):
            if self.step() == 0 and not self.queue:
                break
        return self.stats


# ---------------------------------------------------------------------------
# Temporal-graph batch serving
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GraphServeStats:
    advances: int = 0
    cold_advances: int = 0
    rows_served: int = 0
    rows_solved: int = 0            # post-dedup rows actually solved
    dispatches: int = 0             # all dispatch-site hits (cold + fused)
    fused_dispatches: int = 0       # one per steady-state advance (per
                                    # device group, not per device)
    ticks: int = 0                  # daemon ticks served
    admissions: int = 0             # tenants admitted by the daemon
    retirements: int = 0            # tenants retired by the daemon


@dataclasses.dataclass(frozen=True)
class TickReport:
    """What one daemon tick did: the churn it applied, the cost classes it
    served, and host-snapshot per-tenant results for the SERVED classes
    (tenants whose deep class was skipped this round keep their previous
    answer — that is the round-robin contract)."""

    tick: int
    t_now: int
    classes_served: Tuple[str, ...]
    admitted: Tuple[int, ...]
    retired: Tuple[int, ...]
    results: Dict[int, Any]         # tenant id -> [n_rows, V] host rows
                                    # (tuple of arrays for multi-output)
    latency_s: float                # the tick's ``serve.tick`` span


class GraphBatchServer:
    """Continuous batch serving for temporal-graph queries.

    Two modes share the server.  The batch mode is one ``advance(batch)``
    call per tick: the whole (algorithm x source x window)
    :class:`~repro.engine.queries.QueryBatch` rides ONE ring advance and
    one fused dispatch (per device, when ``mesh`` shards the tenant axis —
    pass a device count, an ``(E, D)`` edge×query tuple, or a
    ``jax.sharding.Mesh``).  The server carries
    the single-use ``SweepState`` between ticks and snaps results to host
    arrays before handing them out, because the next advance DONATES the
    previous device buffers (DESIGN.md §7.3).  If an advance raises
    mid-flight the state is INVALIDATED (the fused step may already have
    consumed the donated buffers — a moved-from state must not be offered
    again), so the next advance runs cold instead of crashing on deleted
    buffers.

    The daemon mode (DESIGN.md §7.6) is ``submit``/``retire``/``tick``:
    tenants are long-lived sliding-window subscriptions, churn queues
    asynchronously and is applied at tick boundaries, and each tick serves
    the instantaneous batch split by COST CLASS — the cheap class every
    tick, deep classes round-robin one per tick — with each class chain
    running ``admission="bucketed"`` so within-bucket churn never
    retraces and never consumes donated state cold.  Daemon mode COMPOSES
    with the mesh (DESIGN.md §7.7): pass ``mesh=D`` or ``mesh=(E, D)``
    and every class chain serves bucketed AND sharded.  The daemon also
    tracks a per-cost-class EWMA of admission arrivals and passes a
    STICKY quantization of it as ``bucket_headroom``, so buckets are
    sized for the rows expected next tick — a forecasted burst admits
    without a single rebucket.  The applied headroom grows the moment
    the forecast does but shrinks only on a 4x forecast collapse (the
    ladder's own hysteresis rule): a raw ``ceil`` of the decaying EWMA
    would jitter by ±1-2 every tick and flap group capacities across
    bucket rungs, thrashing the very jit cache the ladder pins.

    Tenants submitted with ``pinned=True`` keep their historical window
    VERBATIM (``tick`` never re-anchors it) and serve every tick as the
    synthetic ``HISTORY_CLASS`` through the cold tier of the server's
    ``coldstore`` — unbucketed and unsharded, since a time-travel answer
    never rides the donated fused chain; the repeat serve of an unchanged
    pinned window is the noop host-cache path.
    """

    #: EWMA smoothing for the per-class admission arrival rate (rows/tick)
    #: and the safety factor headroom applies on top of the forecast.
    EWMA_ALPHA = 0.5
    HEADROOM_SAFETY = 2.0

    #: the synthetic scheduling class for pinned (time-travel) tenants —
    #: disjoint from every cost class, served every tick through the cold
    #: tier (unbucketed, unsharded: historical windows never re-anchor,
    #: so the repeat serve is the noop host-cache path)
    HISTORY_CLASS = "history"

    def __init__(self, graph, tger=None, *, access: str = "auto",
                 backend: str = "xla_segment", plan=None, mesh=None,
                 warm_start: bool = False, admission: Optional[str] = None,
                 coldstore=None):
        self.graph = graph
        self.tger = tger
        self.access = access
        self.backend = backend
        self.plan = plan
        self.mesh = mesh
        self.warm_start = warm_start
        self.admission = admission
        self.coldstore = coldstore
        self.state = None
        self.stats = GraphServeStats()
        # -- daemon registries (tick mode) ---------------------------------
        self._tenants: Dict[int, QuerySpec] = {}    # tid -> template spec
        self._pending_admit: Deque[Tuple[int, QuerySpec]] = deque()
        self._pending_retire: Deque[int] = deque()
        self._next_tid = 0
        self._class_states: Dict[str, Any] = {}     # cost class -> SweepState
        self._rr_last: Optional[str] = None         # deep-class round-robin:
                                                    # NAME last served (a bare
                                                    # counter into the live
                                                    # list skips/double-serves
                                                    # when a class empties)
        self._admit_ewma: Dict[str, float] = {}     # class -> rows/tick EWMA
        self._admit_hr: Dict[str, int] = {}         # class -> sticky headroom

    # -- batch mode ---------------------------------------------------------

    @telemetry.request("serve.advance")
    def advance(self, batch) -> List:
        """Serve one batch tick; returns host-snapshot per-group results
        (same grouping as :func:`repro.serve.serve_batch`).  One request of
        :mod:`repro.telemetry`: each result's wait for the device
        (``serve.wait``) is timed apart from its copy (``serve.copy``)."""
        from repro.serve import window_sweep as ws

        with ws.dispatch_log() as log:
            try:
                results, self.state = ws.serve_batch(
                    self.graph, batch, self.tger, state=self.state,
                    access=self.access, backend=self.backend, plan=self.plan,
                    warm_start=self.warm_start, mesh=self.mesh,
                    admission=self.admission, coldstore=self.coldstore)
            except BaseException:
                # the donation contract (DESIGN.md §7.3): the fused step
                # may have consumed the state's buffers before raising, so
                # the carried state is moved-from either way — drop it and
                # let the retry run cold rather than reuse donated buffers
                self.state = None
                raise
        snapped = []
        for r in results:
            # in group order, so a cold serve's copy of one group overlaps
            # the next group's solve
            with telemetry.span("serve.wait"):
                jax.block_until_ready(r)
            with telemetry.span("serve.copy"):
                snapped.append(tuple(np.asarray(x) for x in r)
                               if isinstance(r, tuple) else np.asarray(r))
        self.stats.advances += 1
        if self.state.last_advance == "cold":
            self.stats.cold_advances += 1
        self.stats.rows_served += int(batch.n_rows)
        self.stats.rows_solved += int(self.state.n_solved_unique)
        self.stats.dispatches += len(log)
        self.stats.fused_dispatches += sum(
            1 for t in log if t.startswith("fused:"))
        return snapped

    # -- daemon mode (DESIGN.md §7.6) ---------------------------------------

    def submit(self, spec: QuerySpec) -> int:
        """Queue a tenant for ASYNC admission; returns its tenant id.  The
        spec is a template: its window's WIDTH is the subscription, the
        bounds re-anchor to every tick's ``t_now``.  Admission happens at
        the next ``tick`` — submitting never replans, retraces, or touches
        device state."""
        tid = self._next_tid
        self._next_tid += 1
        self._pending_admit.append((tid, spec))
        return tid

    def retire(self, tid: int) -> None:
        """Queue a tenant for retirement at the next ``tick`` (unknown or
        already-retired ids are ignored there)."""
        self._pending_retire.append(tid)

    @property
    def tenants(self) -> Dict[int, QuerySpec]:
        """The LIVE tenant registry (admitted, not retired) — a copy."""
        return dict(self._tenants)

    def _class_of(self, spec: QuerySpec) -> str:
        """The daemon scheduling class for one spec: pinned time-travel
        tenants collapse into HISTORY_CLASS regardless of algorithm cost
        (their serve is a cold-tier solve, not a fused-chain advance);
        everything else keeps its cost class."""
        return self.HISTORY_CLASS if spec.pinned else spec.resolved_cost_class

    def _next_deep(self, deep: List[str]) -> str:
        """Round-robin over the LIVE deep classes by NAME.  A bare counter
        indexed into the (shrinking) class list skips or double-serves a
        surviving class for a lap when another class empties mid-rotation;
        tracking the last-served name and taking its successor in sorted
        order keeps the rotation fair under churn."""
        order = sorted(deep)
        if self._rr_last in order:
            nxt = order[(order.index(self._rr_last) + 1) % len(order)]
        else:
            # the last-served class emptied (or this is the first deep
            # tick): resume at the first live class AFTER it in sort
            # order, wrapping — survivors neither skip nor double-serve
            nxt = order[0]
            if self._rr_last is not None:
                for c in order:
                    if c > self._rr_last:
                        nxt = c
                        break
        self._rr_last = nxt
        return nxt

    def bucket_headroom(self, cls: str) -> int:
        """The arrival-rate bucket headroom for one cost class: the
        extra rows the class's buckets reserve for tenants expected to
        arrive before the next serve (DESIGN.md §7.7).  This is the
        STICKY value maintained by ``tick`` — it tracks
        ``ceil(EWMA rate * safety)`` upward immediately but downward
        only on a 4x forecast collapse, so a decaying EWMA cannot
        jitter group capacities across bucket rungs."""
        return self._admit_hr.get(cls, 0)

    @telemetry.span("serve.class")
    def _serve_class(self, cls: str, sub: QueryBatch, tids: List[int],
                     results: Dict[int, Any]) -> None:
        from repro.serve import window_sweep as ws

        # the history class serves pinned windows through the cold tier,
        # which refuses bucketed admission and the query mesh (its results
        # never ride the donated fused chain) — the hot classes keep both,
        # and every class carries the coldstore so hot advances compact
        history = cls == self.HISTORY_CLASS
        with ws.dispatch_log() as log:
            try:
                res, st = ws.serve_batch(
                    self.graph, sub, self.tger,
                    state=self._class_states.get(cls),
                    access=self.access, backend=self.backend,
                    plan=self.plan,
                    admission=None if history else "bucketed",
                    mesh=None if history else self.mesh,
                    bucket_headroom=0 if history else self.bucket_headroom(cls),
                    coldstore=self.coldstore)
            except BaseException:
                self._class_states.pop(cls, None)   # moved-from: force-cold
                raise
        self._class_states[cls] = st
        self.stats.advances += 1
        if st.last_advance == "cold":
            self.stats.cold_advances += 1
        self.stats.rows_served += int(sub.n_rows)
        self.stats.rows_solved += int(st.n_solved_unique)
        self.stats.dispatches += len(log)
        self.stats.fused_dispatches += sum(
            1 for t in log if t.startswith("fused:"))
        # host-snapshot per tenant, sliced to the group's REAL rows (the
        # bucketed buffers are padded to the bucket capacity)
        for gi, (key, rows) in enumerate(sub.groups().items()):
            r = res[gi]
            host = tuple(
                np.asarray(x)
                for x in (r if isinstance(r, tuple) else (r,)))
            per_spec: Dict[int, List[int]] = {}
            for j, row in enumerate(rows):
                per_spec.setdefault(row.spec_index, []).append(j)
            for si, row_ids in per_spec.items():
                picked = tuple(h[row_ids] for h in host)
                results[tids[si]] = (
                    picked[0] if len(picked) == 1 else picked)

    def tick(self, t_now: int) -> TickReport:
        """One daemon tick: apply pending churn, re-anchor every live
        tenant's window to end at ``t_now``, and serve the instantaneous
        batch by cost class (cheap every tick, deep classes round-robin
        one per tick).  Returns a :class:`TickReport`; served tenants'
        results are host snapshots sliced to their real rows."""
        with telemetry.request("serve.tick") as root:
            admitted: List[int] = []
            arrived: Dict[str, int] = {}    # cost class -> rows admitted NOW
            while self._pending_admit:
                tid, spec = self._pending_admit.popleft()
                self._tenants[tid] = spec
                admitted.append(tid)
                self.stats.admissions += 1
                cls = self._class_of(spec)
                arrived[cls] = arrived.get(cls, 0) + max(1, len(spec.sources))
            retired: List[int] = []
            while self._pending_retire:
                tid = self._pending_retire.popleft()
                if self._tenants.pop(tid, None) is not None:
                    retired.append(tid)
                    self.stats.retirements += 1
            # stale-forecast flush (the empty-class bug): a class whose last
            # tenant retired must NOT keep its EWMA/headroom entries — a
            # re-admission after a quiet gap would inherit the old sticky
            # headroom and oversize its first bucket.  Classes arriving THIS
            # tick keep theirs (retire-and-replace churn is the learned rate).
            live_now = {self._class_of(s) for s in self._tenants.values()}
            for cls in list(self._admit_ewma):
                if cls not in live_now and cls not in arrived:
                    self._admit_ewma.pop(cls, None)
                    self._admit_hr.pop(cls, None)
            # arrival-rate EWMA (rows/tick) per cost class: decays every tick,
            # spikes on bursts — bucket_headroom() reads it so the class's
            # buckets are already sized when the NEXT burst lands
            for cls in set(self._admit_ewma) | set(arrived):
                prev = self._admit_ewma.get(cls, 0.0)
                self._admit_ewma[cls] = (
                    (1.0 - self.EWMA_ALPHA) * prev
                    + self.EWMA_ALPHA * arrived.get(cls, 0))
                # sticky headroom: grow on a higher forecast NOW (the next
                # burst is what the headroom exists for), shrink only when
                # the forecast collapses 4x (the ladder's hysteresis rule) —
                # a raw ceil of the decaying EWMA would flap capacities
                want = int(np.ceil(self._admit_ewma[cls]
                                   * self.HEADROOM_SAFETY))
                held = self._admit_hr.get(cls, 0)
                if want > held or want < held // 4:
                    self._admit_hr[cls] = want
            self.stats.ticks += 1
            tick_no = self.stats.ticks
            results: Dict[int, Any] = {}
            classes_served: Tuple[str, ...] = ()
            if self._tenants:
                # the instantaneous batch: every live tenant's window slid
                # to end at t_now (width preserved from the submitted
                # template) — EXCEPT pinned tenants, whose historical window
                # is the whole point: it serves verbatim through the cold
                # tier every tick
                tids_all: List[int] = []
                specs: List[QuerySpec] = []
                for tid, spec in self._tenants.items():
                    if spec.pinned:
                        specs.append(spec)
                    else:
                        width = int(spec.window[1]) - int(spec.window[0])
                        specs.append(dataclasses.replace(
                            spec, window=(int(t_now) - width, int(t_now))))
                    tids_all.append(tid)
                by_cls: Dict[str, List[int]] = {}
                for i, spec in enumerate(specs):
                    by_cls.setdefault(self._class_of(spec), []).append(i)
                serve_now = [c for c in by_cls
                             if c in (DEFAULT_COST_CLASS, self.HISTORY_CLASS)]
                deep = [c for c in by_cls
                        if c not in (DEFAULT_COST_CLASS, self.HISTORY_CLASS)]
                if deep:
                    serve_now.append(self._next_deep(deep))
                for cls in serve_now:
                    idxs = by_cls[cls]
                    sub = QueryBatch.make([specs[i] for i in idxs])
                    self._serve_class(cls, sub, [tids_all[i] for i in idxs],
                                      results)
                classes_served = tuple(serve_now)
        return TickReport(
            tick=tick_no, t_now=int(t_now), classes_served=classes_served,
            admitted=tuple(admitted), retired=tuple(retired),
            results=results, latency_s=root.duration)

    @property
    def devices(self) -> int:
        return 1 if self.state is None or self.state.mesh is None else (
            self.state.mesh.size)
