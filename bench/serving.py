"""Drive the program's serving path with a traffic mix.

The built-in clients, one per traffic mode, each over
``repro.serve.engine.GraphBatchServer``:

- ``batch``: closed-loop ``advance`` of the whole tenant batch;
- ``history``: closed-loop time-travel ``advance`` of windows the ring has
  evicted into a ``ColdStore``.

A mode of its own is a file ``bench/modes/<mode>.py`` whose ``Client`` has
the same interface; one that only asks other requests can subclass
``BatchClient`` and name its own ``requests``.

A client's ``warm`` is set-up: it runs every program shape the timed
requests will use.  ``measure`` runs the window and returns the end-to-end
values and counters.  ``served`` gives the answers kept for the check, as
``(Ask, tuple of host arrays)`` pairs.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List, Tuple

import numpy as np

from bench import traffic as tr
from bench.deployment import DAY_S, Deployment, rng_for

clock = time.perf_counter


def _spec(ask: tr.Ask, pinned: bool = False):
    from repro.engine import QuerySpec

    return QuerySpec.make(ask.algorithm, ask.window, sources=ask.source,
                          pinned=pinned, **dict(ask.params))


def _batch(asks: List[tr.Ask]):
    from repro.engine import QueryBatch

    return QueryBatch.make([_spec(a) for a in asks])


def _rows(batch, asks, results) -> List[Tuple[tr.Ask, tuple]]:
    """Pair each row of an ``advance`` result with the request it answers
    (groups in ``batch.groups()`` order, rows in group order)."""
    out = []
    for gi, rows in enumerate(batch.groups().values()):
        r = results[gi] if gi < len(results) else ()
        arrays = r if isinstance(r, tuple) else (r,)
        for j, row in enumerate(rows):
            got = tuple(a[j] for a in arrays if j < len(a))
            out.append((asks[row.spec_index], got))
    return out


def _nbytes(results) -> int:
    return sum(a.nbytes for r in results
               for a in (r if isinstance(r, tuple) else (r,)))


class Reservoir:
    """A seeded uniform sample of ``k`` items from a stream of unknown
    length, plus the last item."""

    def __init__(self, k: int, seed: int):
        self.k, self.rng, self.n = k, rng_for(seed, 3), 0
        self.items: list = []
        self.last = None

    def offer(self, item) -> None:
        self.n += 1
        self.last = item
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(self.n))
            if j < self.k:
                self.items[j] = item

    def sample(self) -> list:
        out = [x for x in self.items if x is not self.last]
        return out + ([self.last] if self.last is not None else [])


class BatchClient:
    """Closed-loop advances of one tenant batch.  ``requests(mix, cols,
    seed)`` gives the requests: ``advance(k)``, ``max_advances`` and the
    ``stride`` of a stale answer."""

    requests = tr.Batch

    def __init__(self, dep: Deployment, mix: dict, seed: int, seconds: float,
                 spans):
        from repro.serve.engine import GraphBatchServer

        self.gen = self.requests(mix, dep.cols, seed)
        self.stale_shift = -self.gen.stride
        self.mix, self.spans = mix, spans
        self.server = GraphBatchServer(dep.graph, dep.tger,
                                       access=mix["access"])
        self.k = 0
        self.failed = 0
        self.last: List[Tuple[tr.Ask, tuple]] = []
        self.counters: Dict[str, list] = {"ea_rounds": [],
                                          "host_copy_bytes": []}

    def _advance(self):
        asks = self.gen.advance(self.k)
        self.k += 1
        batch = _batch(asks)
        with self.spans("advance"):
            results = self.server.advance(batch)
        return asks, batch, results

    def warm(self) -> None:
        for _ in range(int(self.mix["warm_advances"])):
            self._advance()

    def _ea_rounds(self):
        st = self.server.state
        rounds = st.last_rounds
        if not isinstance(rounds, (tuple, list)):
            rounds = (rounds,)
        for key, r in zip(st.group_keys, rounds):
            if key[0] == "earliest_arrival" and r is not None:
                n = int(np.asarray(r))
                return n if n >= 0 else None
        return None

    def measure(self, seconds: float) -> Dict[str, float]:
        t0 = clock()
        n, t_end = 0, t0
        while n == 0 or t_end - t0 < seconds:
            if self.k >= self.gen.max_advances:
                self.spans.note(f"the schedule ends after {self.k} advances")
                break
            try:
                asks, batch, results = self._advance()
            except Exception as e:          # a request that never answers
                self.failed += 1
                self.spans.note(f"advance {self.k - 1} failed: {e!r}")
                break
            t_end = clock()
            n += 1
            self.counters["host_copy_bytes"].append(_nbytes(results))
            r = self._ea_rounds()
            if r is not None:
                self.counters["ea_rounds"].append(r)
            self.last = _rows(batch, asks, results)
        self.attempted = n + self.failed
        self.completed = n
        return {"advance_s": (t_end - t0) / max(n, 1)}

    def served(self) -> List[Tuple[tr.Ask, tuple]]:
        return self.last

    def release(self) -> None:
        self.server = None
        gc.collect()


class HistoryClient:
    """Closed-loop time-travel queries through the cold tier."""

    def __init__(self, dep: Deployment, mix: dict, seed: int, seconds: float,
                 spans):
        from repro.core.coldstore import ColdStore
        from repro.serve.engine import GraphBatchServer

        self.gen = tr.History(mix, dep.cols, seed)
        self.warm_q, self.timed_q = self.gen.split_warm(dep.cols)
        self.stale_shift = -DAY_S
        self.spans = spans
        self.store = ColdStore(dep.graph, dep.tger,
                               chunk_slots=int(mix["chunk_slots"]))
        self.server = GraphBatchServer(dep.graph, dep.tger,
                                       access=mix["access"],
                                       coldstore=self.store)
        self.sample = Reservoir(int(mix["check_queries"]) - 1, seed)
        self.failed = 0
        self.counters: Dict[str, list] = {}

    def _query(self, asks):
        batch = _batch(asks)
        with self.spans("query"):
            results = self.server.advance(batch)
        return batch, results

    def warm(self) -> None:
        self._query(self.gen.hot_query())
        for asks in self.warm_q:
            self._query(asks)
            tier = self.server.state.plan.tier
            if tier != "cold":
                raise RuntimeError(f"a time-travel window served {tier!r}")

    def measure(self, seconds: float) -> Dict[str, float]:
        t0 = clock()
        n, t_end = 0, t0
        for asks in self.timed_q:
            if n and t_end - t0 >= seconds:
                break
            try:
                batch, results = self._query(asks)
            except Exception as e:
                self.failed += 1
                self.spans.note(f"query {n} failed: {e!r}")
                continue
            t_end = clock()
            n += 1
            self.sample.offer(_rows(batch, asks, results))
        self.attempted = n + self.failed
        self.completed = n
        self.counters["cold_chunks"] = [self.store.stats()["n_chunks"]]
        return {"time_travel_s": (t_end - t0) / max(n, 1)}

    def served(self) -> List[Tuple[tr.Ask, tuple]]:
        return [row for rows in self.sample.sample() for row in rows]

    def release(self) -> None:
        self.server = self.store = None
        gc.collect()


CLIENTS = {"batch": BatchClient, "history": HistoryClient}
