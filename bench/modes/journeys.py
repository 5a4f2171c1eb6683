"""Journeys: the query stream of a journey planner.  Each closed-loop
advance asks ``queries`` journeys, the listed algorithms round-robin
(``earliest_arrival``: depart from a stop at ``d``; ``latest_departure``:
arrive at a stop by ``d + horizon_s``), each over its own window
``[d, d + horizon_s]``, ``d`` drawn uniformly in ``[t_now, t_now +
spread_s)``.  ``t_now`` starts at ``start_s`` and slides ``stride_s`` per
advance, so no row is ever asked twice.  Stops are drawn uniformly over
stop rank, as in the Connection Scan and RAPTOR query protocol.  The
stream is fixed by ``query_draw_seed``; the run's seed labels the stops
(as it labels the timetable), so every seed asks the same journeys of the
same timetable."""
from __future__ import annotations

import numpy as np

from bench import serving
from bench import traffic as tr


class Journeys:
    def __init__(self, mix: dict, cols, seed: int):
        self.mix = mix
        self.n = int(mix["queries"])
        self.horizon, self.spread = int(mix["horizon_s"]), int(mix["spread_s"])
        self.start, self.stride = int(mix["start_s"]), int(mix["stride_s"])
        self.max_advances = (cols.span_s - self.start - self.spread
                             - self.horizon) // self.stride + 1
        self.n_stops = cols.n_vertices
        self.rank_to_id = cols.rank_to_id

    def advance(self, k: int):
        if not 0 <= k < self.max_advances:
            raise IndexError(f"advance {k} runs past the end of the day")
        rng = np.random.default_rng([int(self.mix["query_draw_seed"]), k])
        departs = (self.start + k * self.stride
                   + rng.integers(0, self.spread, self.n))
        stops = self.rank_to_id[rng.integers(0, self.n_stops, self.n)]
        algs = self.mix["algorithms"]
        asks = []
        for i in range(self.n):
            alg = algs[i % len(algs)]
            asks.append(tr.Ask(
                alg, (int(departs[i]), int(departs[i]) + self.horizon),
                int(stops[i]), tr.ask_params(self.mix, alg)))
        return asks


class Client(serving.BatchClient):
    """``serving.BatchClient`` over journeys; also records the
    latest-departure group's fixpoint rounds per timed advance as the
    ``ld_rounds`` counter."""

    requests = Journeys

    def __init__(self, *args):
        super().__init__(*args)
        self.counters["ld_rounds"] = []

    def _ea_rounds(self):
        # called once per timed advance, after it has answered
        st = self.server.state
        for key, r in zip(st.group_keys, st.last_rounds):
            if key[0] == "latest_departure" and int(np.asarray(r)) >= 0:
                self.counters["ld_rounds"].append(int(np.asarray(r)))
        return super()._ea_rounds()
