"""The general traffic generators.  A mix is a data file,
``bench/traffic/<mix>.json``, whose ``mode`` names one of the generators
below (or a mode of its own, ``bench/modes/<mode>.py``) and whose other
keys are its parameters.  Vertices are drawn by rank and labelled as the
run's seed labels the graph (``Columns.rank_to_id``), so one seed gives
one sequence of requests.

A request is an :class:`Ask`: an algorithm, a window ``(ta, tb)`` in
seconds, a source vertex (None for source-free algorithms) and the
algorithm's parameters.  Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from bench.deployment import DAY_S, Columns, rng_for, zipf_cdf, zipf_draw

SOURCE_FREE = ("cc", "pagerank", "kcore")


@dataclasses.dataclass(frozen=True)
class Ask:
    algorithm: str
    window: Tuple[int, int]
    source: Optional[int]
    params: Tuple[Tuple[str, object], ...] = ()

    def shifted(self, dt: int) -> "Ask":
        return dataclasses.replace(
            self, window=(self.window[0] + dt, self.window[1] + dt))


def ask_params(mix: dict, algorithm: str) -> Tuple[Tuple[str, object], ...]:
    """The mix's parameters of ``algorithm``, as an ``Ask`` holds them."""
    return tuple(sorted(mix.get("params", {}).get(algorithm, {}).items()))


class Batch:
    """Closed-loop batch advances: ``tenants`` tenants take the algorithms
    round-robin, each over a window of ``window_span_fraction`` of the time
    axis; every other tenant lags one stride (window / ``strides_per_window``)
    behind.  Advance ``k`` slides the whole batch ``k`` strides forward
    from ``start_span_fraction`` of the span.  Each tenant keeps one source,
    drawn Zipf(``source_zipf_alpha``) over vertex rank from the fixed
    stream ``source_draw_seed``; the run's seed labels it (as it labels the
    graph), so every seed asks the same questions of the same graph."""

    def __init__(self, mix: dict, cols: Columns, seed: int):
        self.mix = mix
        self.width = int(cols.span_s * float(mix["window_span_fraction"]))
        self.stride = self.width // int(mix["strides_per_window"])
        self.base0 = int(cols.span_s * float(mix["start_span_fraction"]))
        self.last = cols.span_s
        ranks = zipf_draw(np.random.default_rng(int(mix["source_draw_seed"])),
                          zipf_cdf(cols.n_vertices,
                                   float(mix["source_zipf_alpha"])),
                          int(mix["tenants"]))
        self.sources = [int(cols.rank_to_id[r]) for r in ranks]

    @property
    def max_advances(self) -> int:
        return (self.last - self.base0) // self.stride + 1

    def advance(self, k: int) -> List[Ask]:
        if not 0 <= k < self.max_advances:
            raise IndexError(f"advance {k} runs past the end of the graph")
        algs = self.mix["algorithms"]
        base = self.base0 + k * self.stride
        asks = []
        for i in range(int(self.mix["tenants"])):
            alg = algs[i % len(algs)]
            end = base - (i % 2) * self.stride
            asks.append(Ask(
                alg, (end - self.width, end),
                None if alg in SOURCE_FREE else self.sources[i],
                ask_params(self.mix, alg)))
        return asks


class History:
    """Time-travel queries in a closed loop.  The ring holds the newest
    ``hot_days``; each query asks the listed algorithms over one
    ``window_days`` window that ends at a second drawn uniformly from the
    evicted history, no end drawn twice, from a source drawn
    Zipf(``source_zipf_alpha``) over vertex rank.  Ends and sources are
    drawn from the run's seed."""

    def __init__(self, mix: dict, cols: Columns, seed: int):
        self.mix = mix
        rng = rng_for(seed, 2)
        self.width = int(mix["window_days"]) * DAY_S
        self.hot = (cols.span_s - int(mix["hot_days"]) * DAY_S, cols.span_s)
        lo, hi = self.width, self.hot[0] - DAY_S
        n = int(mix["query_pool"])
        ends = lo + rng.choice(hi - lo, size=n, replace=False)
        srcs = cols.rank_to_id[zipf_draw(
            rng, zipf_cdf(cols.n_vertices, float(mix["source_zipf_alpha"])),
            n)]
        self.hot_source = int(cols.rank_to_id[0])
        self.pool = [self._query((int(e) - self.width, int(e)), int(s))
                     for e, s in zip(ends, srcs)]

    def _query(self, window, source) -> List[Ask]:
        return [Ask(a, window, None if a in SOURCE_FREE else source,
                    ask_params(self.mix, a))
                for a in self.mix["algorithms"]]

    def hot_query(self) -> List[Ask]:
        """The advance that fills the ring with the newest days and evicts
        the rest into the cold store."""
        return self._query(self.hot, self.hot_source)

    def split_warm(self, cols: Columns) -> Tuple[List[List[Ask]],
                                                   List[List[Ask]]]:
        """(warm-up queries, timed queries): the ``warm_queries`` windows
        of the pool holding the most edges go to the warm-up, so that the
        timed queries find the program's plan for the largest window
        already built; the rest keep the pool's order."""
        ts = np.sort(cols.t_start)
        wins = np.asarray([q[0].window for q in self.pool], ts.dtype)
        counts = (np.searchsorted(ts, wins[:, 1], side="right")
                  - np.searchsorted(ts, wins[:, 0]))
        k = int(self.mix["warm_queries"])
        top = set(np.argsort(counts, kind="stable")[::-1][:k].tolist())
        warm = [q for i, q in enumerate(self.pool) if i in top]
        timed = [q for i, q in enumerate(self.pool) if i not in top]
        return warm, timed


GENERATORS = {"batch": Batch, "history": History}
