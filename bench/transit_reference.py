"""The plain reference of a timetable deployment: Connection Scan
(Dibbelt, Pajor, Strasser, Wagner, ACM JEA 23, 2018) over the window's
connections.  Numpy and plain Python; nothing of the program.

A connection is a window edge ``(src, dst, t_start, t_end)``: it leaves
``src`` at ``t_start`` and reaches ``dst`` at ``t_end``, inside the query's
window ``[ta, tb]`` (``t_start >= ta`` and ``t_end <= tb``).

- Earliest arrival ("depart at ``ta`` from the source"): one pass over the
  connections in departure order; a connection whose stop is reached by
  its departure (``arr[src] <= t_start``) offers ``t_end`` at ``dst``.
  Unreached stops are ``INF`` (the int32 maximum).
- Latest departure ("arrive at the target by ``tb``"): one pass in
  descending arrival order; a connection that reaches a stop in time for
  the rest of the journey (``t_end <= ld[dst]``) offers ``t_start`` at
  ``src``.  Unreached stops are the int32 minimum.

One pass is exact because every connection takes at least a second: a
connection that can follow another departs at or after its arrival, so
after its departure, and comes later in departure order (earlier in
arrival order for the reverse pass).  ``solve`` checks this of the
window's connections.  Every other algorithm goes to the stock reference.
"""
from __future__ import annotations

import numpy as np

from bench import reference as stock
from bench.reference import INF
from bench.reference import window_edges  # noqa: F401  (the harness reads it)

NEG_INF = np.iinfo(np.int32).min


def earliest_arrival(we, source: int) -> np.ndarray:
    arr = [int(INF)] * we.n_vertices
    arr[source] = we.window[0]
    order = np.argsort(we.ts, kind="stable")
    for s, d, dep, end in zip(we.src[order].tolist(), we.dst[order].tolist(),
                              we.ts[order].tolist(), we.te[order].tolist()):
        if arr[s] <= dep and end < arr[d]:
            arr[d] = end
    return np.asarray(arr, np.int64)


def latest_departure(we, target: int) -> np.ndarray:
    ld = [int(NEG_INF)] * we.n_vertices
    ld[target] = we.window[1]
    order = np.argsort(-we.te, kind="stable")
    for s, d, dep, end in zip(we.src[order].tolist(), we.dst[order].tolist(),
                              we.ts[order].tolist(), we.te[order].tolist()):
        if end <= ld[d] and dep > ld[s]:
            ld[s] = dep
    return np.asarray(ld, np.int64)


def solve(we, algorithm: str, source, params: dict):
    """The reference answer of one served row, as a tuple of arrays."""
    if algorithm in ("earliest_arrival", "latest_departure"):
        if params:
            raise ValueError(f"no reference for {algorithm} with {params}")
        if not (we.te > we.ts).all():
            raise ValueError("a connection of the window takes no time: one "
                             "Connection Scan pass is not exact")
        scan = (earliest_arrival if algorithm == "earliest_arrival"
                else latest_departure)
        return (scan(we, source),)
    return stock.solve(we, algorithm, source, params)
