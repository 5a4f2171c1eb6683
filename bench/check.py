"""Decide ``correct``: the answers the timed path served against the plain
reference that the deployment names (its ``reference`` file, a module
``ref`` with ``window_edges`` and ``solve``), each number beside its limit.

- ``failed``: requests of the window that raised instead of answering.
- ``rows_differ``: rows of every algorithm but PageRank (integer or boolean)
  that are not bit-identical to the reference, plus rows of any algorithm
  that are missing or misshapen.  The answers are exact, so the limit is 0.
- ``pagerank_l1``: over the PageRank rows, the largest
  ``sum|served - reference| / sum|reference|``.  The program iterates in
  float32 and the reference in float64; the limit lies between the
  program's readings and those of the bfloat16 control (``PERF.md``).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench.traffic import Ask


def reference_rows(asks: List[Ask], cols, ref,
                   solve: Optional[Callable] = None) -> Dict[Ask, tuple]:
    """The reference answer of every distinct request, one window's edges
    selected once."""
    solve = solve or (lambda we, a: ref.solve(we, a.algorithm, a.source,
                                              dict(a.params)))
    out: Dict[Ask, tuple] = {}
    by_window: Dict[Tuple[int, int], List[Ask]] = {}
    for a in asks:
        by_window.setdefault(a.window, []).append(a)
    for window, group in by_window.items():
        we = ref.window_edges(cols, window)
        for a in group:
            if a not in out:
                out[a] = solve(we, a)
    return out


def control_rows(served: List[Tuple[Ask, tuple]], cols, shift: int,
                 ref) -> List[Tuple[Ask, tuple]]:
    """The control in the program's place: PageRank rows from the
    bfloat16 iteration, every other row the reference's answer for the
    request's window moved by ``shift`` seconds (a stale answer)."""
    asks = [a for a, _ in served]
    low = reference_rows(
        [a for a in asks if a.algorithm == "pagerank"], cols, ref,
        solve=lambda we, a: (ref.pagerank_lowp(we, **dict(a.params)),))
    stale = reference_rows(
        [a.shifted(shift) for a in asks if a.algorithm != "pagerank"], cols,
        ref)
    return [(a, low[a] if a.algorithm == "pagerank" else stale[a.shifted(shift)])
            for a in asks]


def pagerank_gap(got: np.ndarray, want: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    gap = np.abs(got - want).sum() / np.abs(want).sum()
    return float(gap) if np.isfinite(gap) else float("inf")


def compare(served: List[Tuple[Ask, tuple]], want: Dict[Ask, tuple],
            limits: dict, failed: int) -> Dict[str, Dict[str, float]]:
    """The numbers compared, each ``{"value": v, "limit": l}``."""
    differ, gaps = 0, []
    for ask, got in served:
        w = want[ask]
        if len(got) != len(w) or any(np.shape(g) != np.shape(x)
                                     for g, x in zip(got, w)):
            differ += 1
        elif ask.algorithm == "pagerank":
            gaps.append(pagerank_gap(got[0], w[0]))
        elif not all(np.array_equal(np.asarray(g).astype(np.int64),
                                    np.asarray(x).astype(np.int64))
                     for g, x in zip(got, w)):
            differ += 1
    checks = {
        "failed": {"value": int(failed), "limit": 0},
        "rows_checked": {"value": len(served), "limit": 1},
        "rows_differ": {"value": differ, "limit": int(limits["rows_differ"])},
    }
    if gaps:
        if "pagerank_l1" not in limits:
            raise KeyError("this deployment has no pagerank_l1 limit: read "
                           "one on the chip before it serves PageRank")
        checks["pagerank_l1"] = {"value": max(gaps),
                                 "limit": float(limits["pagerank_l1"])}
    return checks


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    """Every number within its limit; ``rows_checked`` is a floor."""
    for name, c in checks.items():
        ok = (c["value"] >= c["limit"] if name == "rows_checked"
              else c["value"] <= c["limit"])
        if not ok:
            return False
    return True
