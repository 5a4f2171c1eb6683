"""Quickstart: build a temporal graph, index it, run temporal analytics.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import build_tger
from repro.engine import decision_for
from repro.core.algorithms import (
    earliest_arrival,
    temporal_cc,
    temporal_pagerank,
)
from repro.core.temporal_graph import from_edges


def main():
    # A small contact network: (who, whom, interval-start, interval-end)
    #   a=0 b=1 c=2 d=3 e=4 f=5 g=6  (cf. the paper's Figure 1)
    edges = [
        (0, 1, 1, 2), (1, 2, 3, 4), (2, 3, 5, 6),
        (0, 4, 2, 3), (4, 3, 4, 7), (3, 5, 8, 9),
        (5, 6, 10, 11), (1, 6, 2, 12),
    ]
    src, dst, ts, te = map(np.asarray, zip(*edges))
    g = from_edges(src, dst, ts, te)
    print(f"graph: {g.n_vertices} vertices, {g.n_edges} temporal edges")

    # TGER: time-first index + per-vertex histograms (selective: small cutoff
    # here so the demo actually indexes something)
    idx = build_tger(g, degree_cutoff=2)
    print(f"TGER built: {idx.n_indexed} vertices indexed")

    # cost-model access plan for a query window
    window = (0, 12)
    dec = decision_for(g, idx, window)
    print(f"window {window}: access={dec.method} "
          f"(selectivity {dec.selectivity:.2f}, budget {dec.budget})")

    # earliest arrival from vertex a (Algorithm 2)
    arr = np.asarray(earliest_arrival(g, 0, window))
    for v, t in enumerate(arr):
        label = chr(ord("a") + v)
        print(f"  earliest arrival a -> {label}: "
              f"{'unreachable' if t == np.iinfo(np.int32).max else t}")

    labels = np.asarray(temporal_cc(g, window))
    print("temporal components:", labels.tolist())

    pr = np.asarray(temporal_pagerank(g, window, n_iters=50))
    print("top vertex by temporal PageRank:", chr(ord("a") + int(pr.argmax())))


if __name__ == "__main__":
    main()
