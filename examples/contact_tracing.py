"""Contact tracing over a temporal interaction graph (paper Table 1:
epidemiology / temporal minimal paths).

Builds a bursty synthetic contact network, finds everyone reachable from a
patient-zero within an exposure window (earliest arrival = earliest possible
infection time), ranks super-spreaders by temporal betweenness, and shows
the selective-indexing decision flipping between scan and TGER as the
window narrows.

  PYTHONPATH=src python examples/contact_tracing.py
"""
import numpy as np

from repro.core import build_tger
from repro.engine import decision_for, make_plan
from repro.core.algorithms import earliest_arrival, temporal_betweenness
from repro.core.selective import CostModel
from repro.data.generators import power_law_temporal_graph

INT_INF = np.iinfo(np.int32).max


def main():
    g = power_law_temporal_graph(2000, 60_000, seed=7)
    idx = build_tger(g, degree_cutoff=256)
    ts = np.asarray(g.t_start)
    t_max = int(np.asarray(g.t_end).max())
    patient_zero = int(np.argmax(np.asarray(g.out_degree)))
    print(f"contact network: {g.n_vertices} people, {g.n_edges} contacts, "
          f"{idx.n_indexed} hubs TGER-indexed; patient zero = {patient_zero}")

    for frac, label in [(1.0, "full history"), (0.05, "last 5% of time")]:
        lo = int(np.quantile(ts, 1 - frac))
        window = (lo, t_max)
        dec = decision_for(g, idx, window, CostModel())
        plan = make_plan(dec.method,
                         budget=dec.budget if dec.method == "index" else 0)
        arr = np.asarray(
            earliest_arrival(g, patient_zero, window, idx, plan=plan)
        )
        exposed = (arr < INT_INF).sum()
        print(f"[{label}] access={plan.method:5s} "
              f"(sel {dec.selectivity:.3f})  exposed={exposed} people")

    # super-spreader ranking over the recent window
    lo = int(np.quantile(ts, 0.8))
    sources = np.argsort(np.asarray(g.out_degree))[-4:].astype(np.int32)
    bc = np.asarray(temporal_betweenness(g, sources, (lo, t_max), n_buckets=64))
    top = np.argsort(bc)[-5:][::-1]
    print("top-5 temporal-betweenness hubs (recent window):")
    for v in top:
        print(f"  person {int(v):5d}  centrality {bc[v]:.1f}")


if __name__ == "__main__":
    main()
