"""Shared by the CPU tests of the benchmark's cells: run a cell end to end
on a small deployment, and break the timed path underneath it."""
from __future__ import annotations

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import check, harness  # noqa: E402

SMALL = dict(n_vertices=3000, n_edges=60000, tger_degree_cutoff=75)


def small(cfg: dict) -> dict:
    """``cfg`` at the small size, its share of distinct pairs kept."""
    share = cfg["n_static_edges"] / cfg["n_edges"]
    return dict(cfg, **SMALL,
                n_static_edges=round(SMALL["n_edges"] * share))

# every cell the benchmark's files define, whether or not BENCHMARK.json
# lists it yet: (deployment, traffic mix)
CELLS = {
    "so-batch16-w35d": ("sx-stackoverflow", "batch16-w35d"),
    "wt-history-w7d": ("wiki-talk", "history-w7d"),
    "wt-batch16-w35d": ("wiki-talk", "batch16-w35d"),
}
E2E = {"batch": "advance_s", "history": "time_travel_s"}


def spec_with(workload: str) -> dict:
    """BENCHMARK.json, with ``workload`` and its end-to-end metric added
    where it does not list them."""
    spec = json.loads(json.dumps(harness.load_spec(ROOT)))
    if workload not in {w["name"] for w in spec["workloads"]}:
        config, mix = CELLS[workload]
        spec["workloads"].append({"name": workload, "config": config,
                                  "traffic": mix, "chips": 1, "why": "-"})
        name = E2E[harness.load_traffic(mix, ROOT)["mode"]]
        metric = next((m for m in spec["end_to_end"] if m["name"] == name),
                      None)
        if metric is None:
            metric = {"name": name, "unit": "s", "better": "lower",
                      "bound": 0.25, "source": "host_clock", "workloads": []}
            spec["end_to_end"].append(metric)
        metric["workloads"].append(workload)
    return spec


def small_config(workload: str) -> dict:
    return small(harness.load_config(CELLS[workload][0], ROOT))


def run(workload: str, seed: int = 7, **kw) -> dict:
    return harness.run(workload, seed, 0.5, False,
                       config=small_config(workload),
                       spec=spec_with(workload), **kw)


def _alter(results):
    """The first answer of the first group, changed where it is made."""
    first = results[0]
    arrays = list(first) if isinstance(first, tuple) else [first]
    a = arrays[0].copy()
    if np.issubdtype(a.dtype, np.floating):
        a[0] *= 1.5
    elif a.dtype == bool:
        a[0, 0] = ~a[0, 0]
    else:
        a[0, 0] ^= 1
    arrays[0] = a
    first = tuple(arrays) if isinstance(first, tuple) else arrays[0]
    return [first] + list(results[1:])


def _half(results):
    """Every group's second half of rows left out."""
    def cut(a):
        return a[: max(len(a) // 2, 1) - (1 if len(a) == 1 else 0)]
    return [tuple(cut(a) for a in r) if isinstance(r, tuple) else cut(r)
            for r in results]


def break_advance(monkeypatch, fault: str) -> None:
    """Break ``GraphBatchServer.advance``: ``unchanged`` answers every
    advance with the previous advance's rows, ``altered`` changes one
    answer, ``half`` leaves half of each group's rows out."""
    from repro.serve.engine import GraphBatchServer

    real = GraphBatchServer.advance
    prev = {}

    def advance(self, batch):
        out = real(self, batch)
        if fault == "unchanged":
            out, prev[id(self)] = prev.get(id(self), out), out
            return out
        return _alter(out) if fault == "altered" else _half(out)

    monkeypatch.setattr(GraphBatchServer, "advance", advance)


FAULTS = ("unchanged", "altered", "half")


def assert_sound(out: dict) -> None:
    assert out["correct"], out["checks"]
    assert out["checks"]["rows_differ"]["value"] == 0
    assert out["failed"] == 0 and out["attempted"] >= 1


def assert_control_fails(out: dict) -> None:
    ctrl = out["control_checks"]
    assert not check.passed(ctrl), ctrl
