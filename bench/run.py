#!/usr/bin/env python3
"""Benchmark of the temporal-graph serving path on a TPU.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of the machine it is
started on: generates the deployment from the seed, has the program build
it, warms up every shape the cell's traffic uses, measures for
``--seconds``, checks the served answers against the plain reference and
prints one JSON object as the last line of standard output.  With
``--trace 0`` its metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  Without a TPU (or with fewer chips than the cell asks for) it
exits non-zero and prints no result.

JAX's compilation cache is kept in ``.jax_cache/`` at the root of the
checkout, so only the first run of a cell there compiles.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="write the trace here and keep it")
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import bench

    bench.prepare(ROOT)
    from bench import harness

    spec = harness.load_spec(ROOT)
    chips = harness.cell(spec, args.workload)["chips"]
    harness.device_info(chips)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    out = harness.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), trace_dir=args.keep_trace,
                      keep_trace=args.keep_trace is not None)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
