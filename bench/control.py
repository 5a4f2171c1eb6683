#!/usr/bin/env python3
"""Read the control of a cell on the chip, beside the program's own
numbers: the plain reference put in the program's place, PageRank in
bfloat16 and every integer row answered for a stale window.  The control
has to come out as not correct; ``PERF.md`` sets each limit between the
program's readings and the control's.  The benchmark's runs never run it.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

Prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import bench

    bench.prepare(ROOT)
    from bench import harness
    from repro.compile_cache import enable_compile_cache

    harness.device_info(harness.cell(harness.load_spec(ROOT),
                                     args.workload)["chips"])
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run(args.workload, seed, args.seconds, False,
                          control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"],
                          "control_checks": out["control_checks"],
                          "metrics": out["metrics"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
