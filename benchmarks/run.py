"""Benchmark entry point: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --quick    # reduced sizes
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", type=str, default=None,
                    help="comma list: table4,fig7,fig8,fig9,plans,sweep,"
                         "fixpoint,multitenant,mesh2d,history,frontier,"
                         "estimator,roofline "
                         "(multitenant regenerates only BENCH_fixpoint.json "
                         "parts 3/4 — multi-tenant qps + sharded devices; "
                         "mesh2d regenerates only part 6 — the edge×query "
                         "2-D mesh scaling table; history regenerates only "
                         "part 7 — tiered-history compaction + time-travel; "
                         "frontier regenerates only part 8 — the "
                         "frontier-rung ladder deep/crossover rows)")
    args = ap.parse_args()
    wanted = set(args.only.split(",")) if args.only else None
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    def want(name):
        return wanted is None or name in wanted

    print("name,us_per_call,derived")

    if want("table4"):
        from benchmarks import bench_scalability
        sizes = ((2_000, 50_000),) if args.quick else ((5_000, 100_000), (20_000, 1_000_000))
        bench_scalability.run(sizes=sizes, n_sources=4 if args.quick else 8)

    if want("fig8"):
        from benchmarks import bench_tger
        sizes = (100_000,) if args.quick else (100_000, 1_000_000, 4_000_000)
        bench_tger.run(sizes=sizes)

    if want("fig9"):
        from benchmarks import bench_selective
        if args.quick:
            bench_selective.run(n_v=5_000, n_e=200_000, fracs=(0.01, 0.1, 0.5))
        else:
            bench_selective.run()

    if want("plans"):
        from benchmarks import bench_selective
        if args.quick:
            bench_selective.run_plan_sweep(n_v=2_000, n_e=50_000, fracs=(0.01, 0.2))
        else:
            bench_selective.run_plan_sweep()

    if want("sweep"):
        from benchmarks import bench_sweep
        if args.quick:
            bench_sweep.run(n_v=2_000, n_e=50_000, counts=(4, 8), iters=2)
        else:
            bench_sweep.run()

    if want("fixpoint"):
        from benchmarks import bench_fixpoint
        if args.quick:
            # quick runs skip part 6 (one subprocess per (E, D) shape ×
            # regime is too slow for the CI smoke) and part 8 (deep
            # ~200-round fixpoints); --only mesh2d / --only frontier below
            # regenerate them at reduced sizes
            quick_parts = tuple(p for p in bench_fixpoint.PARTS
                                if p not in ("mesh2d", "frontier"))
            bench_fixpoint.run(n_v=2_000, n_e=50_000, W=6, advances=4, iters=2,
                               dev_counts=(1, 2), shard_steps=8,
                               shard_cands=96, daemon_ticks=12,
                               parts=quick_parts)
        else:
            bench_fixpoint.run()

    if wanted is not None and "multitenant" in wanted:
        # explicit-only (a full run already covers parts 3/4 via fixpoint):
        # regenerates multi-tenant qps + sharded device scaling; the JSON
        # merge keeps parts 1/2 from the last full run intact.
        from benchmarks import bench_fixpoint
        if args.quick:
            bench_fixpoint.run(n_v=2_000, n_e=50_000, W=6, advances=4, iters=2,
                               parts=("multi_tenant", "sharded"),
                               dev_counts=(1, 2), shard_steps=8,
                               shard_cands=96)
        else:
            bench_fixpoint.run(parts=("multi_tenant", "sharded"))

    if wanted is not None and "mesh2d" in wanted:
        # explicit-only (a full run already covers part 6 via fixpoint):
        # regenerates the edge×query 2-D mesh scaling table; the JSON
        # merge keeps the other parts intact.
        from benchmarks import bench_fixpoint
        if args.quick:
            bench_fixpoint.run(parts=("mesh2d",),
                               mesh2d_meshes=((1, 1), (2, 2), (1, 4)),
                               mesh2d_steps=6, mesh2d_cands=64)
        else:
            bench_fixpoint.run(parts=("mesh2d",))

    if wanted is not None and "history" in wanted:
        # explicit-only (a full run already covers part 7 via fixpoint):
        # regenerates the tiered-history section — the compaction-on/off
        # advance soak and the time-travel stitch vs rebuild timing; the
        # JSON merge keeps the other parts intact.
        from benchmarks import bench_fixpoint
        if args.quick:
            bench_fixpoint.run(n_v=2_000, n_e=50_000, parts=("history",),
                               history_steps=48, history_iters=3)
        else:
            bench_fixpoint.run(parts=("history",))

    if wanted is not None and "frontier" in wanted:
        # explicit-only (a full run already covers part 8 via fixpoint):
        # regenerates the frontier-rung ladder rows — the deep-transit
        # laddered-vs-dense speedup and the shallow power-law crossover;
        # the JSON merge keeps the other parts intact.
        from benchmarks import bench_fixpoint
        if args.quick:
            bench_fixpoint.run(parts=("frontier",), frontier_nv=1_024,
                               frontier_ne=8_192, frontier_iters=3)
        else:
            bench_fixpoint.run(parts=("frontier",))

    if want("estimator"):
        from benchmarks import bench_estimator
        if args.quick:
            bench_estimator.run(n_v=5_000, n_e=200_000, cutoffs=(128,))
        else:
            bench_estimator.run()

    if want("fig7"):
        from benchmarks import bench_scaling
        bench_scaling.run(dev_counts=(1, 2) if args.quick else (1, 2, 4, 8))

    if want("roofline"):
        from benchmarks import roofline
        roofline.run()


if __name__ == "__main__":
    main()
