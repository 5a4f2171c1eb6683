#!/usr/bin/env bash
# Tier-1 verification: exactly the command ROADMAP.md pins, from any cwd.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
# CI marker: the long-horizon serving soaks (tests/test_serving_soak.py:
# 220 -> 60 advances; tests/test_multitenant.py: 110 -> 36 advances;
# tests/test_daemon.py churn soak: 80 -> 24 ticks) are reduced under CI
# to bound wall clock.  GitHub Actions sets CI=true already; export it
# here so local ci.sh runs match.
export CI="${CI:-1}"
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q "$@"

# forced-multi-device leg: the query-mesh sharding paths (DESIGN.md §7.5)
# only exercise real device boundaries when XLA fakes >1 host device, so
# rerun the distributed + sharded-serving suites under a 4-device CPU
# backend.
XLA_FLAGS="--xla_force_host_platform_device_count=4" \
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python -m pytest -x -q tests/test_distributed.py tests/test_sharded_serving.py

# forced-8-device leg: the edge×query 2-D meshes (DESIGN.md §7.7) at
# mesh shapes (2,4) and (4,2) — both axes genuinely multi-device, which
# the 4-device leg above (max (2,2)) cannot produce.  Reuses the
# env-parameterized 2-D soak with CI-reduced advances.
SOAK2D_DEVICES=8 SOAK2D_MESHES="2x4,4x2" SOAK2D_STEPS=12 \
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python -m pytest -x -q tests/test_sharded_serving.py -k soak_2d

# the compaction soak at FULL length (DESIGN.md §7.8): tier-1 above runs
# tests/test_coldstore.py CI-reduced (COLD_SOAK=16); rerun the acceptance
# soak at the full 48 advances — one fused dispatch per advance, zero
# retraces after warmup, rows bit-identical to the compaction-off chain
# on EVERY advance, cold-store watermark tracking the ring's low
# watermark.
COLD_SOAK=48 \
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python -m pytest -x -q tests/test_coldstore.py -k compaction_soak

# smoke the serving daemon end to end (DESIGN.md §7.6): a short tick loop
# with Poisson tenant churn, bucketed async admission and cost-class
# round-robin — the launch-path wiring the daemon soak in tier-1 above
# (tests/test_daemon.py, CI-reduced) does not cover.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python -m repro.launch.serve --graph --daemon --ticks 8 --tenants 8 \
  --n-vertices 500 --n-edges 10000

# smoke the perf trajectory: gather-once vs re-gather + FUSED incremental
# sweeps + the multi-tenant 1/4/16-tenant queries-per-second regime + the
# sharded qps-vs-device-count chain + the async-admission daemon part
# (bucketed-vs-naive admission cost and Poisson p50/p99 — one-dispatch
# advances asserted against the dispatch-site log at every batch size and
# device count, result-identity asserted before timing; emits
# BENCH_fixpoint.json at the repo root, including the tiny-budget
# crossover regime and the part-2b gate check: the stateless
# tiny_budget_gate chain must not regress below the cold baseline)
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.run --quick --only fixpoint

# smoke the tiered-history part (DESIGN.md §7.8) at reduced sizes: the
# 48-advance compaction-on/off lockstep (identity asserted before timing,
# one-dispatch + zero-retrace asserted per advance) and the time-travel
# stitch vs cold full-history rebuild — merges part 7 into
# BENCH_fixpoint.json; plus the history-chunks launch wiring, once
# in-memory and once spilling sealed chunk payloads to memmap files
# (DESIGN.md §7.9 satellite: decodes must stay bit-identical off disk).
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.run --quick --only history
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python -m repro.launch.serve --graph --daemon --ticks 6 --tenants 6 \
  --n-vertices 500 --n-edges 10000 --history-chunks 512
SPILL_DIR="$(mktemp -d)"
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python -m repro.launch.serve --graph --tenants 6 --advances 8 \
  --n-vertices 500 --n-edges 10000 --history-chunks 512 \
  --history-spill-dir "$SPILL_DIR"
rm -rf "$SPILL_DIR"

# smoke the frontier-rung ladder part (DESIGN.md §7.9) at reduced sizes:
# the deep-transit laddered-vs-dense EA rows (bit-identity asserted
# BEFORE timing, zero retraces on repeated same-shape laddered solves
# asserted from the trace log) and the honest shallow power-law
# crossover row, plus the part-2b tiny-budget gate assertion inside the
# fixpoint leg above — merges part 8 into BENCH_fixpoint.json.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.run --quick --only frontier
