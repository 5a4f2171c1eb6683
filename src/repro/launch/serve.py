"""Serving driver: continuous-batching engine over a (smoke-scale) LM, or
sliding-window temporal-graph batch serving.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
      --requests 16 --slots 4 --max-new 12

  # graph mode: multi-tenant QueryBatch advances on a synthetic graph;
  # --shard-queries N shards the tenant axis over N devices (use
  # XLA_FLAGS=--xla_force_host_platform_device_count=N on a 1-device host)
  PYTHONPATH=src python -m repro.launch.serve --graph --tenants 16 \
      --advances 24 --shard-queries 2

  # daemon mode (DESIGN.md §7.6): long-lived tick loop with Poisson tenant
  # arrivals/departures, bucketed async admission, cost-class round-robin
  PYTHONPATH=src python -m repro.launch.serve --graph --daemon \
      --ticks 40 --arrival-rate 0.5 --depart-rate 0.1
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import telemetry
from repro.compile_cache import enable_compile_cache
from repro.configs import get_arch
from repro.models import transformer as tf
from repro.serve.engine import GraphBatchServer, Request, ServeEngine


def run_graph(args) -> None:
    from repro.core.tger import build_tger
    from repro.data.generators import power_law_temporal_graph
    from repro.engine import QueryBatch, QuerySpec

    g = power_law_temporal_graph(args.n_vertices, args.n_edges,
                                 seed=args.seed)
    idx = build_tger(g, degree_cutoff=max(args.n_edges // 800, 16))
    ts = np.asarray(g.t_start)
    t_max = int(np.asarray(g.t_end).max())
    span = int(ts.max() - ts.min())
    width = max(span // 80, 1)
    stride = max(width // 8, 1)
    base0 = t_max - (args.advances + 2) * stride
    algs = ("earliest_arrival", "reachability", "bfs", "cc", "pagerank")

    def make_batch(base):
        specs = []
        for i in range(args.tenants):
            alg = algs[i % len(algs)]
            off = (i % 2) * stride
            win = (int(base - off - width), int(base - off))
            if alg == "cc":
                specs.append(QuerySpec.make(alg, win))
            elif alg == "pagerank":
                specs.append(QuerySpec.make(alg, win, n_iters=8))
            else:
                specs.append(QuerySpec.make(
                    alg, win, sources=(7 * i) % args.n_vertices))
        return QueryBatch.make(specs)

    mesh = args.shard_queries
    if args.shard_edges:
        mesh = (args.shard_edges, args.shard_queries or 1)
    coldstore = None
    if args.history_chunks:
        from repro.core.coldstore import ColdStore
        coldstore = ColdStore(g, idx, chunk_slots=args.history_chunks,
                              spill_dir=args.history_spill_dir)
    server = GraphBatchServer(g, idx, access="index",
                              mesh=None if coldstore is not None else mesh,
                              coldstore=coldstore)
    t0 = time.perf_counter()
    for k in range(args.advances):
        server.advance(make_batch(base0 + k * stride))
    dt = time.perf_counter() - t0
    s = server.stats
    rate = s.rows_served / max(dt, 1e-9)
    print(
        f"served {s.rows_served} query rows ({s.rows_solved} solved after "
        f"dedup) in {s.advances} advances ({s.cold_advances} cold, "
        f"{s.fused_dispatches} fused dispatches) on {server.devices} "
        f"device(s), {dt:.2f}s ({rate:.1f} rows/s)"
    )
    if coldstore is not None:
        # time-travel: query a window the sweep evicted long ago — it
        # serves from the compacted cold tier, not a full-history rebuild
        from repro.engine import QueryBatch as QB, QuerySpec as QS
        hist_base = int(ts.min()) + span // 8 + width
        hist = QB.make([
            QS.make("earliest_arrival", (hist_base - width, hist_base),
                    sources=1),
            QS.make("cc", (hist_base - width, hist_base)),
        ])
        t0 = time.perf_counter()
        server.advance(hist)
        dt_hist = time.perf_counter() - t0
        st = coldstore.stats()
        tier = server.state.plan.tier
        print(
            f"history: tier={tier!r} time-travel answered in "
            f"{1e3 * dt_hist:.1f} ms; cold store {st['n_chunks']} chunks "
            f"({st['sealed_slots']} slots sealed, watermark "
            f"{st['watermark']}), compaction {st['compaction_ratio']:.2f}x"
        )


def run_daemon(args) -> None:
    """The long-lived serving daemon (DESIGN.md §7.6): Poisson tenant
    arrivals/departures over all five cost-classed algorithms, async
    admission at tick boundaries, per-class bucketed advance chains."""
    from repro.core.tger import build_tger
    from repro.data.generators import power_law_temporal_graph
    from repro.engine import QuerySpec

    g = power_law_temporal_graph(args.n_vertices, args.n_edges,
                                 seed=args.seed)
    idx = build_tger(g, degree_cutoff=max(args.n_edges // 800, 16))
    ts = np.asarray(g.t_start)
    t_max = int(np.asarray(g.t_end).max())
    span = int(ts.max() - ts.min())
    width = max(span // 80, 1)
    stride = max(width // 8, 1)
    t_base = t_max - (args.ticks + 2) * stride
    algs = ("earliest_arrival", "reachability", "bfs", "cc", "pagerank")
    rng = np.random.default_rng(args.seed)

    def fresh_spec(i: int) -> QuerySpec:
        alg = algs[i % len(algs)]
        w = (0, width)
        if alg == "cc":
            return QuerySpec.make(alg, w)
        if alg == "pagerank":
            return QuerySpec.make(alg, w, n_iters=8)
        return QuerySpec.make(alg, w, sources=(7 * i) % args.n_vertices)

    mesh = args.shard_queries
    if args.shard_edges:
        mesh = (args.shard_edges, args.shard_queries or 1)
    coldstore = None
    if args.history_chunks:
        from repro.core.coldstore import ColdStore
        coldstore = ColdStore(g, idx, chunk_slots=args.history_chunks,
                              spill_dir=args.history_spill_dir)
        mesh = None     # the cold tier's history class is unsharded
    server = GraphBatchServer(g, idx, access="index", mesh=mesh,
                              coldstore=coldstore)
    live: list = []
    for i in range(args.tenants):            # the resident base load
        live.append(server.submit(fresh_spec(i)))
    n_spawned = args.tenants

    t0 = time.perf_counter()
    for k in range(args.ticks):
        rep = server.tick(t_base + k * stride)
        if coldstore is not None and k == args.ticks // 2:
            # mid-run, a pinned time-travel tenant arrives: its window is
            # fixed in the evicted past, served verbatim via the cold tier
            hist_lo = int(ts.min()) + span // 8
            live.append(server.submit(QuerySpec.make(
                "cc", (hist_lo, hist_lo + width), pinned=True)))
            n_spawned += 1
        for _ in range(rng.poisson(args.arrival_rate)):
            live.append(server.submit(fresh_spec(n_spawned)))
            n_spawned += 1
        for _ in range(rng.poisson(args.depart_rate)):
            if len(live) > 1:
                server.retire(live.pop(rng.integers(len(live))))
    dt = time.perf_counter() - t0

    s = server.stats
    lat = np.asarray([d for r in telemetry.recent(s.ticks)
                      for d in r.durations("serve.class")])
    print(
        f"daemon: {s.ticks} ticks, {s.advances} class advances "
        f"({s.cold_advances} cold, {s.fused_dispatches} fused), "
        f"{s.admissions} admissions / {s.retirements} retirements, "
        f"{s.rows_served} rows served in {dt:.2f}s"
    )
    if coldstore is not None:
        st = coldstore.stats()
        print(
            f"cold store: {st['n_chunks']} chunks, watermark "
            f"{st['watermark']}, compaction {st['compaction_ratio']:.2f}x"
        )
    print(
        f"class serve: p50 {1e3 * np.percentile(lat, 50):.2f} ms, "
        f"p99 {1e3 * np.percentile(lat, 99):.2f} ms "
        f"({len(server.tenants)} tenants live at exit)"
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--graph", action="store_true",
                    help="serve temporal-graph query batches instead of LM")
    ap.add_argument("--tenants", type=int, default=16)
    ap.add_argument("--advances", type=int, default=24)
    ap.add_argument("--n-vertices", type=int, default=2_000)
    ap.add_argument("--n-edges", type=int, default=50_000)
    ap.add_argument("--shard-queries", type=int, default=None,
                    help="shard the tenant axis over N devices")
    ap.add_argument("--shard-edges", type=int, default=None,
                    help="also shard the ring's slot axis over E devices "
                         "(forms an (E, D) edge-query mesh with "
                         "--shard-queries; needs E*D devices)")
    ap.add_argument("--history-chunks", type=int, default=None,
                    help="attach a cold store compacting evicted ring "
                         "slots into chunks of N slots; graph mode then "
                         "answers a time-travel query over an evicted "
                         "window, daemon mode admits a pinned historical "
                         "tenant mid-run (disables the mesh: the cold "
                         "tier is unsharded)")
    ap.add_argument("--history-spill-dir", default=None, metavar="DIR",
                    help="spill sealed cold-store chunk payloads to "
                         "memmap-backed files under DIR (needs "
                         "--history-chunks); decodes are bit-identical, "
                         "RAM holds only the chunk directory")
    ap.add_argument("--daemon", action="store_true",
                    help="graph daemon mode: tick loop with Poisson churn")
    ap.add_argument("--ticks", type=int, default=40)
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="Poisson tenant arrivals per tick")
    ap.add_argument("--depart-rate", type=float, default=0.25,
                    help="Poisson tenant departures per tick")
    args = ap.parse_args()
    enable_compile_cache()

    if args.history_spill_dir and not args.history_chunks:
        ap.error("--history-spill-dir needs --history-chunks (it spills "
                 "the cold store's sealed chunks)")
    if args.daemon:
        run_daemon(args)
        return
    if args.graph:
        run_graph(args)
        return

    spec = get_arch(args.arch)
    cfg = spec.smoke_cfg
    params = tf.init_params(jax.random.PRNGKey(args.seed), cfg)
    engine = ServeEngine(params, cfg, batch_slots=args.slots, max_seq=args.max_seq)

    rng = np.random.default_rng(args.seed)
    for rid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, args.prompt_len).astype(np.int32)
        engine.submit(Request(rid=rid, prompt=prompt, max_new_tokens=args.max_new))

    t0 = time.perf_counter()
    stats = engine.run()
    dt = time.perf_counter() - t0
    print(
        f"completed {stats.requests_completed}/{args.requests} requests, "
        f"{stats.tokens_generated} tokens in {stats.steps} engine steps, "
        f"{dt:.2f}s ({stats.tokens_generated/max(dt,1e-9):.1f} tok/s)"
    )


if __name__ == "__main__":
    main()
