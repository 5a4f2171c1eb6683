#!/usr/bin/env python3
"""Bring-up smoke: the temporal-graph serving path on one TPU chip, at the
scale of the SNAP temporal network sx-stackoverflow.

    python chip_smoke.py             # one chip, six phases
    python chip_smoke.py --chips 4   # four chips: the sharded serving path only

The deployment is generated from ``--seed`` by
``data.generators.power_law_temporal_graph``: 2,601,977 vertices, 63,497,050
temporal edges, start times in seconds over the source's 2,774 days.  Degree
skew and burst shape are the generator's own, not fitted to the source.

One chip, in one process:
  1. device   — fails (non-zero exit, no result line) unless JAX's first
                device is a TPU;
  2. build    — graph + TGER on the host, then resident on the device;
  3. serve    — ``GraphBatchServer.advance`` over a 16-tenant batch of the
                five served algorithms on a narrow sliding window: every
                advance after the first is one fused dispatch, no compile
                happens in the steady advances, and an earliest-arrival row
                and a cc row match ``core/reference.py``;
  4. daemon   — ``tick`` with ``submit``/``retire`` churn;
  5. history  — a ``ColdStore`` answers a window the ring evicted, checked
                against the reference;
  6. kernels  — the Pallas int32 min and one-feature f32 sum combines run
                natively over every edge of the deployment's tile layout
                against ``xla_segment``, with no ``pallas_tiled`` fallback;
                the 128-feature sum kernel runs on a 2^20-edge slice.

``--chips 4`` runs ``serve_batch`` on a 4-way query mesh and a 2x2
edge x query mesh against the unsharded chain on the same batches, on the
whole deployment.

Each phase prints one line; times are single smoke runs, not benchmarks.
The last line of standard output is the JSON result.  Compiles go to the
persistent cache (``repro.compile_cache``), so a second run compiles less.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import types

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

# SNAP sx-stackoverflow: vertex and temporal-edge counts, time span
N_VERTICES = 2_601_977
N_EDGES = 63_497_050
T_SPAN_S = 2_774 * 86_400
N_TENANTS = 16
N_ADVANCES = 8
N_WARM = 2          # the cold advance and the first fused one compile
N_TICKS = 5
MESH_ADVANCES = 3   # per chain: the cold advance and two fused ones
# the 128-feature sum runs on a slice: its [E, 128] f32 messages over all
# 63.5M edges would be 32.5 GB, twice the chip's HBM
SPMM_EDGES = 1 << 20
ALGS = ("earliest_arrival", "reachability", "bfs", "cc", "pagerank")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def say(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def require(ok, what="check failed") -> None:
    """A smoke check that holds under ``python -O`` too."""
    if not ok:
        raise RuntimeError(what)


def check_device(n_chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}",
              file=sys.stderr)
        sys.exit(2)
    if len(devs) < n_chips:
        print(f"chip_smoke: needs {n_chips} chips, JAX found {len(devs)}",
              file=sys.stderr)
        sys.exit(2)
    return devs


class CompileMeter:
    """Backend compiles (persistent-cache loads included) and their
    seconds, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.n += 1
            self.secs += duration


class Deployment(types.SimpleNamespace):
    """The generated graph: ``g`` on the device, ``host`` numpy copies of
    its edge columns for the reference, the serving window geometry."""


def build(seed: int, meter: CompileMeter, fraction: float = 1.0) -> Deployment:
    """The deployment, or ``fraction`` of its vertices and edges (a CPU
    rehearsal of the phases at a small size)."""
    import jax

    from repro.core.tger import build_tger
    from repro.data.generators import power_law_temporal_graph

    n_v = int(round(N_VERTICES * fraction))
    n_e = int(round(N_EDGES * fraction))
    t0 = time.perf_counter()
    g = power_law_temporal_graph(n_v, n_e, seed=seed, t_max=T_SPAN_S)
    idx = build_tger(g, degree_cutoff=max(n_e // 800, 16))
    jax.block_until_ready((g, idx))
    build_s = time.perf_counter() - t0
    host = types.SimpleNamespace(
        src=np.asarray(g.src), dst=np.asarray(g.dst),
        t_start=np.asarray(g.t_start), t_end=np.asarray(g.t_end),
        weight=np.asarray(g.weight), n_vertices=n_v)
    ts = host.t_start
    span = int(ts.max() - ts.min())
    width = max(span // 80, 1)
    stride = max(width // 16, 1)
    mem = jax.devices()[0].memory_stats() or {}
    say("build", vertices=n_v, edges=n_e, host_build_s=build_s,
        indexed=idx.n_indexed, bytes_in_use=mem.get("bytes_in_use"),
        peak_bytes_in_use=mem.get("peak_bytes_in_use"),
        compiles=meter.n)
    return Deployment(g=g, idx=idx, host=host, span=span, width=width,
                      stride=stride, t_min=int(ts.min()),
                      t_max=int(host.t_end.max()))


def tenant_batch(dep: Deployment, base: int, n_tenants: int = N_TENANTS):
    """The five algorithms round-robin over ``n_tenants`` tenants, every
    other tenant one stride behind (``launch/serve.py``'s graph mix)."""
    from repro.engine import QueryBatch, QuerySpec

    specs = []
    for i in range(n_tenants):
        alg = ALGS[i % len(ALGS)]
        off = (i % 2) * dep.stride
        win = (int(base - off - dep.width), int(base - off))
        if alg == "cc":
            specs.append(QuerySpec.make(alg, win))
        elif alg == "pagerank":
            specs.append(QuerySpec.make(alg, win, n_iters=8))
        else:
            specs.append(QuerySpec.make(
                alg, win, sources=(7 * i) % dep.g.n_vertices))
    return QueryBatch.make(specs)


def first_row(batch, results, algorithm: str):
    """(row 0 of the algorithm's group, its source, its window)."""
    for gi, (key, rows) in enumerate(batch.groups().items()):
        if key[0] == algorithm:
            r = results[gi]
            r = r[-1] if isinstance(r, tuple) else r
            return np.asarray(r)[0], rows[0].source, rows[0].window
    raise KeyError(algorithm)


def check_reference(phase: str, dep: Deployment, batch, results) -> None:
    """An earliest-arrival row and a cc row against core/reference.py."""
    from repro.core.reference import earliest_arrival_ref, temporal_cc_ref

    got, src, win = first_row(batch, results, "earliest_arrival")
    t0 = time.perf_counter()
    ref = earliest_arrival_ref(dep.host, src, win)
    ea_ok = np.array_equal(got.astype(np.int64), ref)
    reached = int((ref < np.iinfo(np.int32).max).sum())
    got_cc, _, win_cc = first_row(batch, results, "cc")
    ref_cc = temporal_cc_ref(dep.host, win_cc)
    cc_ok = np.array_equal(got_cc.astype(np.int64), ref_cc)
    say(phase + ":reference", ea_source=src, ea_window=list(win),
        ea_reached=reached, ea_match=ea_ok, cc_window=list(win_cc),
        cc_components=int(np.unique(ref_cc).size), cc_match=cc_ok,
        reference_s=time.perf_counter() - t0)
    require(ea_ok, f"{phase}: earliest_arrival row differs from reference")
    require(cc_ok, f"{phase}: cc row differs from the reference")


def serve(dep: Deployment, meter: CompileMeter) -> None:
    from repro.serve import window_sweep as ws
    from repro.serve.engine import GraphBatchServer

    server = GraphBatchServer(dep.g, dep.idx, access="index")
    base0 = dep.t_max - (N_ADVANCES + 2) * dep.stride
    times, logs = [], []
    n0, s0 = meter.n, meter.secs
    for k in range(N_ADVANCES):
        if k == N_WARM:
            n_warm, s_warm = meter.n, meter.secs
        batch = tenant_batch(dep, base0 + k * dep.stride)
        t0 = time.perf_counter()
        with ws.dispatch_log() as log:
            results = server.advance(batch)   # host snapshots: synced
        times.append(time.perf_counter() - t0)
        logs.append(list(log))
    steady = np.asarray(times[N_WARM:])
    say("serve", tenants=N_TENANTS, rows=batch.n_rows,
        window_s=dep.width, stride_s=dep.stride,
        ring_capacity=server.state.capacity,
        cold_advance_s=times[0],
        first_fused_advance_s=times[1],
        steady_advance_s_median=float(np.median(steady)),
        steady_advance_s_min=float(steady.min()),
        steady_advance_s_max=float(steady.max()),
        setup_compiles=n_warm - n0, setup_compile_s=s_warm - s0,
        steady_compiles=meter.n - n_warm, dispatch_logs=logs)
    require(server.stats.cold_advances == 1, server.stats)
    for k, log in enumerate(logs[1:], start=1):
        require(log == ["fused:index"], f"advance {k} dispatched {log}")
    require(meter.n == n_warm,
            f"{meter.n - n_warm} compile(s) inside the steady advances")
    check_reference("serve", dep, batch, results)


def daemon(dep: Deployment, seed: int) -> None:
    from repro import telemetry
    from repro.engine import QuerySpec
    from repro.serve.engine import GraphBatchServer

    rng = np.random.default_rng(seed)

    def spec(i: int) -> QuerySpec:
        alg = ALGS[i % len(ALGS)]
        w = (0, dep.width)
        if alg == "cc":
            return QuerySpec.make(alg, w)
        if alg == "pagerank":
            return QuerySpec.make(alg, w, n_iters=8)
        return QuerySpec.make(alg, w, sources=(7 * i) % dep.g.n_vertices)

    server = GraphBatchServer(dep.g, dep.idx, access="index")
    live = [server.submit(spec(i)) for i in range(N_TENANTS)]
    n_spawned = N_TENANTS
    t_base = dep.t_max - (N_TICKS + 2) * dep.stride
    served = 0
    for k in range(N_TICKS):
        rep = server.tick(t_base + k * dep.stride)
        require(rep.classes_served and rep.results, rep)
        for tid, r in rep.results.items():
            for a in (r if isinstance(r, tuple) else (r,)):
                require(np.all(np.isfinite(a)), f"tenant {tid}: non-finite")
        served += len(rep.results)
        live.append(server.submit(spec(n_spawned)))
        n_spawned += 1
        server.retire(live.pop(int(rng.integers(len(live)))))
    s = server.stats
    lat = np.asarray([d for r in telemetry.recent(s.ticks)
                      for d in r.durations("serve.class")])
    say("daemon", ticks=s.ticks, class_advances=s.advances,
        cold=s.cold_advances, fused=s.fused_dispatches,
        admissions=s.admissions, retirements=s.retirements,
        tenant_results=served,
        class_serve_s_median=float(np.median(lat)),
        class_serve_s_max=float(lat.max()))
    require(s.fused_dispatches > 0, s)


def history(dep: Deployment) -> None:
    from repro.core.coldstore import ColdStore
    from repro.engine import QueryBatch, QuerySpec
    from repro.serve.engine import GraphBatchServer

    store = ColdStore(dep.g, dep.idx, chunk_slots=1 << 16)
    server = GraphBatchServer(dep.g, dep.idx, access="index",
                              coldstore=store)

    def pair(win):
        return QueryBatch.make([
            QuerySpec.make("earliest_arrival", win, sources=0),
            QuerySpec.make("cc", win)])

    now = dep.t_max - dep.stride
    server.advance(pair((now - dep.width, now)))     # hot: evicts history
    past = dep.t_min + dep.span // 8 + dep.width
    batch = pair((past - dep.width, past))
    t0 = time.perf_counter()
    results = server.advance(batch)
    dt = time.perf_counter() - t0
    tier = server.state.plan.tier
    st = store.stats()
    say("history", tier=tier, window=list(batch.union()),
        time_travel_s=dt, chunks=st["n_chunks"],
        watermark=st["watermark"],
        compaction=st["compaction_ratio"])
    require(tier in ("cold", "split"), tier)
    check_reference("history", dep, batch, results)


def kernels(dep: Deployment) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.engine.backends import (
        get_backend,
        pallas_fallbacks,
        segment_combine,
    )
    from repro.engine.plan import plan_query
    from repro.kernels import ops, resolve_interpret

    require(resolve_interpret() is False, "kernels would interpret")
    g, V = dep.g, dep.g.n_vertices
    win = (dep.t_max - dep.width, dep.t_max)
    t0 = time.perf_counter()
    plan = plan_query(g, dep.idx, win, access="scan", backend="pallas_tiled")
    layout_s = time.perf_counter() - t0
    require(plan.backend == "pallas_tiled", plan.backend)
    before = pallas_fallbacks()

    # int32 min over every edge of the graph, in the deployment's layout
    ok = (g.t_start >= win[0]) & (g.t_end <= win[1])
    cand = jnp.where(ok, g.t_end, jnp.iinfo(jnp.int32).max)
    backend = get_backend("pallas_tiled")
    tiled = jax.jit(lambda p, c, d: backend.combine(p, c, d, V, "min"))
    compiled = tiled.lower(plan, cand, g.dst).compile()
    require("tpu_custom_call" in compiled.as_text(), "no min kernel")
    got = compiled(plan, cand, g.dst)
    want = jax.jit(lambda c, d: segment_combine(c, d, V, "min"))(cand, g.dst)
    min_ok = bool(jnp.array_equal(got, want))

    # f32 one-feature sums (PageRank's rank / out-degree messages) over
    # every edge of the same layout
    rank = jax.random.uniform(jax.random.PRNGKey(0), (V,), jnp.float32) / V
    msgs = rank[g.src] / jnp.maximum(g.out_degree[g.src], 1).astype(jnp.float32)
    tiled_sum = jax.jit(lambda p, m, d: backend.combine(p, m, d, V, "sum"))
    compiled = tiled_sum.lower(plan, msgs, g.dst).compile()
    require("tpu_custom_call" in compiled.as_text(), "no one-feature sum kernel")
    got_sum = np.asarray(compiled(plan, msgs, g.dst), np.float64)
    seg_sum = jax.jit(lambda m, d: segment_combine(m, d, V, "sum"))
    want_sum = np.asarray(seg_sum(msgs, g.dst), np.float64)
    # summation orders differ: any order of n terms is within (n - 1) u of
    # their L1 mass (Higham), so the two sums within n eps of each other
    deg = np.bincount(dep.host.dst, minlength=V)
    l1 = np.bincount(dep.host.dst, weights=np.asarray(msgs, np.float64),
                     minlength=V)
    err = np.abs(got_sum - want_sum)
    tol = np.finfo(np.float32).eps * deg * l1
    sum_ok = bool((err <= tol).all())
    del msgs, got_sum, want_sum

    # f32 feature sums (D = 128) over a slice of the graph
    n_sub = min(SPMM_EDGES, g.n_edges)
    dst_sub = dep.host.dst[:n_sub]
    lay = ops.prepare_layout(dst_sub, V, tile_v=256, block_e=512)
    feats = jax.random.normal(jax.random.PRNGKey(0), (n_sub, 128), jnp.float32)
    dst_d = jnp.asarray(dst_sub)

    def spmm(perm, block_tile, d, m):
        lay_t = dataclasses.replace(lay, perm=perm, block_tile=block_tile)
        return ops.spmm(lay_t, d, m, n_vertices=V)

    args = (jnp.asarray(lay.perm), jnp.asarray(lay.block_tile), dst_d, feats)
    compiled = jax.jit(spmm).lower(*args).compile()
    require("tpu_custom_call" in compiled.as_text(), "no feature sum kernel")
    got_mat = np.asarray(compiled(*args))
    want_mat = np.asarray(seg_sum(feats, dst_d))
    # the same bound, at 4 eps: a slice's in-degrees are small
    l1_mat = np.asarray(seg_sum(jnp.abs(feats), dst_d))
    err_mat = np.abs(got_mat - want_mat)
    tol_mat = 4 * np.finfo(np.float32).eps * l1_mat + 1e-6
    spmm_ok = bool((err_mat <= tol_mat).all())
    fell_back = {k: v - before.get(k, 0) for k, v in pallas_fallbacks().items()
                 if v != before.get(k, 0)}
    say("kernels", layout_blocks=int(plan.layout_block_tile.shape[0]),
        layout_build_s=layout_s, min_edges=g.n_edges,
        min_bit_identical=min_ok, sum_edges=g.n_edges,
        sum_max_rel_err=float((err / np.maximum(l1, 1e-30)).max()),
        sum_err_over_bound=float((err / np.maximum(tol, 1e-30)).max()),
        sum_within_bound=sum_ok, spmm_edges=n_sub,
        spmm_max_abs_err=float(err_mat.max()),
        spmm_err_over_bound=float((err_mat / tol_mat).max()),
        spmm_within_bound=spmm_ok, pallas_fallbacks=sum(fell_back.values()))
    require(min_ok, "pallas segment-min differs from xla_segment")
    require(sum_ok, "pallas segment-sum differs from xla_segment")
    require(spmm_ok, "pallas feature sum differs from xla_segment")
    require(not fell_back,
            f"pallas_tiled fell back to the segment path: {fell_back}")


def mesh(dep: Deployment, n_chips: int) -> None:
    """serve_batch on mesh=4 and mesh=(2, 2) against the unsharded chain."""
    import jax

    from repro.serve import window_sweep as ws
    from repro.serve.engine import GraphBatchServer

    base0 = dep.t_max - (MESH_ADVANCES + 2) * dep.stride
    batches = [tenant_batch(dep, base0 + k * dep.stride)
               for k in range(MESH_ADVANCES)]
    plain = GraphBatchServer(dep.g, dep.idx, access="index")
    want = [plain.advance(b) for b in batches]
    del plain
    devices = set(jax.devices()[:n_chips])
    for shape, tag in ((4, "fused:index@q4"), ((2, 2), "fused:index@e2q2")):
        server = GraphBatchServer(dep.g, dep.idx, access="index", mesh=shape)
        t0 = time.perf_counter()
        for k, b in enumerate(batches):
            with ws.dispatch_log() as log:
                got = server.advance(b)
            if k:
                require(log == [tag], f"mesh {shape} advance {k}: {log}")
            for gi, (key, _) in enumerate(b.groups().items()):
                for x, y in zip(*(r if isinstance(r, tuple) else (r,)
                                  for r in (got[gi], want[k][gi]))):
                    if np.issubdtype(y.dtype, np.floating):
                        require(np.allclose(x, y, rtol=1e-5, atol=1e-6), key)
                    else:
                        require(np.array_equal(x, y), (shape, k, key))
        dt = time.perf_counter() - t0
        st = server.state
        ring = st.edges.src
        rows = st.results[0]
        rows = rows[0] if isinstance(rows, tuple) else rows
        ring_devs = {s.device for s in ring.addressable_shards}
        row_devs = {s.device for s in rows.addressable_shards}
        ring_slices = {str(s.index) for s in ring.addressable_shards}
        say("mesh", shape=shape, advances=len(batches), seconds=dt,
            dispatch=tag, ring_devices=len(ring_devs),
            ring_slices=len(ring_slices), row_devices=len(row_devs),
            rows_bit_identical=True)
        require(ring_devs == devices and row_devs == devices,
                f"shards on {ring_devs} (ring), {row_devs} (rows)")
        require(len(ring_slices) == (2 if shape == (2, 2) else 1), ring_slices)
        del server, st, ring, rows
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = check_device(args.chips)
    import jax

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    meter = CompileMeter()
    say("device", platform=devs[0].platform, kind=repr(devs[0].device_kind),
        count=len(devs), jax=jax.__version__, vertices=N_VERTICES,
        edges=N_EDGES, compile_cache=cache)
    t_all = time.perf_counter()
    dep = build(args.seed, meter)
    if args.chips == 4:
        mesh(dep, args.chips)
    else:
        serve(dep, meter)
        gc.collect()
        daemon(dep, args.seed)
        gc.collect()
        history(dep)
        gc.collect()
        kernels(dep)
    mem = devs[0].memory_stats() or {}
    say("done", total_s=time.perf_counter() - t_all,
        compiles=meter.n, compile_s=meter.secs,
        peak_bytes_in_use=mem.get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
