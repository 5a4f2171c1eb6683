"""One run of one cell: resolve it by name, generate, build, warm up,
measure, check, report.

Everything that belongs to one cell is found from ``BENCHMARK.json`` by
name, and every Python file it names is loaded by :func:`load_module`:

- the deployment ``bench/configs/<config>.json``.  Its ``reference`` names
  the plain reference, a file with ``window_edges(cols, window)``,
  ``solve(window_edges, algorithm, source, params)`` and, where the cell
  serves PageRank, the control's ``pagerank_lowp``.  Its ``generator``, where
  given, names a file whose ``generate(cfg, seed)`` draws the edge columns
  (``bench.deployment.Columns``); without it ``bench/deployment.py`` draws a
  SNAP-shaped power-law graph.  The program builds either the same way.
- the traffic mix ``bench/traffic/<traffic>.json``.  Its ``mode`` picks a
  client of ``bench/serving.py`` (``batch``, ``history``) or else the
  ``Client`` of ``bench/modes/<mode>.py``, built as
  ``Client(deployment, mix, seed, seconds, spans)`` with the interface of
  ``serving.BatchClient``.
- each per-layer metric's reader ``bench/metrics/<metric>.py``, a
  ``read(record)`` that returns a number or None.

So a cell, a mix of a new mode, a deployment of its own shape with its own
reference, or a metric is added by new files and entries alone, and edits
nothing here.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import re
import shutil
import sys
import time
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(*parts) -> None:
    print(" ".join(str(p) for p in parts), file=sys.stderr, flush=True)


# ---- resolution by name ---------------------------------------------------

def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(root: str, *parts: str) -> dict:
    with open(os.path.join(root, "bench", *parts)) as f:
        return json.load(f)


def load_config(name: str, root: str = ROOT) -> dict:
    return _json(root, "configs", f"{name}.json")


def load_traffic(name: str, root: str = ROOT) -> dict:
    return _json(root, "traffic", f"{name}.json")


def load_module(path: str, root: str = ROOT):
    """The Python file ``path`` of the checkout (relative to ``root``),
    executed anew as a module of its own."""
    full = os.path.realpath(os.path.join(root, path))
    if not full.startswith(os.path.join(os.path.realpath(root), "")):
        raise ValueError(f"{path!r} is not a file of the checkout")
    name = "bench_files." + re.sub(r"\W", "_", path[:-len(".py")])
    spec = importlib.util.spec_from_file_location(name, full)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: str = ROOT):
    return load_module(f"bench/metrics/{name}.py", root).read


def load_generator(cfg: dict, root: str = ROOT):
    """The deployment's ``generate(cfg, seed)``."""
    if "generator" not in cfg:
        from bench import deployment

        return deployment.generate
    return load_module(cfg["generator"], root).generate


def load_client(mode: str, root: str = ROOT):
    """The client class of a traffic mode."""
    from bench import serving

    if mode in serving.CLIENTS:
        return serving.CLIENTS[mode]
    return load_module(f"bench/modes/{mode}.py", root).Client


def cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def end_to_end_of(spec: dict, name: str) -> List[dict]:
    return [m for m in spec["end_to_end"]
            if name in m.get("workloads", [name])]


def per_layer_of(spec: dict, name: str) -> List[dict]:
    e2e = {m["name"] for m in end_to_end_of(spec, name)}
    return [m for m in spec["per_layer"]
            if name in m.get("workloads", [name] if m["moves"] in e2e else [])]


# ---- instruments ----------------------------------------------------------

class CompileMeter:
    """Backend compiles, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.n += 1


class Spans:
    """Host spans around each call into a layer, written into the
    profiler's trace as ``bench.<name>`` and timed on the host clock."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        self.seconds[name] = (self.seconds.get(name, 0.0)
                              + time.perf_counter() - t0)

    def note(self, text: str) -> None:
        log("[note]", text)


@dataclasses.dataclass
class Record:
    """What a per-layer reader reads."""

    ops: int                          # requests completed in the window
    window_s: float                   # host clock
    compiles_in_window: int
    counters: Dict[str, list]
    trace: Optional[object] = None    # bench.trace.Summary

    def mean(self, name: str) -> Optional[float]:
        xs = self.counters.get(name) or []
        return sum(xs) / len(xs) if xs else None


# ---- one run --------------------------------------------------------------

def device_info(n_chips: int):
    """(device, peaks) of a TPU with at least ``n_chips`` chips; raises
    where JAX finds anything else or the device is not in the table."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < n_chips:
        raise SystemExit(f"bench: needs {n_chips} chips, JAX found "
                         f"{len(devs)}")
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if devs[0].device_kind not in peaks:
        raise SystemExit(f"bench: no peaks for {devs[0].device_kind!r} in "
                         "bench/peaks.json")
    return devs[0], peaks[devs[0].device_kind]


def run(workload: str, seed: int, seconds: float, trace: bool,
        root: str = ROOT, config: Optional[dict] = None,
        trace_dir: Optional[str] = None, keep_trace: bool = False,
        control: bool = False, spec: Optional[dict] = None) -> dict:
    """Run one cell and return the result object (the last line).  On a
    chip the caller has already checked the device; ``config`` replaces
    the cell's deployment (tests run small ones on the CPU), ``spec`` the
    contents of ``BENCHMARK.json``.  ``control`` also reads the control's
    numbers (``bench/control.py``; never in the benchmark's own runs)."""
    import jax

    from bench import check, deployment
    from bench import trace as tracing

    spec = spec or load_spec(root)
    w = cell(spec, workload)
    cfg = config or load_config(w["config"], root)
    mix = load_traffic(w["traffic"], root)
    generate = load_generator(cfg, root)
    ref = load_module(cfg["reference"], root)
    client_type = load_client(mix["mode"], root)
    dev = jax.devices()[0]
    meter = CompileMeter()
    spans = Spans()

    t = time.perf_counter()
    cols = generate(cfg, seed)
    log(f"[generate] {cfg['name']} vertices={cols.n_vertices} "
        f"edges={cols.n_edges} seconds={time.perf_counter() - t}")

    t_setup = time.perf_counter()
    dep = deployment.build(cfg, cols)
    t_build = time.perf_counter() - t_setup
    client = client_type(dep, mix, seed, seconds, spans)
    client.warm()
    setup_s = time.perf_counter() - t_setup
    compiles_setup = meter.n
    log(f"[setup] build_s={t_build} setup_s={setup_s} "
        f"compiles={compiles_setup}")

    trace_dir = trace_dir or os.path.join(root, ".bench_trace", workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t_win = time.perf_counter()
    try:
        with spans("window"):
            values = client.measure(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    window_s = time.perf_counter() - t_win
    compiles_window = meter.n - compiles_setup
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"[window] seconds={window_s} completed={client.completed} "
        f"failed={client.failed} compiles={compiles_window} "
        + " ".join(f"{k}={v}" for k, v in values.items()))

    summary = None
    if trace:
        summary = tracing.summarize(tracing.load(
            tracing.find_xplane(trace_dir)))
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)

    served = client.served()
    client.release()
    del dep
    with spans("reference"):
        want = check.reference_rows([a for a, _ in served], cols, ref)
    checks = check.compare(served, want, cfg["limits"], client.failed)
    correct = check.passed(checks)
    log(f"[reference] seconds={spans.seconds['reference']}")

    metrics = {}
    if not trace:
        values["setup_s"] = setup_s
        for m in end_to_end_of(spec, workload):
            if m["name"] not in values:
                raise KeyError(f"{workload} does not produce {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        rec = Record(ops=client.completed, window_s=window_s,
                     compiles_in_window=compiles_window,
                     counters=client.counters, trace=summary)
        for m in per_layer_of(spec, workload):
            v = load_reader(m["name"], root)(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": client.attempted,
           "failed": client.failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {
            "device_ops": [list(x) for x in summary.device_ops],
            "idle_gaps": [list(x) for x in summary.idle_gaps]}
    if control:
        ctrl = check.control_rows(served, cols, client.stale_shift, ref)
        out["control_checks"] = check.compare(ctrl, want, cfg["limits"], 0)
    out["checks"] = checks
    for name, c in checks.items():
        log(f"[check] {name} {c['value']} limit {c['limit']}")
    return out
