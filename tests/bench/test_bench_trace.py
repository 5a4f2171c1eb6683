"""The reduction from a profiler trace to device busy time, top device
operations and labelled idle gaps."""
import gzip
import json
import os

import pytest

import cellcheck  # noqa: F401  (puts the checkout on sys.path)
from bench import trace
from bench.trace import Event

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tpu_batch_window.json.gz")
S = 1e9


def _ev(plane, line, name, a, b):
    return Event(plane, line, name, a * S, b * S)


def _synthetic():
    tpu, host = "/device:TPU:0", "/host:CPU"
    return [
        _ev(host, "python", "bench.window", 0.0, 1.0),
        _ev(host, "python", "bench.advance", 0.0, 0.5),
        _ev(host, "python", "bench.advance", 0.5, 1.0),
        _ev(host, "python", "PjitFunction(step)", 0.45, 0.65),
        _ev(tpu, "XLA Modules", "jit_step", 0.1, 0.7),
        _ev(tpu, "XLA Ops", "fusion.1", 0.1, 0.3),
        _ev(tpu, "XLA Ops", "scatter.2", 0.2, 0.4),
        _ev(tpu, "XLA Ops", "fusion.1", 0.6, 0.7),
        _ev(tpu, "XLA Ops", "fusion.1", 1.1, 1.2),     # after the window
    ]


def test_busy_union_ops_and_gaps():
    s = trace.summarize(_synthetic())
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(1.0)
    assert s.busy_s == pytest.approx(0.4)
    assert s.idle_s == pytest.approx(0.6)
    assert [n for n, _ in s.device_ops] == ["fusion.1", "scatter.2"]
    assert s.device_ops[0][1] == pytest.approx(0.3)
    assert [(n, pytest.approx(d)) for n, d in s.idle_gaps] == [
        ("advance", 0.3), ("advance:PjitFunction(step)", 0.2),
        ("advance", 0.1)]


def test_two_devices_are_averaged():
    ev = _synthetic() + [_ev("/device:TPU:1", "XLA Ops", "fusion.9", 0.0, 1.0)]
    s = trace.summarize(ev)
    assert s.n_devices == 2
    assert s.busy_s == pytest.approx(0.7)


def test_no_window_or_no_device_reads_nothing():
    ev = _synthetic()
    assert trace.summarize([e for e in ev if e.name != "bench.window"]) is None
    assert trace.summarize([e for e in ev
                            if not e.plane.startswith("/device")]) is None


def test_op_names():
    assert trace.op_name("%fusion.237 = s32[80000]{0:T(1024)} fusion(s32[6] "
                         "%b), kind=kCustom, calls=%f.70") == (
        "fusion.237 fusion kCustom")
    assert trace.op_name("%while.91 = (s32[3]{0}, pred[]) while((s32[3]{0}, "
                         "pred[]) %tuple.191), condition=%c") == (
        "while.91 while")
    assert trace.op_name("jit_step") == "jit_step"


def test_recorded_chip_trace():
    """The first 0.12 s of a window traced on one v5e chip: a batch cell
    of 16 tenants on a 20,000-vertex graph, about four advances."""
    with gzip.open(FIXTURE) as f:
        ev = [Event(*row) for row in json.load(f)["events"]]
    s = trace.summarize(ev)
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(0.12)
    assert s.busy_s == pytest.approx(0.088169447, rel=1e-6)
    assert s.device_ops[0] == ("while.91 while", pytest.approx(0.02944681))
    assert sum(d for _, d in s.idle_gaps) <= s.idle_s + 1e-9
    assert s.idle_gaps[0][0] == "advance:np.asarray(jax.Array)"
    assert all(n.startswith("advance") for n, _ in s.idle_gaps)


def test_load_reads_a_recorded_trace(tmp_path):
    """``load`` reads JAX's own trace file: the benchmark's spans are
    there by name (a CPU trace has no device plane, so nothing to
    summarize)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            f(jnp.ones(8)).block_until_ready()
    ev = trace.load(trace.find_xplane(str(tmp_path)))
    assert any(e.name == trace.WINDOW for e in ev)
    assert trace.summarize(ev) is None
