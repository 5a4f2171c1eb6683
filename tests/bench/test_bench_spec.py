"""BENCHMARK.json keeps to the benchmark's contract, and every cell
resolves by name to files of its own."""
import json
import os
import re
import shutil

import pytest

import cellcheck
from bench import harness

ROOT = cellcheck.ROOT
SPEC = harness.load_spec(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in SPEC["workloads"]]


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    cmd = SPEC["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    files = [w for w in cmd if os.path.exists(os.path.join(ROOT, w))]
    assert files and all(any(f.startswith(p + "/") for p in SPEC["paths"])
                         for f in files)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_text_fields():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            assert NAME.match(entry["name"]), entry["name"]
            key = (group in ("end_to_end", "per_layer"), entry["name"])
            assert key not in seen, entry["name"]
            seen.add(key)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _line(m["layer"])


def test_four_chip_cells_within_their_share():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_by_name(workload):
    w = harness.cell(SPEC, workload)
    cfg = harness.load_config(w["config"], ROOT)
    entry = next(c for c in SPEC["configs"] if c["name"] == w["config"])
    assert entry["file"] == f"bench/configs/{w['config']}.json"
    assert cfg["name"] == w["config"] and cfg["source"] == entry["source"]
    mix = harness.load_traffic(w["traffic"], ROOT)
    assert callable(harness.load_client(mix["mode"], ROOT))
    assert callable(harness.load_generator(cfg, ROOT))
    ref = harness.load_module(cfg["reference"], ROOT)
    assert callable(ref.window_edges) and callable(ref.solve)
    for m in harness.per_layer_of(SPEC, workload):
        assert callable(harness.load_reader(m["name"], ROOT))


@pytest.mark.parametrize("workload", CELLS)
def test_cell_reports_setup_another_metric_and_what_each_layer_moves(workload):
    e2e = {m["name"] for m in harness.end_to_end_of(SPEC, workload)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = harness.per_layer_of(SPEC, workload)
    assert layers
    for m in layers:
        assert m["moves"] in e2e, (m["name"], workload)


def test_every_config_and_metric_is_used():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def _snap_shaped(root, spec):
    """A SNAP-shaped deployment at other counts, a batch mix of four
    tenants and a metric, on the stock generator, reference and client."""
    cfg = dict(cellcheck.small(harness.load_config("wiki-talk", ROOT)),
               name="tiny-talk", limits={"rows_differ": 0, "pagerank_l1": 1e-4})
    (root / "bench" / "configs" / "tiny-talk.json").write_text(
        json.dumps(cfg))
    mix = dict(harness.load_traffic("batch16-w35d", ROOT), tenants=4)
    (root / "bench" / "traffic" / "batch4.json").write_text(json.dumps(mix))
    (root / "bench" / "metrics" / "advances.tiny.py").write_text(
        "def read(rec):\n    return rec.ops\n")
    _add_cell(spec, "tiny-talk", "batch4", "advances.tiny")
    return "tiny-talk-batch4", {"bench/reference.py",
                                "bench/metrics/advances.tiny.py"}


def _timetable(root, spec):
    """A timetable deployment with a generator and a reference of its own
    (the reference answers kcore, which the stock one lacks) and a mix in a
    mode of its own (``tests/bench/fixtures/transit``)."""
    shutil.copytree(TRANSIT, root / "bench", dirs_exist_ok=True)
    _add_cell(spec, "tiny-transit", "departures-k2", "ea_rounds.tiny")
    return "tiny-transit-departures-k2", {
        "bench/timetable.py", "bench/kcore_reference.py",
        "bench/modes/departures.py", "bench/metrics/ea_rounds.tiny.py"}


def _timetable_wrong_reference(root, spec):
    """The timetable cell with its reference's kcore rows planted wrong."""
    workload, files = _timetable(root, spec)
    path = root / "bench" / "kcore_reference.py"
    text = path.read_text()
    assert text.count("return (kcore(") == 1
    path.write_text(text.replace("return (kcore(", "return (~kcore("))
    return workload, files


TRANSIT = os.path.join(os.path.dirname(__file__), "fixtures", "transit")
ADDED = {"snap-shaped": (_snap_shaped, True),
         "timetable": (_timetable, True),
         "timetable-wrong-reference": (_timetable_wrong_reference, False)}


def _add_cell(spec, config, traffic, metric):
    spec["configs"].append({"name": config, "source": "x",
                            "file": f"bench/configs/{config}.json",
                            "reduced": [], "why": "x"})
    name = f"{config}-{traffic}"
    spec["workloads"].append({"name": name, "config": config,
                              "traffic": traffic, "chips": 1, "why": "x"})
    next(m for m in spec["end_to_end"]
         if m["name"] == "advance_s")["workloads"].append(name)
    spec["per_layer"].append({"name": metric, "unit": "count",
                              "better": "higher", "source": "program_counter",
                              "layer": "batch serving", "moves": "advance_s",
                              "workloads": [name]})


@pytest.mark.parametrize("case", ADDED)
def test_a_cell_is_added_by_files_and_an_entry_alone(tmp_path, monkeypatch,
                                                     case):
    """A copy of the benchmark gains a deployment, a traffic mix, a metric
    and a cell through new files and entries; the harness runs it, loads
    only the files they name, and judges it by the deployment's own
    reference."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench")
    spec = json.loads(json.dumps(SPEC))
    add, sound = ADDED[case]
    workload, files = add(root, spec)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    loaded = []
    real = harness.load_module

    def load_module(path, root=harness.ROOT):
        loaded.append(path)
        return real(path, root)

    monkeypatch.setattr(harness, "load_module", load_module)
    out = harness.run(workload, 3, 0.2, False, root=str(root))
    assert out["correct"] is sound, out["checks"]
    assert out["failed"] == 0 and out["checks"]["rows_checked"]["value"] >= 4
    if not sound:
        assert out["checks"]["rows_differ"]["value"] >= 1
        return
    assert set(out["metrics"]) == {"setup_s", "advance_s"}
    out = harness.run(workload, 3, 0.2, True, root=str(root))
    assert out["correct"], out["checks"]
    (metric,) = (m["name"] for m in harness.per_layer_of(spec, workload))
    assert out["metrics"][metric]["value"] >= 1
    assert set(loaded) == files
