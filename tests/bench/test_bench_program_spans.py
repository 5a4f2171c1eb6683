"""A traced run of each cell on the CPU at a small size reads a number for
every per-layer metric taken from the program's own spans and counters
(``repro.telemetry``)."""
import pytest

import cellcheck
from bench import harness

READERS = {
    "so-batch16-w35d": ("prep_s.advance", "copy_s.advance"),
    "wt-history-w7d": ("host_s.history", "stitch_s.history",
                       "upload_mb.history"),
}


@pytest.mark.parametrize("workload", sorted(READERS))
def test_traced_run_reads_every_program_metric(workload, tmp_path):
    out = harness.run(workload, 7, 0.5, True,
                      config=cellcheck.small_config(workload),
                      spec=cellcheck.spec_with(workload),
                      trace_dir=str(tmp_path))
    cellcheck.assert_sound(out)
    for name in READERS[workload]:
        assert name in out["metrics"], (name, out["metrics"])
        assert out["metrics"][name]["value"] > 0, name
