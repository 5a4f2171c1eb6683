"""A traced run of the time-travel cell on the CPU at a small size reads
one compacted connected-components solve per query: its 7-day window
touches far fewer than a quarter of the vertices."""
import cellcheck
from bench import harness


def test_traced_history_run_reads_one_compact_solve_per_query(tmp_path):
    workload = "wt-history-w7d"
    out = harness.run(workload, 11, 0.5, True,
                      config=cellcheck.small_config(workload),
                      spec=cellcheck.spec_with(workload),
                      trace_dir=str(tmp_path))
    cellcheck.assert_sound(out)
    assert out["metrics"]["cc_compact.history"]["value"] == 1.0
