"""The so-batch16-w35d cell end to end on the CPU at a small size: a sound run
is correct, the control is not, and each fault of the timed path is
caught."""
import pytest

import cellcheck

WORKLOAD = "so-batch16-w35d"


def test_sound_run_is_correct_and_control_is_not():
    out = cellcheck.run(WORKLOAD, control=True)
    cellcheck.assert_sound(out)
    cellcheck.assert_control_fails(out)


@pytest.mark.parametrize("fault", cellcheck.FAULTS)
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    cellcheck.break_advance(monkeypatch, fault)
    out = cellcheck.run(WORKLOAD, seed=11)
    assert out["correct"] is False, out["checks"]
