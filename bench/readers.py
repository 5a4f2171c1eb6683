"""Shared arithmetic of the per-layer readers in ``bench/metrics/``.  Each
reader takes a ``harness.Record`` and returns a number, or None where the
run holds nothing to read."""
from __future__ import annotations


def idle_pct(rec):
    """Share of the window in which no operation ran on the device."""
    t = rec.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * t.idle_s / t.window_s


def busy_s_per_op(rec):
    """Device busy seconds per completed request."""
    t = rec.trace
    if t is None or rec.ops <= 0 or t.busy_s <= 0:
        return None
    return t.busy_s / rec.ops


def compiles(rec):
    """Backend compiles inside the window."""
    return rec.compiles_in_window
