"""Rows that ran a fixpoint per batch advance, after dedup: the program's
``serve.rows_solved`` counter, averaged over the window's advances
(``repro.telemetry``); nothing where the program does not count it."""


def read(rec):
    try:
        from repro.telemetry import recent
    except ImportError:                 # a program without the recorder
        return None
    reqs = recent(rec.ops)
    if rec.ops <= 0 or len(reqs) < rec.ops:
        return None
    if any("serve.rows_solved" not in r.counters for r in reqs):
        return None
    return sum(r.counters["serve.rows_solved"] for r in reqs) / len(reqs)
