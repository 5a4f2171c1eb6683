"""Connected-components solves per time-travel query that ran in the
window's own vertex space: the program's ``cc.compact_solves`` counter,
averaged over the window's queries (``repro.telemetry``); 0 where no
solve compacted."""


def read(rec):
    try:
        from repro.telemetry import recent
    except ImportError:                 # a program without the recorder
        return None
    reqs = recent(rec.ops)
    if rec.ops <= 0 or len(reqs) < rec.ops:
        return None
    return sum(r.counters.get("cc.compact_solves", 0)
               for r in reqs) / len(reqs)
