"""Host seconds per time-travel query spent stitching the window's ring
view from the cold store (chunk decode and mirror gather): the program's
``cold.stitch`` spans, averaged over the window's queries
(``repro.telemetry``)."""


def read(rec):
    try:
        from repro.telemetry import recent
    except ImportError:                 # a program without the recorder
        return None
    reqs = recent(rec.ops)
    if rec.ops <= 0 or len(reqs) < rec.ops:
        return None
    return sum(r.total("cold.stitch") for r in reqs) / len(reqs)
