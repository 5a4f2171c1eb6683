"""Megabytes per time-travel query uploaded to the device as the stitched
ring view (its fields and mask): the program's ``cold.upload_bytes``
counter, averaged over the window's queries (``repro.telemetry``)."""


def read(rec):
    try:
        from repro.telemetry import recent
    except ImportError:                 # a program without the recorder
        return None
    reqs = recent(rec.ops)
    if rec.ops <= 0 or len(reqs) < rec.ops:
        return None
    return sum(r.counters.get("cold.upload_bytes", 0)
               for r in reqs) / len(reqs) / 1e6
