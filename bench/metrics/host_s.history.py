"""Host seconds per time-travel query: the program's ``serve.advance``
span less the ``serve.wait`` spans in it, in which the host waits for the
device; averaged over the window's queries (``repro.telemetry``)."""


def read(rec):
    try:
        from repro.telemetry import recent
    except ImportError:                 # a program without the recorder
        return None
    reqs = recent(rec.ops)
    if rec.ops <= 0 or len(reqs) < rec.ops:
        return None
    return sum(r.root.duration - r.total("serve.wait")
               for r in reqs) / len(reqs)
