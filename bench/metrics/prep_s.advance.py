"""Host seconds per batch advance before the device starts: the program's
``serve.plan`` (grouping, row matching, schedule) and ``serve.dispatch``
(enqueue and argument transfer) spans, summed per advance and averaged
over the window's advances (``repro.telemetry``)."""


def read(rec):
    try:
        from repro.telemetry import recent
    except ImportError:                 # a program without the recorder
        return None
    reqs = recent(rec.ops)
    if rec.ops <= 0 or len(reqs) < rec.ops:
        return None
    return sum(r.total("serve.plan") + r.total("serve.dispatch")
               for r in reqs) / len(reqs)
