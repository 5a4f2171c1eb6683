"""Host seconds per batch advance spent copying the result rows to the
host once the device has finished them: the program's ``serve.copy``
spans (the wait for the device is ``serve.wait``, apart), summed per
advance and averaged over the window's advances (``repro.telemetry``)."""


def read(rec):
    try:
        from repro.telemetry import recent
    except ImportError:                 # a program without the recorder
        return None
    reqs = recent(rec.ops)
    if rec.ops <= 0 or len(reqs) < rec.ops:
        return None
    return sum(r.total("serve.copy") for r in reqs) / len(reqs)
