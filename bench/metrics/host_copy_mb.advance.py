"""Megabytes of result rows that ``GraphBatchServer.advance`` copies to
the host per advance (nbytes of the rows it returns)."""


def read(rec):
    b = rec.mean("host_copy_bytes")
    return None if b is None else b / 1e6
