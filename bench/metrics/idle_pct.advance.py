"""Device idle share of a batch cell's window (profiler trace)."""
from bench.readers import idle_pct as read  # noqa: F401
