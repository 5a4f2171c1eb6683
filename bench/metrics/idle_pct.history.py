"""Device idle share of the time-travel cell's window (profiler trace)."""
from bench.readers import idle_pct as read  # noqa: F401
