"""Backend compiles inside the time-travel window (JAX monitoring events)."""
from bench.readers import compiles as read  # noqa: F401
