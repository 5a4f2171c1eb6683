"""Device busy seconds per batch advance: the union of the device's
operation intervals over the window (the fused fixpoint and combine
program), divided by the advances completed."""
from bench.readers import busy_s_per_op as read  # noqa: F401
