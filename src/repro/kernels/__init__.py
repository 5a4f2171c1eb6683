"""Pallas kernels for the graph engine's destination-tile combines.

Whether a kernel runs in Pallas interpret mode is decided here and nowhere
else: every kernel entry takes ``interpret=None``, which resolves to True
only when JAX's default backend is the CPU.  An explicit bool overrides it
(a compile rehearsal for a described TPU passes ``interpret=False`` while
the process itself runs on the CPU).
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Interpret mode for one kernel call: the platform's choice unless
    the caller gave one."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
