"""Query-axis sharding for multi-tenant batch serving (DESIGN.md §7.5).

The multi-tenant engine (`serve.serve_batch`) answers a whole QueryBatch
in one fused dispatch per advance; this module supplies the pieces that
scale that dispatch ACROSS devices by partitioning the batch's expanded
(algorithm × source × window) rows over a one-axis query mesh:

  * :func:`query_mesh` — the mesh itself, built on the mesh axis the
    ``"queries"`` logical rule reserves (``distributed/sharding.py``:
    ``queries -> "model"``), via ``compat.make_mesh``.
  * :func:`row_partition` — the pad-and-mask row layout: ``n_rows`` rows
    partition into ``n_shards`` CONTIGUOUS chunks of ``cap = ceil(n/D)``
    rows; the tail pads by REPEATING THE LAST REAL ROW (a real solve whose
    duplicate result is dropped at the fan-out gather — solving a
    fabricated window could diverge, and masking a lane out of a
    ``shard_map`` body would need a per-lane cond the fused program does
    not want).  Real row ``j`` keeps global index ``j``, so the fan-out /
    assembly gathers downstream of the solve are layout-oblivious.
  * :func:`replicate` / :func:`replicated_arrays` — replicated
    (``PartitionSpec()``) placement for the structures every device needs
    whole: the ring-buffer edge view, the carried [Q, V] result rows, and
    the graph field/permutation arrays (identity-cached per (mesh, arrays)
    so a serving horizon replicates them once, not per advance).

The row partition is deliberately chunked (not strided): each device's
rows form a contiguous span of the batch's row order, so callers control
locality by ordering rows — e.g. clustering deep-convergence tenants on
one device so the other devices' local fixpoint loops exit early
(DESIGN.md §7.5; the per-device while_loop is where the single-host
speedup of `benchmarks/bench_fixpoint.py` part 4 comes from).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.hostcache import identity_cache
from repro.distributed.compat import make_mesh
from repro.distributed.sharding import DEFAULT_RULES


def query_axis() -> str:
    """The mesh axis name the ``"queries"`` logical axis maps to."""
    ax = DEFAULT_RULES["queries"]
    if not isinstance(ax, str):
        raise TypeError(f"'queries' must map to ONE mesh axis, got {ax!r}")
    return ax


def query_mesh(n_devices: Optional[int] = None) -> Mesh:
    """A one-axis mesh over the query axis (all devices by default)."""
    n = jax.device_count() if n_devices is None else int(n_devices)
    if n < 1:
        raise ValueError(f"need at least one device, got {n}")
    if n > jax.device_count():
        raise ValueError(
            f"query_mesh({n}) exceeds the {jax.device_count()} available "
            f"device(s) — force host devices via XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N for CPU scale tests")
    return make_mesh((n,), (query_axis(),))


def edge_axis() -> str:
    """The mesh axis name the serving ring's EDGE axis shards over in a 2-D
    edge×query mesh (DESIGN.md §7.7).  The ``"edges"`` logical rule maps to
    the ``("pod", "data")`` axes of the distributed engine's meshes; the
    serving mesh is single-host, so it uses the LAST of those — ``"data"``
    — as its one edge axis."""
    ax = DEFAULT_RULES["edges"]
    return ax[-1] if isinstance(ax, (tuple, list)) else ax


def serve_mesh(edge_shards: int, query_shards: int) -> Mesh:
    """The 2-D ``(edge_shards, query_shards)`` serving mesh (DESIGN.md
    §7.7): axis 0 shards the ring view's slot axis, axis 1 the batch's
    expanded row axis.  ``serve_mesh(1, D)`` degenerates to the 1-D
    :func:`query_mesh` (the exact same program must serve both, so the
    shapes must not differ)."""
    e, d = int(edge_shards), int(query_shards)
    if e < 1 or d < 1:
        raise ValueError(f"mesh shape must be >= (1, 1), got ({e}, {d})")
    if e == 1:
        return query_mesh(d)
    if e * d > jax.device_count():
        raise ValueError(
            f"serve_mesh({e}, {d}) needs {e * d} devices but only "
            f"{jax.device_count()} are available — force host devices via "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N")
    return make_mesh((e, d), (edge_axis(), query_axis()))


def row_partition(n_rows: int, n_shards: int, *,
                  align: int = 1) -> Tuple[int, np.ndarray]:
    """Contiguous-chunk pad-and-mask partition of ``n_rows`` over
    ``n_shards`` devices.

    Returns ``(cap, pad_map)``: the per-device row capacity ``cap =
    ceil(n_rows / n_shards)`` and an i32[cap * n_shards] gather map that
    lays rows out for a ``PartitionSpec(axis)``-sharded array — identity
    for the real rows (row ``j`` stays at global index ``j``), then the
    LAST real row repeated over the tail padding.  Row counts not
    divisible by the device count therefore pad, never drop — and because
    ``cap`` depends only on (n_rows, n_shards), which are already static
    via the fused-step schedule, padding never retraces.

    ``align`` snaps ``cap`` up to the next multiple — the bucket-aligned
    partition of DESIGN.md §7.7: with ``align`` a power of two dividing
    the admission bucket capacity, every chunk boundary lands on a
    ``bucket_capacity`` multiple, so the bucketed dynamic gather maps stay
    device-local under the query mesh."""
    if n_rows < 1:
        raise ValueError(f"row_partition needs at least one row, got {n_rows}")
    if n_shards < 1:
        raise ValueError(f"need at least one shard, got {n_shards}")
    if align < 1:
        raise ValueError(f"align must be >= 1, got {align}")
    cap = -(-n_rows // n_shards)
    cap = -(-cap // align) * align
    pad_map = np.minimum(
        np.arange(cap * n_shards, dtype=np.int32), np.int32(n_rows - 1))
    return cap, pad_map


def replicate(tree, mesh: Mesh):
    """Place a pytree fully replicated over ``mesh`` (every device holds a
    whole copy — the ring view / carried results layout of §7.5)."""
    return jax.device_put(tree, NamedSharding(mesh, PartitionSpec()))


@identity_cache(max_entries=8)
def replicated_arrays(mesh: Mesh, *arrays):
    """Replicate ``arrays`` over ``mesh``, identity-cached per
    ``(mesh, id(arrays)...)`` — graph fields and time-first permutations
    are immutable for the life of a graph/index, so a serving horizon
    pays the replication transfer once, and the fused step's input
    shardings stay stable from the first sharded advance (no
    per-sharding recompiles)."""
    return replicate(tuple(arrays), mesh)


__all__ = [
    "query_axis",
    "query_mesh",
    "edge_axis",
    "serve_mesh",
    "row_partition",
    "replicate",
    "replicated_arrays",
]
