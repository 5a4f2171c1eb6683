"""A deployment: its edge columns drawn from a fixed stream and relabelled
by the seed, then built by the program under test.

``generate`` is the benchmark's stand-in for reading the dataset.  It
keeps the source's published counts: ``n_vertices``, ``n_edges`` temporal
edges over ``span_days``, and exactly ``n_static_edges`` distinct directed
(src, dst) pairs.  What the source does not publish is assumed and listed
in the configuration's ``assumed``: both endpoints of the static pairs are
drawn by :func:`power_law_draw`, rank density ``(rank + 1) **
-degree_exponent``, the repeats of a pair
uniformly over the static pairs, start times uniformly over the span and
durations uniformly over ``0..max_duration_s``.

The graph is drawn once, from ``structure_seed``; ``--seed`` draws a
permutation of the vertex ids.  So every seed serves the same graph up to
its labels, and the same work.  ``build`` is the program's own work:
``from_edges`` (T-CSR sort and upload) and ``build_tger`` (the
time-first index), timed in ``setup_s``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

DAY_S = 86_400


@dataclasses.dataclass(frozen=True)
class Columns:
    """Host edge columns of one generated deployment."""

    n_vertices: int
    src: np.ndarray         # int32[E]
    dst: np.ndarray         # int32[E]
    t_start: np.ndarray     # int32[E], seconds
    t_end: np.ndarray       # int32[E]
    span_s: int             # length of the time axis
    rank_to_id: np.ndarray  # int32[V]: the seed's label of each rank

    @property
    def n_edges(self) -> int:
        return int(self.src.size)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one use of the seed; any whole number is a seed."""
    return np.random.default_rng([seed % (1 << 64), stream])


def zipf_cdf(n: int, alpha: float) -> np.ndarray:
    """Cumulative probabilities of rank ``r`` (0-based) ~ (r + 1)^-alpha."""
    p = np.arange(1, n + 1, dtype=np.float64) ** (-alpha)
    cdf = np.cumsum(p)
    return cdf / cdf[-1]


def zipf_draw(rng: np.random.Generator, cdf: np.ndarray, size) -> np.ndarray:
    idx = np.searchsorted(cdf, rng.random(size), side="right")
    return np.minimum(idx, cdf.size - 1)


def power_law_draw(rng: np.random.Generator, n: int, a: float,
                   size) -> np.ndarray:
    """Ranks ``floor(x)`` of ``x`` on ``[0, n)`` with density
    ``~ (x + 1) ** -a`` (``0 < a < 1``), by inverting its distribution."""
    top = (n + 1.0) ** (1.0 - a) - 1.0
    x = (1.0 + rng.random(size) * top) ** (1.0 / (1.0 - a)) - 1.0
    return np.minimum(x.astype(np.int64), n - 1)


def power_law_share(n: int, a: float, k: int) -> float:
    """The share of all draws of :func:`power_law_draw` on its ``k``
    lowest ranks."""
    return ((k + 1.0) ** (1.0 - a) - 1.0) / ((n + 1.0) ** (1.0 - a) - 1.0)


def static_pairs(rng: np.random.Generator, n_v: int, a: float,
                 n_static: int) -> np.ndarray:
    """``n_static`` distinct (src, dst) rank pairs without self-loops, as
    sorted keys ``src * V + dst``; where the draws give more, the excess
    is dropped uniformly."""
    keys = np.empty(0, np.int64)
    while keys.size < n_static:
        m = int((n_static - keys.size) * 1.05) + 1024
        s = power_law_draw(rng, n_v, a, m)
        d = power_law_draw(rng, n_v, a, m)
        keys = np.unique(np.concatenate([keys, (s * n_v + d)[s != d]]))
    drop = rng.choice(keys.size, keys.size - n_static, replace=False)
    return np.delete(keys, drop)


def generate(cfg: dict, seed: int) -> Columns:
    """The deployment's edge columns: one graph, labelled by ``seed``."""
    n_v, n_e = int(cfg["n_vertices"]), int(cfg["n_edges"])
    n_s = int(cfg["n_static_edges"])
    span = int(cfg["span_days"]) * DAY_S
    rng = np.random.default_rng(int(cfg["structure_seed"]))
    pairs = static_pairs(rng, n_v, float(cfg["degree_exponent"]), n_s)
    repeat = rng.integers(0, n_s, n_e - n_s)
    keys = np.concatenate([pairs, pairs[repeat]])
    del pairs, repeat
    t_start = rng.integers(0, span, n_e).astype(np.int32)
    dur = rng.integers(0, int(cfg["max_duration_s"]) + 1, n_e)
    t_end = (t_start + dur).astype(np.int32)
    rank_to_id = rng_for(seed, 0).permutation(n_v).astype(np.int32)
    src = rank_to_id[keys // n_v]
    dst = rank_to_id[keys % n_v]
    return Columns(n_vertices=n_v, src=src, dst=dst, t_start=t_start,
                   t_end=t_end, span_s=span, rank_to_id=rank_to_id)


@dataclasses.dataclass
class Deployment:
    """The program's graph and index on the device, beside the columns."""

    cfg: dict
    cols: Columns
    graph: object
    tger: object


def build(cfg: dict, cols: Columns) -> Deployment:
    """The program builds its graph and index from the host columns and
    leaves them resident on the device."""
    import jax

    from repro.core.temporal_graph import from_edges
    from repro.core.tger import build_tger

    g = from_edges(cols.src, cols.dst, cols.t_start, cols.t_end,
                   n_vertices=cols.n_vertices)
    idx = build_tger(g, degree_cutoff=int(cfg["tger_degree_cutoff"]))
    jax.block_until_ready((g, idx))
    return Deployment(cfg=cfg, cols=cols, graph=g, tger=idx)
