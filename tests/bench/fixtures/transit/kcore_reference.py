"""The plain reference of the timetable deployment: the stock reference,
and temporal k-core, which it lacks.  Numpy only; nothing of the program."""
import numpy as np

from bench import reference as stock
from bench.reference import window_edges  # noqa: F401  (the harness reads it)


def kcore(we, k: int) -> np.ndarray:
    """Peel, round by round, every vertex whose degree over the window's
    live edges (each temporal edge counted at both ends) is below ``k``."""
    alive = np.ones(we.n_vertices, bool)
    while True:
        live = alive[we.src] & alive[we.dst]
        deg = (np.bincount(we.src[live], minlength=we.n_vertices)
               + np.bincount(we.dst[live], minlength=we.n_vertices))
        new = alive & (deg >= k)
        if np.array_equal(new, alive):
            return alive
        alive = new


def solve(we, algorithm: str, source, params: dict):
    if algorithm == "kcore":
        return (kcore(we, int(params["k"])),)
    return stock.solve(we, algorithm, source, params)
