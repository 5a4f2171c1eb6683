"""A small public-transit timetable as a temporal graph: ``lines`` lines of
``stops_per_line`` stops each cross at one shared hub (every line's middle
stop); along each line a trip runs each way every ``headway_s`` seconds,
line ``l`` offset by ``l * headway_s / lines``, taking ``hop_s`` seconds
from stop to stop and standing ``dwell_s`` of them at each stop.  A hop is
an edge from its departure to its arrival.  The timetable is fixed;
``--seed`` permutes the stop labels, as it permutes the vertices of a
SNAP-shaped deployment."""
import numpy as np

from bench.deployment import DAY_S, Columns, rng_for


def generate(cfg: dict, seed: int) -> Columns:
    lines, per_line = int(cfg["lines"]), int(cfg["stops_per_line"])
    headway, hop, dwell = (int(cfg[k]) for k in ("headway_s", "hop_s",
                                                 "dwell_s"))
    span = int(cfg["span_days"]) * DAY_S
    n_v = 1 + lines * (per_line - 1)
    hub = per_line // 2
    src, dst, ts = [], [], []
    for line in range(lines):
        own = 1 + line * (per_line - 1) + np.arange(per_line - 1)
        stops = np.insert(own, hub, 0)
        last = span - (per_line - 1) * hop
        departs = np.arange(line * headway // lines, last, headway)
        for seq in (stops, stops[::-1]):
            offsets = np.arange(per_line - 1) * hop
            src.append(np.tile(seq[:-1], departs.size))
            dst.append(np.tile(seq[1:], departs.size))
            ts.append((departs[:, None] + offsets[None, :]).ravel())
    t_start = np.concatenate(ts).astype(np.int32)
    rank_to_id = rng_for(seed, 0).permutation(n_v).astype(np.int32)
    return Columns(n_vertices=n_v,
                   src=rank_to_id[np.concatenate(src)],
                   dst=rank_to_id[np.concatenate(dst)],
                   t_start=t_start, t_end=t_start + (hop - dwell),
                   span_s=span, rank_to_id=rank_to_id)
