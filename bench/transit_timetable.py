"""A city's weekday bus timetable as a temporal graph, drawn to the
published counts of the configuration: ``n_stops`` stops, ``n_routes``
routes, ``n_trips`` trips and exactly ``n_connections`` elementary
connections.  A connection is one vehicle's hop from a stop to the next:
an edge from its departure stop to its arrival stop, ``t_start`` the
departure and ``t_end`` the arrival, in seconds from midnight.

What the source does not publish is assumed and listed in the
configuration's ``assumed``:

- stops lie uniformly over a square of ``area_km2``;
- a route is a walk over nearby stops (each step to one of the
  ``walk_neighbours`` nearest stops not yet on the route, keeping roughly
  to its heading, stops no route has served yet preferred), and its trips
  run it both ways, alternately;
- route lengths and trips per route are drawn around their means and
  scaled so that the trips total ``n_trips`` exactly and the connections
  ``n_connections`` exactly; the few connections the whole routes leave
  over are taken off as short workings (single trips that end one stop
  early);
- each direction's trips leave their first stop at even steps of the
  weekday ``headway_profile`` (relative service per hour of the day,
  morning and evening peaks), from a phase of its own;
- a hop takes its straight-line distance at ``bus_speed_kmh``, rounded up
  to whole seconds and never under 1 s, and the vehicle stands
  ``dwell_s`` at every stop; changing vehicles at a stop takes no time.

Every connection takes at least one second, which the Connection Scan
reference relies on.  The timetable is drawn once, from
``structure_seed``; ``--seed`` permutes the stop labels, so every seed
serves the same work.  Numpy and scipy only; nothing of the program.
"""
from __future__ import annotations

import numpy as np

from bench.deployment import DAY_S, Columns, rng_for


def _apportion(total: int, weights: np.ndarray, floor: int) -> np.ndarray:
    """Whole numbers ``>= floor`` in proportion to ``weights`` that sum to
    ``total`` exactly (largest remainders)."""
    spare = total - floor * weights.size
    share = spare * weights / weights.sum()
    out = np.floor(share).astype(np.int64)
    left = spare - int(out.sum())
    out[np.argsort(out - share, kind="stable")[:left]] += 1
    return out + floor


def _route_hops(rng, trips: np.ndarray, total: int, lo: float,
                hi: float) -> tuple:
    """(hops per route, number of short workings): hops drawn uniformly
    between ``lo`` and ``hi`` times their mean, scaled so that
    ``sum(trips * hops)`` is ``total`` plus fewer short workings than the
    smallest route has trips."""
    rel = rng.uniform(lo, hi, trips.size)
    hops = np.maximum(np.ceil(rel * total / float(trips @ rel)), 2)
    hops = hops.astype(np.int64)
    excess = int(trips @ hops) - total
    for r in rng.permutation(trips.size):
        if excess < trips.min():
            break
        if trips[r] <= excess and hops[r] > 2:
            hops[r] -= 1
            excess -= int(trips[r])
    return hops, excess


def _stop_walk(rng, xy, tree, served, n_stops: int, k: int) -> np.ndarray:
    """One route: ``n_stops`` distinct stops, each near the last."""
    free = np.flatnonzero(served == 0)
    cur = int(rng.choice(free) if free.size else rng.integers(len(xy)))
    ang = rng.uniform(0, 2 * np.pi)
    heading = np.array([np.cos(ang), np.sin(ang)])
    route = [cur]
    on = {cur}
    while len(route) < n_stops:
        for kk in (k, 4 * k, 16 * k):
            _, near = tree.query(xy[cur], kk + 1)
            cand = np.asarray([c for c in near[1:] if c not in on])
            if cand.size:
                break
        step = xy[cand] - xy[cur]
        unit = step / np.linalg.norm(step, axis=1, keepdims=True)
        ahead = unit @ heading
        w = np.where(ahead > 0.2, 1.0 + ahead, 0.05)
        w *= np.where(served[cand] == 0, 3.0, 1.0)
        j = int(rng.choice(cand.size, p=w / w.sum()))
        cur = int(cand[j])
        heading = 0.7 * heading + 0.3 * unit[j]
        heading /= np.linalg.norm(heading)
        route.append(cur)
        on.add(cur)
    served[route] += 1
    return np.asarray(route, np.int64)


def _departures(rng, n: int, profile: np.ndarray) -> np.ndarray:
    """``n`` first-stop departures, at even steps of the cumulative hourly
    ``profile`` from a random phase, in whole seconds."""
    cdf = np.concatenate([[0.0], np.cumsum(profile)]) / profile.sum()
    q = (np.arange(n) + rng.uniform()) / n
    return np.round(np.interp(q, cdf, np.arange(25) * 3600.0)).astype(np.int64)


def draw(cfg: dict):
    """The timetable by stop rank: ``(src, dst, t_start, t_end,
    trip_hops)``, the last the number of connections of each trip run."""
    from scipy.spatial import cKDTree

    n_v, n_r = int(cfg["n_stops"]), int(cfg["n_routes"])
    n_trips, n_conn = int(cfg["n_trips"]), int(cfg["n_connections"])
    rng = np.random.default_rng(int(cfg["structure_seed"]))
    side = np.sqrt(float(cfg["area_km2"])) * 1000.0
    xy = rng.uniform(0.0, side, (n_v, 2))
    tree = cKDTree(xy)
    lo, hi = cfg["route_spread"]
    trips = _apportion(n_trips, rng.uniform(lo, hi, n_r), 2)
    hops, short = _route_hops(rng, trips, n_conn, lo, hi)
    short_trip = np.zeros(n_trips, bool)
    short_trip[rng.choice(n_trips, short, replace=False)] = True
    profile = np.asarray(cfg["headway_profile"], np.float64)
    speed = float(cfg["bus_speed_kmh"]) / 3.6
    dwell = int(cfg["dwell_s"])
    served = np.zeros(n_v, np.int64)
    src, dst, ts, te, trip_hops = [], [], [], [], []
    first_trip = 0
    for r in range(n_r):
        stops = _stop_walk(rng, xy, tree, served, int(hops[r]) + 1,
                           int(cfg["walk_neighbours"]))
        dist = np.linalg.norm(np.diff(xy[stops], axis=0), axis=1)
        ride = np.maximum(np.ceil(dist / speed), 1).astype(np.int64)
        n_fwd = (int(trips[r]) + 1) // 2
        n_back = int(trips[r]) - n_fwd
        for seq, hop_s, m in ((stops, ride, n_fwd),
                              (stops[::-1], ride[::-1], n_back)):
            dep_off = np.concatenate([[0], np.cumsum(hop_s + dwell)[:-1]])
            start = _departures(rng, m, profile)
            keep = np.ones((m, hop_s.size), bool)
            keep[short_trip[first_trip:first_trip + m], -1] = False
            first_trip += m
            dep = start[:, None] + dep_off[None, :]
            src.append(np.broadcast_to(seq[:-1], dep.shape)[keep])
            dst.append(np.broadcast_to(seq[1:], dep.shape)[keep])
            ts.append(dep[keep])
            te.append((dep + hop_s[None, :])[keep])
            trip_hops.append(keep.sum(axis=1))
    return (np.concatenate(src), np.concatenate(dst),
            np.concatenate(ts).astype(np.int32),
            np.concatenate(te).astype(np.int32), np.concatenate(trip_hops))


def generate(cfg: dict, seed: int) -> Columns:
    """The deployment's connections: one timetable, labelled by ``seed``."""
    src, dst, t_start, t_end, _ = draw(cfg)
    n_v = int(cfg["n_stops"])
    rank_to_id = rng_for(seed, 0).permutation(n_v).astype(np.int32)
    return Columns(n_vertices=n_v, src=rank_to_id[src], dst=rank_to_id[dst],
                   t_start=t_start, t_end=t_end,
                   span_s=max(int(t_end.max()) + 1, DAY_S),
                   rank_to_id=rank_to_id)
