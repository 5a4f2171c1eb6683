"""Departures: ``tenants`` tenants ask the listed algorithms round-robin
over one ``window_s`` window of the timetable, slid ``stride_s`` per
closed-loop advance from the start of service; each tenant keeps one stop,
drawn uniformly by rank from the run's seed."""
from bench import serving
from bench import traffic as tr
from bench.deployment import rng_for


class Departures:
    def __init__(self, mix: dict, cols, seed: int):
        self.mix = mix
        self.width, self.stride = int(mix["window_s"]), int(mix["stride_s"])
        self.max_advances = (cols.span_s - self.width) // self.stride + 1
        ranks = rng_for(seed, 2).integers(0, cols.n_vertices,
                                          int(mix["tenants"]))
        self.sources = [int(cols.rank_to_id[r]) for r in ranks]

    def advance(self, k: int):
        window = (k * self.stride, k * self.stride + self.width)
        algs = self.mix["algorithms"]
        asks = []
        for i in range(int(self.mix["tenants"])):
            alg = algs[i % len(algs)]
            asks.append(tr.Ask(
                alg, window,
                None if alg in tr.SOURCE_FREE else self.sources[i],
                tr.ask_params(self.mix, alg)))
        return asks


class Client(serving.BatchClient):
    requests = Departures
