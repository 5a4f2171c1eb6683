"""Every deployment keeps its source's published counts, every seed serves
the same graph under other labels, and every traffic generator gives one
sequence of requests per seed."""
import hashlib

import numpy as np
import pytest

import cellcheck
from bench import deployment, harness
from bench import traffic as tr

SEEDS = (5, 2**31 + 7)
CONFIGS = ("sx-stackoverflow", "wiki-talk")


def _cols(seed, workload):
    return deployment.generate(cellcheck.small_config(workload), seed)


def _ranks(cols):
    """The columns with every label mapped back to its rank."""
    id_to_rank = np.argsort(cols.rank_to_id)
    return id_to_rank[cols.src], id_to_rank[cols.dst]


@pytest.mark.parametrize("seed", SEEDS)
def test_columns_repeat_per_seed(seed):
    a, b = (_cols(seed, "so-batch16-w35d") for _ in range(2))
    for f in ("src", "dst", "t_start", "t_end", "rank_to_id"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert (a.t_end >= a.t_start).all() and (a.src != a.dst).all()


# SHA-256 of the five columns drawn at the small size, as the benchmark
# drew them when deployments could first bring a generator of their own:
# a change to the stock draw changes what every existing cell serves.
PINNED = {
    ("sx-stackoverflow", 5):
        "0948c8c7b12fe79fd9bfca0694189c21c56cf913865c0552f08bc8420d538ecf",
    ("sx-stackoverflow", 2**31 + 7):
        "7e78356eb2dc566055c94c0e47afc22dc3fa783b319098a28d10ec73583f1fa5",
    ("wiki-talk", 5):
        "24e60a8bf4683f59fcd73138423cff1d6572eb9c2c9eeb245e001b298c960017",
    ("wiki-talk", 2**31 + 7):
        "d93fc9c6219d4f326787606ed881f577d52a471b2216d262e6bdeff70962e403",
}


@pytest.mark.parametrize("config, seed", PINNED)
def test_existing_deployments_draw_the_pinned_columns(config, seed):
    cfg = harness.load_config(config, cellcheck.ROOT)
    assert "generator" not in cfg
    cols = deployment.generate(cellcheck.small(cfg), seed)
    h = hashlib.sha256()
    for f in ("src", "dst", "t_start", "t_end", "rank_to_id"):
        a = np.ascontiguousarray(getattr(cols, f))
        h.update(f.encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PINNED[config, seed]


@pytest.mark.parametrize("seed", SEEDS)
def test_every_seed_serves_one_graph_under_other_labels(seed):
    a, c = _cols(seed, "so-batch16-w35d"), _cols(seed + 1, "so-batch16-w35d")
    assert not np.array_equal(a.src, c.src)
    for x, y in zip(_ranks(a), _ranks(c)):
        assert np.array_equal(x, y)
    assert np.array_equal(a.t_start, c.t_start)
    assert np.array_equal(a.t_end, c.t_end)


@pytest.mark.parametrize("config", CONFIGS)
def test_no_vertex_holds_a_large_share_of_the_edges(config):
    """At full size the degree law gives the busiest vertex under 0.5% of
    all endpoints and the ten busiest under 1%."""
    cfg = harness.load_config(config, cellcheck.ROOT)
    n, a = cfg["n_vertices"], cfg["degree_exponent"]
    assert deployment.power_law_share(n, a, 1) < 0.005
    assert deployment.power_law_share(n, a, 10) < 0.01


def test_wiki_talk_at_full_size_keeps_the_published_counts():
    cfg = harness.load_config("wiki-talk", cellcheck.ROOT)
    cols = deployment.generate(cfg, 3)
    assert cols.n_vertices == cfg["n_vertices"]
    assert cols.n_edges == cfg["n_edges"]
    keys = cols.src.astype(np.int64) * cols.n_vertices + cols.dst
    assert np.unique(keys).size == cfg["n_static_edges"]
    deg = (np.bincount(cols.src, minlength=cols.n_vertices)
           + np.bincount(cols.dst, minlength=cols.n_vertices))
    assert deg.max() < 0.005 * deg.sum()
    assert cols.t_start.min() >= 0
    assert cols.t_start.max() < cfg["span_days"] * deployment.DAY_S


@pytest.mark.parametrize("config", CONFIGS)
def test_small_deployment_keeps_its_share_of_distinct_pairs(config):
    cfg = cellcheck.small(harness.load_config(config, cellcheck.ROOT))
    cols = deployment.generate(cfg, 9)
    keys = cols.src.astype(np.int64) * cols.n_vertices + cols.dst
    assert np.unique(keys).size == cfg["n_static_edges"]


def _requests(mode, mix, cols, seed):
    gen = tr.GENERATORS[mode](mix, cols, seed)
    if mode == "batch":
        return [gen.advance(k) for k in range(4)]
    warm, timed = gen.split_warm(cols)
    return [gen.hot_query()] + warm + timed


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("workload", ["so-batch16-w35d", "wt-history-w7d"])
def test_traffic_repeats_per_seed(workload, seed):
    mix = harness.load_traffic(cellcheck.CELLS[workload][1], cellcheck.ROOT)
    cols = _cols(seed, workload)
    first = _requests(mix["mode"], mix, cols, seed)
    assert first == _requests(mix["mode"], mix, cols, seed)
    other = _cols(seed + 1, workload)
    assert first != _requests(mix["mode"], mix, other, seed + 1)


def test_batch_asks_the_same_ranks_under_every_seed():
    mix = harness.load_traffic("batch16-w35d", cellcheck.ROOT)
    ranks = []
    for seed in SEEDS:
        cols = _cols(seed, "so-batch16-w35d")
        id_to_rank = np.argsort(cols.rank_to_id)
        gen = tr.Batch(mix, cols, seed)
        ranks.append([None if a.source is None else int(id_to_rank[a.source])
                      for a in gen.advance(0)])
        windows = [a.window for a in gen.advance(0)]
    assert ranks[0] == ranks[1]
    assert len({a for a in windows}) == 2


def test_history_windows_are_distinct_and_evicted():
    mix = harness.load_traffic("history-w7d", cellcheck.ROOT)
    cols = _cols(3, "wt-history-w7d")
    gen = tr.History(mix, cols, 3)
    ends = [q[0].window[1] for q in gen.pool]
    assert len(set(ends)) == len(ends) == mix["query_pool"]
    assert max(ends) < gen.hot[0] and min(q[0].window[0] for q in gen.pool) >= 0
