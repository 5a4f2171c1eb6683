"""Paper Figure 7: parallel scaling (runtime vs worker count).

The paper sweeps 1..24 cores; this container has one physical core, so we
sweep XLA host-platform device counts (1/2/4/8) in subprocesses running the
*distributed* engine — measuring the structural overhead/benefit of the
edge-partitioned shard_map program.  On real multi-core/TPU hardware the
same sweep measures true parallel speedup.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import emit

_PROG = textwrap.dedent(
    """
    import os, sys, json, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
    sys.path.insert(0, "src")
    import numpy as np, jax, jax.numpy as jnp
    from repro.data.generators import synthetic_temporal_graph
    from repro.distributed import graph_engine as ge
    from repro.core.edgemap import INT_INF

    n_dev = %d
    from repro.distributed.compat import make_mesh
    mesh = make_mesh((n_dev, 1), ("data", "model"))
    g = synthetic_temporal_graph(20_000, 1_000_000, seed=3)
    ts = np.asarray(g.t_start)
    win = jnp.asarray([int(np.quantile(ts, 0.9)), int(np.asarray(g.t_end).max())],
                      jnp.int32)
    arr0 = jnp.full((4, g.n_vertices), INT_INF, jnp.int32)
    arr0 = arr0.at[jnp.arange(4), jnp.arange(4)].set(win[0])
    edges = ge.shard_edges(mesh, g.src, g.dst, g.t_start, g.t_end)
    evalid = ge.shard_edges(mesh, jnp.ones(g.n_edges, bool))[0]
    from repro.engine.plan import make_plan
    rnd = jax.jit(ge.make_ea_round_plan(mesh, g.n_vertices, make_plan("scan")))
    out = rnd(arr0, *edges, evalid, win)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(5):
        out = rnd(out, *edges, evalid, win)
    jax.block_until_ready(out)
    print(json.dumps({"sec_per_round": (time.perf_counter() - t0) / 5}))
    """
)


def run(dev_counts=(1, 2, 4, 8)):
    base = None
    # the children measure forced CPU host devices, never the accelerator
    # the parent may hold
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for n in dev_counts:
        out = subprocess.run(
            [sys.executable, "-c", _PROG % (n, n)],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"fig7 ea_round child on {n} host devices failed:\n"
                f"{out.stderr[-3000:]}")
        sec = json.loads(out.stdout.strip().splitlines()[-1])["sec_per_round"]
        base = base or sec
        emit(f"fig7/ea_round/dev{n}", sec, f"speedup_vs_1dev={base/sec:.2f}x")


if __name__ == "__main__":
    run()
