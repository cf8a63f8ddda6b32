"""Distributed self-test: run the BSP engine sharded over N host devices and
compare against the single-device engine.  Invoked in a subprocess (so the
device-count env var doesn't leak into the main test process):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m repro.launch.selftest
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np


def main() -> int:
    n_dev = len(jax.devices())
    assert n_dev >= 2, f"need >1 device, got {n_dev}"
    from repro.core import graph as G
    from repro.core import partition as PT
    from repro.core.bsp import (BSPEngine, DistributedBSPEngine,
                                batch_state, unbatch_state)
    from repro.algorithms import bfs, pagerank
    from repro.algorithms.bfs import BFS_PROGRAM
    from repro.algorithms.pagerank import pagerank_distributed

    mesh = jax.make_mesh((n_dev,), ("parts",))
    g = G.rmat(10, 8, seed=7)
    pg = PT.partition(g, n_dev, PT.HIGH, align=8)

    local = BSPEngine(pg)
    dist = DistributedBSPEngine(pg, mesh)

    # BFS
    lv_local, _ = bfs(local, source=0)
    level0 = np.full((pg.num_parts, pg.v_max), np.inf, dtype=np.float32)
    sp = int(pg.assignment.part_of[0])
    sl = int(pg.assignment.local_id[0])
    level0[sp, sl] = 0.0
    state_b, steps_q = dist.execute(BFS_PROGRAM,
                                    batch_state({"level":
                                                 jnp.asarray(level0)}))
    state, steps = unbatch_state(state_b), steps_q[0]
    lv_dist = pg.gather_global(np.asarray(state["level"]))
    np.testing.assert_array_equal(lv_local, lv_dist)
    print(f"BFS distributed == local over {n_dev} devices "
          f"({int(steps)} supersteps)")

    # PageRank
    pr_local = pagerank(local, num_iterations=10)
    pr_dist = pagerank_distributed(dist, num_iterations=10)
    np.testing.assert_allclose(pr_local, pr_dist, rtol=1e-5, atol=1e-8)
    print("PageRank distributed == local")

    # Fused superstep path (Pallas kernel) sharded over the mesh:
    # shard_map + fused compute must compose.
    fused = DistributedBSPEngine(pg, mesh, fused=True)
    state_b, _ = fused.execute(BFS_PROGRAM,
                               batch_state({"level": jnp.asarray(level0)}))
    lv_fused = pg.gather_global(np.asarray(unbatch_state(state_b)["level"]))
    np.testing.assert_array_equal(lv_local, lv_fused)
    pr_fused = pagerank_distributed(fused, num_iterations=10)
    np.testing.assert_allclose(pr_local, pr_fused, rtol=1e-5, atol=1e-8)
    print("Fused superstep distributed == local")
    print("SELFTEST OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
