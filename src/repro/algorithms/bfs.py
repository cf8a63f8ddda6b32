"""Level-synchronous BFS (paper Fig. 11) as a TOTEM vertex program.

Push formulation with min-reduction: every vertex at the current level sends
``level + 1`` along its out-edges; the reduction keeps the minimum, and
unvisited vertices adopt it.  Identical to the paper's kernel where the
"visited" test is the ``level == INF`` check (the cache-resident bitmap is a
CPU-specific optimization; the TPU analogue is the VMEM-resident frontier of
the dense block — see kernels/dense_spmv).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.bsp import (MIN, BSPEngine, EdgeMessage, IncrementalForm,
                            VertexProgram, gather_src)
from repro.core.graph import CSRGraph
from repro.core.partition import PartitionedGraph

INF = jnp.float32(jnp.inf)


def multi_source_state(pg: PartitionedGraph, sources: Sequence[int],
                       fill=np.inf, value=0.0) -> np.ndarray:
    """[Q, P, v_max] per-query state with ``value`` at each query's source.

    The shared multi-source constructor: one row per query, ``fill``
    elsewhere — BFS levels, SSSP distances, and BC's dist/sigma all start
    from this shape.
    """
    sources = np.asarray(sources, dtype=np.int64).reshape(-1)
    out = np.full((len(sources), pg.num_parts, pg.v_max), fill,
                  dtype=np.float32)
    out[np.arange(len(sources)), pg.assignment.part_of[sources],
        pg.assignment.local_id[sources]] = value
    return out


def gather_batch(pg: PartitionedGraph, per_part: np.ndarray) -> np.ndarray:
    """Collect a [Q, P, v_max] batched state into global [Q, n] order."""
    with obs.span(obs.FETCH):
        with obs.span(obs.WAIT):
            per_part = np.asarray(per_part)
        return np.stack([pg.gather_global(row) for row in per_part])


def _edge_fn(state, src, weight, step):
    del weight
    level = gather_src(state["level"], src)
    # Only frontier vertices (level == step) send; others send identity.
    return jnp.where(level == step.astype(jnp.float32), level + 1.0, INF)


def _edge_msg_fn(vals, weight, step, consts):
    del weight, consts
    level = vals["level"]
    # np.inf (not the jnp INF const): Pallas kernels may not capture arrays.
    return jnp.where(level == step, level + 1.0, np.inf)


def _apply_fn(state, acc, step):
    del step
    level = state["level"]
    newly = jnp.isinf(level) & jnp.isfinite(acc)
    new_level = jnp.where(newly, acc, level)
    finished = ~jnp.any(newly)
    return {"level": new_level}, finished


# --- incremental (warm-start) form -----------------------------------------
# The level-synchronous program cannot lower a *finite* level (its frontier
# test is ``level == step`` and its apply only fills unvisited vertices), so
# warm starts run BFS's relaxation restatement instead: unit-weight
# Bellman-Ford over levels with an active set.  Its fixpoint is reachable by
# descent from any over-approximation — exactly the previous solution after
# insert-only mutations — and since levels are small exact-f32 integers the
# warm fixpoint is *bitwise* equal to a cold rerun (docs/dynamic.md).

def _inc_edge_fn(state, src, weight, step):
    del weight, step
    level = gather_src(state["level"], src)
    active = gather_src(state["active"].astype(jnp.float32), src) > 0
    return jnp.where(active, level + 1.0, INF)


def _inc_edge_msg_fn(vals, weight, step, consts):
    del weight, step, consts
    # np.inf (not the jnp INF const): Pallas kernels may not capture arrays.
    return jnp.where(vals["active"] > 0, vals["level"] + 1.0, np.inf)


def _inc_apply_fn(state, acc, step):
    del step
    level = state["level"]
    improved = acc < level
    new_level = jnp.where(improved, acc, level)
    return {"level": new_level, "active": improved}, ~jnp.any(improved)


BFS_RELAX_PROGRAM = VertexProgram(
    combine=MIN, edge_fn=_inc_edge_fn, apply_fn=_inc_apply_fn,
    edge_msg=EdgeMessage(gather=("level", "active"), fn=_inc_edge_msg_fn))


def _inc_seed(prev_state, dirty):
    """Warm state: previous levels + dirty-frontier active set.  ``dirty``
    is a [Pl, v_max] mask of vertices whose out-edges changed; only dirty
    vertices that are themselves reached can improve a neighbour."""
    level = prev_state["level"]
    active = jnp.logical_and(jnp.broadcast_to(dirty, level.shape),
                             jnp.isfinite(level))
    return {"level": level, "active": active}


# Weightless min combine → the hybrid backend runs BFS under the pure-min
# semiring (the message already carries level+1), with the frontier-density
# push/pull direction switch as the traversal showcase: sparse frontiers take
# the push segment-min, dense frontiers the frontier-oblivious SpMV pull.
# Under the distributed hybrid, boundary levels min-reduce into outbox slots
# at the source, so frontier-sparse supersteps ship aggregated slots (not
# per-edge messages) over the mesh axis.
BFS_PROGRAM = VertexProgram(combine=MIN, edge_fn=_edge_fn,
                            apply_fn=_apply_fn,
                            # Every frontier vertex sends the SAME value
                            # (step+1) — the bottom-up kernel's early exit
                            # is exact (kernels/bottomup.py).
                            edge_msg=EdgeMessage(gather=("level",),
                                                 fn=_edge_msg_fn,
                                                 frontier_uniform=True),
                            incremental=IncrementalForm(BFS_RELAX_PROGRAM,
                                                        _inc_seed))


def bfs_batched(engine: BSPEngine,
                sources: Sequence[int]) -> Tuple[np.ndarray, np.ndarray]:
    """Run a batch of Q BFS queries through one engine invocation.

    All queries share the resident partitioned graph and advance through a
    single compiled ``lax.while_loop``; each converges independently.
    Returns (levels [Q, n], per-query supersteps [Q]).
    """
    pg = engine.pg
    with obs.span(obs.STATE_INIT):
        level0 = jnp.asarray(multi_source_state(pg, sources))
    state, steps = engine.execute(BFS_PROGRAM, {"level": level0})
    return gather_batch(pg, state["level"]), np.asarray(steps)


def bfs(engine: BSPEngine, source: int) -> Tuple[np.ndarray, int]:
    """Run BFS from global vertex ``source``; returns (levels [n], steps)."""
    levels, steps = bfs_batched(engine, [source])
    return levels[0], int(steps[0])


def bfs_incremental(engine: BSPEngine, prev_levels: np.ndarray,
                    dirty_global: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Warm-start a batch of BFS solutions after insert-only mutations.

    ``prev_levels`` is the [Q, n] (or [n]) result of an earlier run whose
    sources are being kept fresh; ``dirty_global`` the [n] mask of vertices
    with inserted out-edges since (``DynamicGraph.dirty_since`` — the caller
    must fall back to cold :func:`bfs_batched` when that window was not
    monotone).  Returns (levels [Q, n], supersteps [Q]) — bitwise equal to
    a cold rerun, typically in a fraction of the supersteps.
    """
    pg = engine.pg
    prev = np.atleast_2d(np.asarray(prev_levels, dtype=np.float32))
    state = {"level": jnp.asarray(np.stack(
        [pg.scatter_global(row, np.inf) for row in prev]))}
    st, steps = engine.execute(BFS_PROGRAM, state,
                               incremental=pg.scatter_dirty(dirty_global))
    return gather_batch(pg, st["level"]), np.asarray(steps)


def bfs_reference(g: CSRGraph, source: int) -> np.ndarray:
    """Pure-numpy frontier BFS oracle."""
    n = g.num_vertices
    level = np.full(n, np.inf, dtype=np.float32)
    level[source] = 0.0
    frontier = np.array([source], dtype=np.int64)
    d = 0
    while len(frontier):
        nbrs = np.concatenate([
            g.col[g.row_ptr[v]: g.row_ptr[v + 1]] for v in frontier
        ]) if len(frontier) else np.empty(0, dtype=np.int64)
        nbrs = np.unique(nbrs)
        newly = nbrs[np.isinf(level[nbrs])]
        level[newly] = d + 1
        frontier = newly
        d += 1
    return level


def teps(g: CSRGraph, levels: np.ndarray, seconds: float) -> float:
    """Graph500-style TEPS: sum of degrees of visited vertices / time."""
    visited = np.isfinite(levels)
    traversed = int(g.out_degrees()[visited].sum())
    return traversed / max(seconds, 1e-12)
