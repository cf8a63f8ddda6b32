"""PageRank (paper Fig. 14) as a TOTEM vertex program.

The paper uses a *pull* kernel (each vertex sums its in-neighbours' ranks);
algebraically identical is the *push* form used here — each vertex pushes
``rank / out_degree`` along its out-edges and the engine sum-reduces — which
shares the outbox machinery with the other algorithms and is how the paper's
own boundary-edge communication works for PR (the rank sum is reducible,
§3.4).  Damping and termination follow the paper: a fixed number of rounds.

Distribution note: per-vertex constants (inverse out-degree, vertex mask)
ride in the state pytree so they shard with the partitions — closures over
global arrays would silently replicate under shard_map.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.bsp import (SUM, BSPEngine, EdgeMessage, VertexProgram,
                            batch_state, gather_src, unbatch_state)
from repro.kernels import ops as kops

DAMPING = 0.85


def _edge_fn(state, src, weight, step):
    del weight, step
    return gather_src(state["rank"] * state["inv_deg"], src)


def _edge_msg_fn(vals, weight, step, consts):
    del weight, step, consts
    return vals["rank"] * vals["inv_deg"]


@functools.lru_cache(maxsize=None)
def make_pagerank_program(num_vertices: int, damping: float = DAMPING,
                          max_steps: int = 1 << 30) -> VertexProgram:
    delta = (1.0 - damping) / num_vertices

    def apply_fn(state, acc, step):
        # The barrier pins mul-then-add rounding: XLA is otherwise free to
        # contract ``delta + damping * acc`` into an FMA, and it decides
        # per fusion context — the resident while_loop body and the
        # out-of-core streamed superstep would then disagree by 1 ulp.
        rank = delta + kops.pin(damping * acc)
        rank = jnp.where(state["mask"], rank, 0.0)
        return dict(state, rank=rank), jnp.bool_(True)

    # Weightless sum combine → the hybrid backend runs PR under plus_times:
    # the dense block's multi-edge counts ride in the adjacency values.  The
    # distributed hybrid sum-reduces boundary contributions into outbox
    # slots at the source — the paper's §3.4 "rank sum is reducible" case.
    return VertexProgram(combine=SUM, edge_fn=_edge_fn, apply_fn=apply_fn,
                         max_steps=max_steps,
                         edge_msg=EdgeMessage(gather=("rank", "inv_deg"),
                                              fn=_edge_msg_fn))


def initial_state(pg, damping: float = DAMPING) -> dict:
    out_deg = pg.out_deg
    inv = np.where(out_deg > 0, 1.0 / np.maximum(out_deg, 1.0), 0.0)
    rank0 = np.where(pg.vertex_mask, 1.0 / pg.num_vertices, 0.0)
    return {"rank": jnp.asarray(rank0, jnp.float32),
            "inv_deg": jnp.asarray(inv, jnp.float32),
            "mask": jnp.asarray(pg.vertex_mask)}


def pagerank(engine: BSPEngine, num_iterations: int = 20,
             damping: float = DAMPING) -> np.ndarray:
    pg = engine.pg
    program = make_pagerank_program(pg.num_vertices, damping)
    with obs.span(obs.STATE_INIT):
        state0 = batch_state(initial_state(pg))
    state = unbatch_state(engine.execute(program, state0,
                                         num_steps=num_iterations))
    with obs.span(obs.FETCH):
        with obs.span(obs.WAIT):
            rank = np.asarray(state["rank"])
        return pg.gather_global(rank)


def make_personalized_pagerank_program(damping: float = DAMPING,
                                       max_steps: int = 1 << 30
                                       ) -> VertexProgram:
    """PPR: the uniform teleport ``(1-d)/n`` becomes a per-query restart
    distribution carried in ``state["reset"]`` — the query axis is what
    makes one engine run serve Q personalizations at once."""
    def apply_fn(state, acc, step):
        rank = (1.0 - damping) * state["reset"] + damping * acc
        rank = jnp.where(state["mask"], rank, 0.0)
        return dict(state, rank=rank), jnp.bool_(True)

    return VertexProgram(combine=SUM, edge_fn=_edge_fn, apply_fn=apply_fn,
                         max_steps=max_steps,
                         edge_msg=EdgeMessage(gather=("rank", "inv_deg"),
                                              fn=_edge_msg_fn))


@functools.lru_cache(maxsize=None)
def _ppr_program(damping: float, num_iterations: int) -> VertexProgram:
    """Memoized so repeated serving batches reuse one compiled loop (the
    engine's jit cache keys on program identity)."""
    program = make_personalized_pagerank_program(damping,
                                                 max_steps=num_iterations)
    return dataclasses.replace(program,
                               apply_fn=_never_finished(program.apply_fn))


def personalized_pagerank(engine: BSPEngine, reset,
                          num_iterations: int = 20,
                          damping: float = DAMPING) -> np.ndarray:
    """Batched personalized PageRank: one run, Q restart distributions.

    ``reset`` is either [Q, n] per-query restart distributions (each row a
    probability vector over global vertex ids) or a length-Q sequence of
    seed vertex ids (one-hot teleport).  Iteration count is fixed (paper
    Fig. 14 termination); ranks start *at* the reset distribution.  Works on
    both the single-device and the distributed engine (the fixed round
    count rides ``max_steps`` with a never-finished vote, the same device
    as ``pagerank_distributed``).  Returns ranks [Q, n].
    """
    from repro.algorithms.bfs import gather_batch

    pg = engine.pg
    reset = np.asarray(reset)
    if reset.ndim == 1:                      # seed vertex ids → one-hot
        seeds = reset.astype(np.int64)
        reset = np.zeros((len(seeds), pg.num_vertices), dtype=np.float32)
        reset[np.arange(len(seeds)), seeds] = 1.0
    q = reset.shape[0]
    base = initial_state(pg, damping)
    reset_p = np.stack([pg.scatter_global(row.astype(np.float32), 0.0)
                        for row in reset])
    state = {
        "rank": jnp.asarray(reset_p),
        "reset": jnp.asarray(reset_p),
        # query-independent constants, broadcast along the query axis
        "inv_deg": jnp.broadcast_to(base["inv_deg"],
                                    (q,) + base["inv_deg"].shape),
        "mask": jnp.broadcast_to(base["mask"], (q,) + base["mask"].shape),
    }
    out, _ = engine.execute(_ppr_program(damping, num_iterations), state)
    return gather_batch(pg, out["rank"])


def personalized_pagerank_reference(g, reset, num_iterations: int = 20,
                                    damping: float = DAMPING) -> np.ndarray:
    """Pure-numpy batched PPR oracle (same push semantics as the engine)."""
    n = g.num_vertices
    reset = np.asarray(reset, dtype=np.float64)
    q = reset.shape[0]
    deg = g.out_degrees().astype(np.float64)
    src = g.edge_sources()
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0)
    rank = reset.copy()
    rows = np.arange(q)[:, None]
    for _ in range(num_iterations):
        contrib = (rank * inv)[:, src]
        acc = np.zeros((q, n))
        np.add.at(acc, (rows, g.col[None, :]), contrib)
        rank = (1.0 - damping) * reset + damping * acc
    return rank.astype(np.float32)


def pagerank_distributed(engine, num_iterations: int = 20,
                         damping: float = DAMPING) -> np.ndarray:
    """PageRank on a DistributedBSPEngine (fixed-round via max_steps)."""
    pg = engine.pg
    program = make_pagerank_program(pg.num_vertices, damping,
                                    max_steps=num_iterations)
    # run() terminates early only if a program votes finish with False
    # improvement; PR always votes True, so force the round count:
    program = dataclasses.replace(
        program,
        apply_fn=_never_finished(program.apply_fn))
    state_b, _ = engine.execute(program, batch_state(initial_state(pg)))
    return pg.gather_global(np.asarray(unbatch_state(state_b)["rank"]))


def _never_finished(apply_fn):
    def wrapped(state, acc, step):
        new_state, _ = apply_fn(state, acc, step)
        return new_state, jnp.bool_(False)
    return wrapped


def pagerank_reference(g, num_iterations: int = 20,
                       damping: float = DAMPING) -> np.ndarray:
    """Pure-numpy push PageRank oracle (same semantics, incl. dangling)."""
    n = g.num_vertices
    deg = g.out_degrees().astype(np.float64)
    src = g.edge_sources()
    rank = np.full(n, 1.0 / n)
    delta = (1.0 - damping) / n
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0)
    for _ in range(num_iterations):
        contrib = rank * inv
        acc = np.zeros(n)
        np.add.at(acc, g.col, contrib[src])
        rank = delta + damping * acc
    return rank.astype(np.float32)
