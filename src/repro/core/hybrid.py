"""The hybrid two-engine step — TOTEM's CPU/GPU split, TPU-native.

The paper assigns the few high-degree vertices to the CPU (cache-friendly)
and the many low-degree vertices to the GPU (latency-hiding) — §6.2.  A TPU
chip is homogeneous silicon but has two *execution paths* with exactly the
same duality:

  - the **MXU** (systolic matmul): the high-degree block's adjacency is dense
    enough that SpMV-as-GEMM wins (kernels/dense_spmv);
  - the **VPU + HBM streaming** path: the low-degree remainder has a tight
    degree bound, ideal for ELLPACK row-block streaming (kernels/ell_spmv).

``degree_split`` plays the role of the paper's HIGH partitioning: vertices
are ranked by (in+out) degree, the top-K become the dense block H, and every
edge inside H×H moves to the dense engine; the rest stays sparse.

Generalized semirings (one per TOTEM reduction class, §3.4) make the split a
backend for *every* vertex program, not just SpMV-style PageRank:

  - ``plus_times`` — y[v] = Σ x[u]·w(u,v)        (PageRank, BC)
  - ``min_plus``   — y[v] = min x[u]+w(u,v)      (BFS, SSSP)
  - ``min``        — y[v] = min x[u]             (CC label propagation)

``auto_degree_split`` drives |H| from the performance model: candidate splits
are ranked by ``perf_model.hybrid_makespan_tpu`` (the role Eq. 4 plays in the
paper) and the argmin wins — which may be 0 (pure sparse) or the whole graph
(pure dense); ``HybridGraph.mode`` reports which engine(s) actually run.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.graph import CSRGraph, from_edge_list
from repro.core import perf_model
from repro.core.partition import (EdgeArrays, PartitionedGraph,
                                  _round_up, boundary_edges,
                                  build_block_metadata)
from repro.kernels import ops as kops
from repro.kernels.ell_spmv import SEMIRINGS

PLUS_TIMES = "plus_times"
MIN_PLUS = "min_plus"
MIN_SR = "min"


def add_identity(semiring: str) -> float:
    """⊕-identity of a semiring (0 for sum, +inf for min)."""
    return SEMIRINGS[semiring][1]


@dataclasses.dataclass
class HybridGraph:
    """Degree-split graph: dense H×H block + ELL remainder (pull form)."""

    num_vertices: int
    num_edges: int
    k_dense: int                 # |H| (0 → pure sparse)
    perm: np.ndarray             # new -> old id (H, then rows longest first)
    inv_perm: np.ndarray         # old id -> new id
    dense_block: np.ndarray      # [K, K] f32 (⊗ values; ⊕-identity non-edges)
    ell_col: np.ndarray          # [V, kmax] int32 (pull: in-neighbours)
    ell_val: np.ndarray          # [V, kmax] f32
    out_deg: np.ndarray          # [V] f32 in new id space (true out-degree)
    dense_edges: int             # edges handled by the MXU path
    sparse_edges: int            # edges handled by the ELL path
    semiring: str = PLUS_TIMES
    model_table: Optional[List[dict]] = None  # perf-model ranking (auto split)

    @property
    def dense_density(self) -> float:
        return self.dense_edges / max(self.k_dense ** 2, 1)

    @property
    def dense_fraction(self) -> float:
        return self.dense_edges / max(self.num_edges, 1)

    @property
    def mode(self) -> str:
        """Which engine(s) this split runs: dense, sparse, or hybrid."""
        return perf_model.split_mode(self.k_dense, self.num_vertices,
                                     self.sparse_edges)

    def predicted_makespan(self, num_chips: int = 1) -> dict:
        return perf_model.hybrid_makespan_tpu(
            self.dense_edges, self.dense_density, self.sparse_edges,
            boundary_slots=0, num_chips=num_chips)

    def sliced(self):
        """The ELL remainder in the sliced layout of kernels/ell_spmv.py:
        ``(col [Σ w_c, C], val [Σ w_c, C], widths [nc])``.  The rows are
        ordered longest first below the dense block, so each chunk's width
        is close to its rows' real lengths."""
        return kops.sliced_ell(self.ell_col, self.ell_val, self.num_vertices,
                               SEMIRINGS[self.semiring][2])


def _degree_perm(g: CSRGraph):
    """Degree-descending vertex ranking (new -> old) and its inverse."""
    total_deg = g.out_degrees() + g.in_degrees()
    perm = np.argsort(-total_deg, kind="stable")       # new -> old
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm))
    return perm, inv


def edge_max_ranks(g: CSRGraph) -> np.ndarray:
    """Per-edge max(rank(src), rank(dst)) under the degree ranking.

    ``e_dense(k) = #{edges with max rank < k}`` — the perf model's input for
    ranking candidate splits (symmetric under graph reversal, so one table
    serves both edge directions).
    """
    _, inv = _degree_perm(g)
    return np.maximum(inv[g.edge_sources()], inv[g.col])


def degree_split(g: CSRGraph, k_dense: int,
                 semiring: str = PLUS_TIMES) -> HybridGraph:
    """Split ``g``: top-``k_dense`` degree vertices → dense block.

    The top ``k_dense`` vertices keep the total-degree order of
    ``edge_max_ranks`` (the planner's and the dense block's); the others
    follow by ELL row length (in-degree), longest first, ties in
    total-degree order, so the sliced ELL's chunks hold rows of near-equal
    length (``HybridGraph.sliced``).

    Edge ⊗ values follow the semiring (kernels/ops.csr_to_ell): weights where
    the graph has them, multiplicity counts (``plus_times``) or zero-cost
    hops (``min_plus``) otherwise.  Multi-edges accumulate with ⊕ in the
    dense block, matching the reference engine's per-edge reduction.
    """
    if semiring not in SEMIRINGS:
        raise ValueError(f"unknown semiring {semiring!r}")
    perm, _ = _degree_perm(g)
    tail = perm[k_dense:]
    tail = tail[np.argsort(-g.in_degrees()[tail], kind="stable")]
    perm = np.concatenate([perm[:k_dense], tail])
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm))
    src = inv[g.edge_sources()]
    dst = inv[g.col]
    if semiring == PLUS_TIMES:
        w = (g.weights if g.weights is not None
             else np.ones(g.num_edges, dtype=np.float32))
    elif semiring == MIN_PLUS:
        w = (g.weights if g.weights is not None
             else np.zeros(g.num_edges, dtype=np.float32))
    else:  # pure min: edge values are irrelevant, hop cost 0
        w = np.zeros(g.num_edges, dtype=np.float32)

    in_h = (src < k_dense) & (dst < k_dense)
    dense = np.full((k_dense, k_dense), add_identity(semiring),
                    dtype=np.float32)
    if k_dense:
        if semiring == PLUS_TIMES:
            np.add.at(dense, (src[in_h], dst[in_h]), w[in_h])
        else:
            np.minimum.at(dense, (src[in_h], dst[in_h]), w[in_h])

    rest = ~in_h
    # Attach explicit per-edge values (w holds the per-semiring defaults) so
    # the ELL packing always matches the dense block, independent of
    # csr_to_ell's unweighted fallbacks; pure-min values are never read.
    rest_w = w[rest] if semiring != MIN_SR else None
    g_rest = from_edge_list(src[rest], dst[rest], g.num_vertices,
                            weights=rest_w)
    col, val, _ = kops.csr_to_ell(g_rest, semiring=semiring, transpose=True)

    deg = g.out_degrees().astype(np.float32)[perm]
    return HybridGraph(
        num_vertices=g.num_vertices, num_edges=g.num_edges, k_dense=k_dense,
        perm=perm, inv_perm=inv, dense_block=dense, ell_col=col, ell_val=val,
        out_deg=deg, dense_edges=int(in_h.sum()), sparse_edges=int(rest.sum()),
        semiring=semiring)


def auto_degree_split(g: CSRGraph, semiring: str = PLUS_TIMES,
                      candidates=None, skewed: bool = True,
                      num_chips: int = 1) -> HybridGraph:
    """Degree split with |H| chosen by the performance model (Eq. 4 role).

    Ranks ``candidates`` (default: ``perf_model.k_dense_candidates``; pass
    ``skewed=False`` when the partition block-span histograms show no
    high-degree concentration) by predicted makespan and splits at the
    argmin.  The ranking table rides on the result for introspection.
    """
    if candidates is None:
        candidates = perf_model.k_dense_candidates(g.num_vertices,
                                                   skewed=skewed)
    k, table = perf_model.choose_k_dense(edge_max_ranks(g), g.num_edges,
                                         candidates, num_chips=num_chips)
    hg = degree_split(g, k, semiring=semiring)
    hg.model_table = table
    return hg


def hybrid_spmv(dense: jax.Array, ell_col: jax.Array, ell_val: jax.Array,
                x: jax.Array, *, semiring: str, k_dense: int,
                widths: Optional[np.ndarray] = None,
                interpret: Optional[bool] = None) -> jax.Array:
    """One generalized two-engine step: y[v] = ⊕ over in-edges x[u] ⊗ w.

    The dense H×H block runs on the MXU path (plus_times) or its tropical
    twin (min_plus/min); the remainder streams through the ELL kernel.  ``x``
    is the per-source value vector in hybrid (degree-ranked) id space — or a
    ``[Q, n]`` *query batch* of such vectors, in which case the batch rides
    the MXU's M axis (SpMV becomes SpMM: Q concurrent traversals amortize
    one pass over the resident adjacency) and the ELL kernel's leading grid
    axis; returns ``[Q, n]``.  ``ell_col``/``ell_val`` are rectangular
    ``[n, kmax]``, or sliced with their chunk ``widths``
    (``HybridGraph.sliced``).
    """
    ident = add_identity(semiring)
    squeeze = x.ndim == 1
    if squeeze:
        x = x[None]
    q = x.shape[0]
    with obs.phase("bsp.gather"):
        xs = jnp.concatenate([x, jnp.full((q, 1), ident, x.dtype)], axis=1)
    y = kops.ell_spmv_op(ell_col, ell_val, xs, semiring=semiring,
                         widths=widths, interpret=interpret)
    if k_dense:
        # The barriers pin the dense stage's rounding: interpret-mode
        # Pallas inlines the dot, and XLA's FMA-contraction choice for the
        # inlined reduction depends on the surrounding fusion context.  The
        # resident while_loop body and the out-of-core tiered jits (which
        # assemble y across jit boundaries) must round identically, so the
        # dense stage is compiled as the same isolated subgraph everywhere.
        with obs.phase("bsp.reduce"):
            xd = jax.lax.optimization_barrier(x[:, :k_dense])
            if semiring == PLUS_TIMES:
                yh = jax.lax.optimization_barrier(
                    kops.dense_spmv_op(xd, dense, interpret=interpret))
                y = y.at[:, :k_dense].add(yh)
            else:
                yh = jax.lax.optimization_barrier(
                    kops.dense_spmv_minplus_op(xd, dense,
                                               interpret=interpret))
                y = y.at[:, :k_dense].min(yh)
    return y[0] if squeeze else y


def hybrid_spmv_scan(dense: jax.Array, ell_col: jax.Array,
                     ell_val: Optional[jax.Array], x: jax.Array,
                     kreal: jax.Array, *, semiring: str, k_dense: int,
                     early_exit: bool = False,
                     skip: Optional[jax.Array] = None,
                     widths: Optional[np.ndarray] = None,
                     interpret: Optional[bool] = None):
    """``hybrid_spmv`` with the bottom-up scan kernel on the ELL path.

    Returns ``(y, scanned)`` where ``y`` is bitwise equal to
    ``hybrid_spmv``'s (the scan kernel's reduction is the same vectorized
    gather + row-min, and the dense MXU stage below is the identical
    barrier-pinned subgraph) and ``scanned [Q]`` sums the per-row
    early-exit work model (kernels/bottomup.py) — the slots a sequential
    bottom-up scan would examine.  ``kreal [n]`` is the per-row real slot
    count; ``skip`` [Q, n] marks rows whose value is already final under
    the uniform-frontier licence (they charge zero scanned slots — a
    sequential bottom-up visits only unvisited rows); min combines only.
    """
    ident = add_identity(semiring)
    squeeze = x.ndim == 1
    if squeeze:
        x = x[None]
    q = x.shape[0]
    with obs.phase("bsp.gather"):
        xs = jnp.concatenate([x, jnp.full((q, 1), ident, x.dtype)], axis=1)
    y, scanned = kops.bottomup_scan_op(
        ell_col, ell_val if semiring == MIN_PLUS else None, xs, kreal,
        semiring=semiring, early_exit=early_exit, skip=skip, widths=widths,
        interpret=interpret)
    if k_dense:
        # Same barrier discipline as hybrid_spmv — the two paths must round
        # identically so direction is purely a performance choice.
        with obs.phase("bsp.reduce"):
            xd = jax.lax.optimization_barrier(x[:, :k_dense])
            yh = jax.lax.optimization_barrier(
                kops.dense_spmv_minplus_op(xd, dense, interpret=interpret))
            y = y.at[:, :k_dense].min(yh)
    cnt = jnp.sum(scanned, axis=1)
    return (y[0], cnt[0]) if squeeze else (y, cnt)


# ---------------------------------------------------------------------------
# Per-shard degree split for the distributed hybrid engine (paper §4.3, §6)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ShardHybridData:
    """One direction's per-shard degree-split + outbox data, stacked on a
    leading shard axis so it shards over the mesh under ``shard_map``.

    Each shard (device) owns ``parts_per_shard`` consecutive partitions and
    runs the two-engine step over its *intra-partition* edges in a
    shard-local degree-ranked id space (``slot``/``hid`` translate to/from
    the engine's ``[pl, v_max]`` layout).  Every inter-partition edge rides
    the outbox-slot segment space from ``partition.py`` instead: boundary
    messages are reduced into ``o_max`` slots at the source (§3.4) and only
    the *used* slots of each (shard, peer-shard) pair are exchanged —
    ``send_idx``/``recv_ids`` are the static gather/scatter maps of that
    compact ``all_to_all``, ``loc_idx``/``loc_ids`` the same-device pairs
    that never touch the wire.  All shapes are shard-uniform (padded to the
    max over shards); pad slots read/write dedicated identity sinks.
    """

    semiring: str
    num_shards: int
    parts_per_shard: int      # pl
    v_max: int
    num_parts: int            # P
    o_max: int
    k_dense: int              # uniform compiled dense-block size (max shard k)
    n_max: int                # padded per-shard hybrid vertex count
    num_slots: int            # pl * P * o_max flat outbox space per shard
    # --- stacked per-shard device data [S, ...] ---
    n_vert: np.ndarray        # [S] true hybrid vertex count per shard
    dense: np.ndarray         # [S, K, K] ⊗ values (⊕-identity non-edges)
    ell_col: np.ndarray       # [S, n_max, kmax] (sentinel = n_max)
    ell_val: np.ndarray       # [S, n_max, kmax]
    slot: np.ndarray          # [S, n_max] hybrid id -> p_local*v_max + local
    hid: np.ndarray           # [S, pl, v_max] slot -> hybrid id (pad = n_max)
    # --- boundary edges, sorted by flat outbox slot id ---
    b_src: np.ndarray         # [S, be_pad] hybrid source id (pad -> n_max)
    b_local: np.ndarray       # [S, be_pad] rank of the edge's slot in block
    b_ids: np.ndarray         # [S, nb, span] per-block distinct slot ids
    b_mask: np.ndarray        # [S, be_pad] 1 for real edges
    b_weight: Optional[np.ndarray]   # [S, be_pad] f32 or None
    b_span: int               # static span bound for the outbox kernel
    b_block: int              # outbox kernel block size
    # --- compact exchange maps ---
    send_idx: np.ndarray      # [S, S, w] flat outbox index (pad -> num_slots)
    recv_ids: np.ndarray      # [S, S, w] local scatter segment id
    loc_idx: np.ndarray       # [S, L] same-device flat outbox indices
    loc_ids: np.ndarray       # [S, L] same-device scatter segment ids
    wire_width: int           # w: packed slots per (shard, peer) pair
    wire_slots_used: int      # true cross-device slots summed over shards
    has_boundary: bool
    has_remote: bool
    has_local_slots: bool
    # --- push direction (min combines; None disables the switch) ---
    push_src: Optional[np.ndarray]   # [S, ei_pad] hybrid ids (pad -> n_max)
    push_dst: Optional[np.ndarray]   # [S, ei_pad]
    push_w: Optional[np.ndarray]     # [S, ei_pad] (min_plus) or None
    per_shard_k: List[int] = dataclasses.field(default_factory=list)

    @property
    def scatter_segments(self) -> int:
        """Local scatter segment space: pl*(v_max+1) reals + 1 pad sink."""
        return self.parts_per_shard * (self.v_max + 1)

    def wire_values_per_superstep(self) -> int:
        """Padded f32 buffer one shard puts on the wire each superstep (the
        SPMD all_to_all ships shard-uniform blocks; ``wire_slots_used`` is
        the aggregated payload inside them)."""
        if not self.has_remote:
            return 0
        return (self.num_shards - 1) * self.wire_width


def _shard_intra(pg: PartitionedGraph, num_shards: int, g: CSRGraph):
    """Per-shard intra-partition edge sets + degree-descending rankings.

    Ranks each shard's vertices by (in+out) degree over the *intra* edges
    only (the edges the shard's two-engine step will run); the ranking is
    direction-symmetric, so forward and reverse splits share it.  Returns
    per shard: (ranked global ids, global->hybrid inverse, intra src, intra
    dst, intra weights).
    """
    asg = pg.assignment
    pl = pg.num_parts // num_shards
    src_g, dst_g = g.edge_sources(), g.col
    sp = asg.part_of[src_g]
    intra = sp == asg.part_of[dst_g]
    shard_of_edge = sp // pl
    deg = np.zeros(pg.num_vertices, dtype=np.int64)
    np.add.at(deg, src_g[intra], 1)
    np.add.at(deg, dst_g[intra], 1)
    out = []
    for s in range(num_shards):
        verts = np.concatenate(
            [asg.l2g[p] for p in range(s * pl, (s + 1) * pl)])
        order = verts[np.argsort(-deg[verts], kind="stable")]
        inv = np.full(pg.num_vertices, -1, dtype=np.int64)
        inv[order] = np.arange(len(order))
        em = intra & (shard_of_edge == s)
        w = g.weights[em] if g.weights is not None else None
        out.append((order, inv, src_g[em], dst_g[em], w))
    return out


def shard_plan_inputs(pg: PartitionedGraph, num_shards: int, layouts=None):
    """Perf-model inputs for :func:`perf_model.plan_shards` (Eq. 1 per shard).

    Returns ``(ranks, edges, slots, nverts)``: per shard, the intra-edge
    ``max(rank(src), rank(dst))`` array, the intra edge count, the number of
    *cross-shard* outbox slots it ships per superstep (same-device peer
    slots never touch the interconnect), and its vertex count.  ``layouts``
    reuses a precomputed forward-direction ``_shard_intra`` result.
    """
    pl = pg.num_parts // num_shards
    om = pg.fwd.outbox_mask
    if layouts is None:
        layouts = _shard_intra(pg, num_shards, pg.source)
    ranks, edges, slots, nverts = [], [], [], []
    for s, (order, inv, es, ed, _) in enumerate(layouts):
        ranks.append(np.maximum(inv[es], inv[ed]))
        edges.append(len(es))
        rows = om[s * pl:(s + 1) * pl]
        slots.append(float(rows.sum() - rows[:, s * pl:(s + 1) * pl].sum()))
        nverts.append(len(order))
    return ranks, edges, slots, nverts


def _boundary_arrays(ea: EdgeArrays, asg, shard: int, pl: int, v_max: int,
                     inv: np.ndarray):
    """One shard's boundary edges as (hybrid src, flat slot id, weight);
    already sorted by flat slot id (partition.py sorts edges by ``dst_ext``
    and the flat id is p_local-major)."""
    P, o_max = ea.outbox_dst.shape[0], ea.o_max
    srcs, flats, ws = [], [], []
    for p_local in range(pl):
        p = shard * pl + p_local
        src, flat, w = boundary_edges(ea, p, v_max)
        srcs.append(inv[asg.l2g[p][src]])
        flats.append(p_local * (P * o_max) + flat)
        if w is not None:
            ws.append(w)
    return (np.concatenate(srcs), np.concatenate(flats),
            np.concatenate(ws) if ea.weight is not None else None)


def shard_degree_split(pg: PartitionedGraph, num_shards: int, semiring: str,
                       per_shard_k: Sequence[int], *,
                       use_reverse: bool = False, use_weights: bool = True,
                       direction_switch: bool = False, layouts=None,
                       block_e: int = 256, align: int = 8) -> ShardHybridData:
    """Build one direction's :class:`ShardHybridData` (numpy preprocessing).

    ``per_shard_k`` is each shard's chosen |H| (from
    :func:`perf_model.plan_shards`); the dense blocks are padded to the
    shard maximum K so the SPMD step compiles one shape, but shard ``s``
    only promotes its own top-``k_s`` edges to the MXU path — the rest stay
    in its ELL remainder, exactly its own split decision.

    ``use_weights=False`` packs the semiring defaults (multiplicity counts /
    zero-cost hops) even on a weighted graph — for programs whose
    EdgeMessage ignores the weight.  ``layouts`` reuses a precomputed
    ``_shard_intra`` result for this direction (only valid for
    ``use_reverse=False`` layouts computed on ``pg.source``).
    """
    if semiring not in SEMIRINGS:
        raise ValueError(f"unknown semiring {semiring!r}")
    if pg.source is None:
        raise ValueError("per-shard split needs PartitionedGraph.source")
    asg = pg.assignment
    S, pl = num_shards, pg.num_parts // num_shards
    P, v_max = pg.num_parts, pg.v_max
    g = pg.source.reverse() if use_reverse else pg.source
    ea = pg.rev if use_reverse else pg.fwd
    if ea is None:
        raise ValueError(
            "distributed hybrid needs reverse edge/outbox arrays for "
            "use_reverse programs; partition with include_reverse=True")
    o_max = ea.o_max
    ident = add_identity(semiring)
    mul_ident = SEMIRINGS[semiring][2]
    if layouts is None or use_reverse:
        layouts = _shard_intra(pg, S, g)

    k_list = [int(k) for k in per_shard_k]
    K = max(k_list) if k_list else 0
    n_max = max(_round_up(max(len(o) for o, *_ in layouts), align), align, K)

    n_vert = np.array([len(o) for o, *_ in layouts], dtype=np.int32)
    dense = np.full((S, K, K), ident, dtype=np.float32)
    slot = np.zeros((S, n_max), dtype=np.int32)
    hid = np.full((S, pl, v_max), n_max, dtype=np.int32)
    ell_cols, ell_vals = [], []
    push = ([], [], []) if direction_switch else None

    for s, (order, inv, es, ed, ws) in enumerate(layouts):
        n_s, k_s = len(order), k_list[s]
        # slot/hid translation between [pl, v_max] layout and hybrid ids
        slot[s, :n_s] = ((asg.part_of[order] - s * pl) * v_max
                         + asg.local_id[order]).astype(np.int32)
        for p_local in range(pl):
            l2g = asg.l2g[s * pl + p_local]
            hid[s, p_local, : len(l2g)] = inv[l2g]
        # per-semiring ⊗ values (same policy as degree_split)
        hs, hd = inv[es], inv[ed]
        if not use_weights:
            ws = None
        if semiring == PLUS_TIMES:
            w = ws if ws is not None else np.ones(len(es), dtype=np.float32)
        elif semiring == MIN_PLUS:
            w = ws if ws is not None else np.zeros(len(es), dtype=np.float32)
        else:
            w = np.zeros(len(es), dtype=np.float32)
        in_h = (hs < k_s) & (hd < k_s)
        if k_s:
            if semiring == PLUS_TIMES:
                np.add.at(dense[s], (hs[in_h], hd[in_h]), w[in_h])
            else:
                np.minimum.at(dense[s], (hs[in_h], hd[in_h]), w[in_h])
        rest = ~in_h
        rest_w = w[rest] if semiring != MIN_SR else None
        g_rest = from_edge_list(hs[rest], hd[rest], n_max, weights=rest_w)
        col, val, _ = kops.csr_to_ell(g_rest, semiring=semiring,
                                      transpose=True)
        ell_cols.append(col)
        ell_vals.append(val)
        if push is not None:
            push[0].append(hs.astype(np.int32))
            push[1].append(hd.astype(np.int32))
            push[2].append(w.astype(np.float32))

    kmax = max(c.shape[1] for c in ell_cols)
    ell_col = np.stack([
        np.pad(c, ((0, 0), (0, kmax - c.shape[1])), constant_values=n_max)
        for c in ell_cols])
    ell_val = np.stack([
        np.pad(v, ((0, 0), (0, kmax - v.shape[1])),
               constant_values=mul_ident)
        for v in ell_vals])

    # ---- boundary edges → outbox-slot segment space ------------------------
    num_slots = pl * P * o_max
    bnd = [_boundary_arrays(ea, asg, s, pl, v_max, layouts[s][1])
           for s in range(S)]
    be_req = max(len(b[0]) for b in bnd)
    has_boundary = be_req > 0
    be_max = max(_round_up(be_req, align), align)
    b_src_rows = np.full((S, be_max), n_max, dtype=np.int32)
    b_flat = np.full((S, be_max), num_slots, dtype=np.int32)
    b_mask_rows = np.zeros((S, be_max), dtype=bool)
    b_w_rows = (np.zeros((S, be_max), dtype=np.float32)
                if ea.weight is not None else None)
    counts = np.zeros(S, dtype=np.int64)
    for s, (bs, bf, bw) in enumerate(bnd):
        k = len(bs)
        b_src_rows[s, :k] = bs
        b_flat[s, :k] = bf
        b_mask_rows[s, :k] = True
        if b_w_rows is not None and k:
            b_w_rows[s, :k] = bw
        counts[s] = k
    # Reuse the fused-path block preprocessing: rows sorted by "dst_ext"
    # (here: flat slot id) → per-block ids/local/span for the outbox kernel.
    blk = build_block_metadata(
        EdgeArrays(src=b_src_rows, dst_ext=b_flat, weight=b_w_rows,
                   edge_mask=b_mask_rows,
                   outbox_dst=np.zeros((S, S, 1), np.int32),
                   outbox_mask=np.zeros((S, S, 1), bool),
                   inbox_dst=np.zeros((S, S, 1), np.int32),
                   num_edges=counts),
        block_e=block_e)

    # ---- compact exchange maps --------------------------------------------
    pair_counts = np.zeros((S, S), dtype=np.int64)
    for u in range(S):
        for t in range(S):
            if t == u:
                continue
            rows = ea.outbox_mask[u * pl:(u + 1) * pl, t * pl:(t + 1) * pl]
            pair_counts[u, t] = int(rows.sum())
    w_req = int(pair_counts.max()) if S > 1 else 0
    has_remote = w_req > 0
    w_pad = max(_round_up(w_req, align), align)
    seg_sink = pl * (v_max + 1)
    send_idx = np.full((S, S, w_pad), num_slots, dtype=np.int32)
    recv_ids = np.full((S, S, w_pad), seg_sink, dtype=np.int32)
    loc_lists = [([], []) for _ in range(S)]
    for u in range(S):
        for t in range(S):
            j = 0
            for p_local in range(pl):
                p = u * pl + p_local
                for q in range(t * pl, (t + 1) * pl):
                    k = int(ea.outbox_mask[p, q].sum())
                    if k == 0:
                        continue
                    idx = p_local * (P * o_max) + q * o_max + np.arange(k)
                    ids = ((q - t * pl) * (v_max + 1)
                           + ea.outbox_dst[p, q, :k])
                    if t == u:
                        loc_lists[u][0].append(idx)
                        loc_lists[u][1].append(ids)
                    else:
                        send_idx[u, t, j: j + k] = idx
                        recv_ids[t, u, j: j + k] = ids
                        j += k
    l_req = max((sum(len(a) for a in ls[0]) for ls in loc_lists), default=0)
    has_local = l_req > 0
    l_pad = max(_round_up(l_req, align), align)
    loc_idx = np.full((S, l_pad), num_slots, dtype=np.int32)
    loc_ids = np.full((S, l_pad), seg_sink, dtype=np.int32)
    for s, (idxs, idss) in enumerate(loc_lists):
        if idxs:
            cat_i = np.concatenate(idxs)
            cat_d = np.concatenate(idss)
            loc_idx[s, : len(cat_i)] = cat_i
            loc_ids[s, : len(cat_d)] = cat_d

    push_src = push_dst = push_w = None
    if push is not None:
        ei_req = max(len(a) for a in push[0])
        ei_max = max(_round_up(ei_req, align), align)
        push_src = np.full((S, ei_max), n_max, dtype=np.int32)
        push_dst = np.full((S, ei_max), n_max, dtype=np.int32)
        for s in range(S):
            push_src[s, : len(push[0][s])] = push[0][s]
            push_dst[s, : len(push[1][s])] = push[1][s]
        if semiring == MIN_PLUS and use_weights and g.weights is not None:
            push_w = np.zeros((S, ei_max), dtype=np.float32)
            for s in range(S):
                push_w[s, : len(push[2][s])] = push[2][s]

    return ShardHybridData(
        semiring=semiring, num_shards=S, parts_per_shard=pl, v_max=v_max,
        num_parts=P, o_max=o_max, k_dense=K, n_max=n_max,
        num_slots=num_slots, n_vert=n_vert, dense=dense,
        ell_col=ell_col, ell_val=ell_val, slot=slot, hid=hid,
        b_src=blk.src, b_local=blk.local, b_ids=blk.ids,
        b_mask=blk.mask, b_weight=blk.weight, b_span=blk.span,
        b_block=block_e, send_idx=send_idx, recv_ids=recv_ids,
        loc_idx=loc_idx, loc_ids=loc_ids, wire_width=w_pad,
        wire_slots_used=int(pair_counts.sum()),
        has_boundary=has_boundary, has_remote=has_remote,
        has_local_slots=has_local, push_src=push_src, push_dst=push_dst,
        push_w=push_w, per_shard_k=k_list)


def hybrid_pagerank(hg: HybridGraph, num_iterations: int = 20,
                    damping: float = 0.85,
                    interpret: Optional[bool] = None) -> np.ndarray:
    """PageRank where H×H runs on the MXU path, the rest on the ELL path.

    Returns ranks in the *original* vertex id order.
    """
    if hg.semiring != PLUS_TIMES:
        raise ValueError("hybrid_pagerank needs a plus_times split")
    n = hg.num_vertices
    dense = jnp.asarray(hg.dense_block)
    col, val, widths = hg.sliced()
    col, val = jnp.asarray(col), jnp.asarray(val)
    inv_deg = jnp.asarray(np.where(hg.out_deg > 0,
                                   1.0 / np.maximum(hg.out_deg, 1.0), 0.0))
    delta = (1.0 - damping) / n

    @jax.jit
    def step(rank):
        contrib = rank * inv_deg
        y = hybrid_spmv(dense, col, val, contrib, semiring=PLUS_TIMES,
                        k_dense=hg.k_dense, widths=widths,
                        interpret=interpret)
        return delta + damping * y

    rank = jnp.full((n,), 1.0 / n, dtype=jnp.float32)
    for _ in range(num_iterations):
        rank = step(rank)
    out = np.asarray(rank)
    result = np.empty_like(out)
    result[hg.perm] = out          # back to original id order
    return result
