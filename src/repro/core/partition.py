"""Graph partitioning for hybrid/multi-shard processing (paper §4.3, §6).

Implements the paper's partition data layout in a JAX-friendly, fixed-shape
form:

- Each vertex is assigned to exactly one partition; vertex ids are re-labelled
  into a per-partition local space (paper Fig. 6).
- Per-partition CSR edges are flattened to edge-parallel ``(src_local,
  dst_ext)`` pairs.  ``dst_ext`` is an *extended* destination index: local
  destinations map to ``[0, v_max)``; boundary (remote) destinations map to an
  **outbox slot** ``v_max + 1 + peer * o_max + slot`` — exactly the paper's
  trick of storing the outbox index in the edge array (§4.3.1).
- The outbox has one slot per *unique* (source-partition, remote-vertex) pair:
  source-side message reduction (§3.4) therefore happens for free inside a
  single ``segment_min`` / ``segment_sum`` over ``dst_ext``.
- Outboxes/inboxes are symmetric (paper Fig. 6): ``inbox_dst[p, q, s]`` is the
  local id on ``p`` of the vertex that receives ``outbox[q, p, s]``.

Partitioning strategies (paper §6): RAND, HIGH (high-degree vertices to
partition 0 — the "CPU" / dense-path analogue), LOW (low-degree to partition
0).  The strategy is O(|V| log |V|) via sorting, matching the paper's cost
analysis (§6.2).

All of this is numpy preprocessing; the returned arrays are handed to JAX.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np

from repro.core.graph import CSRGraph

RAND = "rand"
HIGH = "high"
LOW = "low"
STRATEGIES = (RAND, HIGH, LOW)

# Bin edges for BlockMetadata.span_histogram / degree_skew (one shared tuple
# so the skew signal can't drift from the histogram buckets).
SPAN_HIST_BINS = (1, 129, 513, 1025, 2049, 4097, 1 << 30)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass
class VertexAssignment:
    """Vertex → (partition, local id) mapping plus the inverse."""

    num_parts: int
    part_of: np.ndarray     # [n] int32, partition of each global vertex
    local_id: np.ndarray    # [n] int32, local id of each global vertex
    l2g: List[np.ndarray]   # per-partition local → global

    @property
    def part_sizes(self) -> np.ndarray:
        return np.array([len(x) for x in self.l2g])


@dataclasses.dataclass
class EdgeArrays:
    """Fixed-shape per-partition edge-parallel arrays (stacked on axis 0)."""

    src: np.ndarray         # [P, e_max] int32 local source vertex
    dst_ext: np.ndarray     # [P, e_max] int32 extended destination index
    weight: Optional[np.ndarray]  # [P, e_max] float32 or None
    edge_mask: np.ndarray   # [P, e_max] bool (False for padding)
    outbox_dst: np.ndarray  # [P, P, o_max] int32 local id on the *peer*
    outbox_mask: np.ndarray  # [P, P, o_max] bool
    inbox_dst: np.ndarray   # [P, P, o_max] = outbox_dst.transpose(1, 0, 2)
    num_edges: np.ndarray   # [P] true edge counts
    # Original edge index of each slot (-1 padding): the dynamic layer's
    # tombstone locator (core/dynamic.py).  None for arrays built before
    # this field existed.
    edge_id: Optional[np.ndarray] = None  # [P, e_max] int64

    @property
    def e_max(self) -> int:
        return self.src.shape[1]

    @property
    def o_max(self) -> int:
        return self.outbox_dst.shape[2]


@dataclasses.dataclass
class PartitionedGraph:
    """A partitioned graph ready for the BSP engine."""

    num_parts: int
    num_vertices: int
    num_edges: int
    v_max: int                       # padded vertices per partition
    assignment: VertexAssignment
    fwd: EdgeArrays                  # out-edges (push direction)
    rev: Optional[EdgeArrays]        # in-edges (pull / BC backward)
    out_deg: np.ndarray              # [P, v_max] float32 true global out-degree
    vertex_mask: np.ndarray          # [P, v_max] bool
    # --- partition quality statistics (paper Fig. 4) ---
    alpha: np.ndarray                # [P] share of edges per partition
    beta_no_reduction: float         # boundary edges / |E|
    beta_with_reduction: float       # outbox slots / |E|  (paper §3.4)
    # The un-partitioned graph, kept for backends that re-derive their own
    # layout from it (the hybrid degree-split engine).  None for
    # PartitionedGraphs built before this field existed.
    source: Optional[CSRGraph] = None

    @property
    def seg_count(self) -> int:
        """Extended segment space: v_max locals + 1 sink + P*o_max outbox."""
        return self.v_max + 1 + self.num_parts * self.fwd.o_max

    def gather_global(self, per_part: np.ndarray) -> np.ndarray:
        """Collect a [P, v_max] per-partition state into global [n] order."""
        out = np.empty(self.num_vertices, dtype=per_part.dtype)
        for p, l2g in enumerate(self.assignment.l2g):
            out[l2g] = per_part[p, : len(l2g)]
        return out

    def scatter_global(self, global_vals: np.ndarray,
                       fill) -> np.ndarray:
        """Distribute a global [n] array into [P, v_max] partition layout."""
        out = np.full((self.num_parts, self.v_max), fill,
                      dtype=np.asarray(global_vals).dtype)
        for p, l2g in enumerate(self.assignment.l2g):
            out[p, : len(l2g)] = global_vals[l2g]
        return out

    def scatter_dirty(self, dirty_global: np.ndarray) -> np.ndarray:
        """Global [n] dirty-vertex mask (``DynamicGraph.dirty_since``) into
        [P, v_max] layout — the warm-start seeding helper
        (``BSPEngine.run_incremental``)."""
        return self.scatter_global(np.asarray(dirty_global, dtype=bool),
                                   False)


def assign_vertices(g: CSRGraph, num_parts: int, strategy: str = RAND,
                    cpu_edge_fraction: Optional[float] = None,
                    seed: int = 0) -> VertexAssignment:
    """Assign vertices to partitions (paper §6.2/§6.3.1).

    ``cpu_edge_fraction`` is the paper's α: the share of *edges* kept on
    partition 0 (the bottleneck / "CPU" partition).  The remaining edges are
    split evenly (by edge count) across partitions ``1..P-1``.  When ``None``,
    edges are split evenly across all partitions.
    """
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown strategy {strategy!r}")
    n = g.num_vertices
    deg = g.out_degrees()
    rng = np.random.default_rng(seed)
    if strategy == RAND:
        order = rng.permutation(n)
    elif strategy == HIGH:
        # High-degree first → partition 0 (stable to keep determinism).
        order = np.argsort(-deg, kind="stable")
    else:  # LOW
        order = np.argsort(deg, kind="stable")

    cum = np.cumsum(deg[order])
    total = int(cum[-1]) if len(cum) else 0
    if cpu_edge_fraction is None:
        targets = [total * (p + 1) / num_parts for p in range(num_parts - 1)]
    else:
        rest = (1.0 - cpu_edge_fraction) / max(num_parts - 1, 1)
        fracs = [cpu_edge_fraction] + [rest] * (num_parts - 1)
        targets = list(np.cumsum(fracs)[:-1] * total)
    cuts = np.searchsorted(cum, targets, side="left")
    bounds = np.concatenate([[0], cuts, [n]]).astype(np.int64)

    part_of = np.empty(n, dtype=np.int32)
    local_id = np.empty(n, dtype=np.int32)
    l2g = []
    for p in range(num_parts):
        verts = order[bounds[p]: bounds[p + 1]]
        part_of[verts] = p
        local_id[verts] = np.arange(len(verts), dtype=np.int32)
        l2g.append(np.asarray(verts, dtype=np.int64))
    return VertexAssignment(num_parts, part_of, local_id, l2g)


def boundary_edges(ea: EdgeArrays, p: int, v_max: int):
    """One partition's boundary edges as (local src, flat outbox slot,
    weight-or-None), in ``dst_ext`` order (so flat slot ids ascend).

    The flat slot id is ``q * o_max + slot`` — the edge's position in the
    partition's ``[P, o_max]`` outbox — recovered from the extended
    destination index the edge arrays already carry (§4.3.1: the outbox
    index is stored in the edge array).  The distributed hybrid engine
    reduces boundary messages into exactly this segment space before the
    exchange (§3.4 source-side aggregation).
    """
    em = ea.edge_mask[p] & (ea.dst_ext[p] > v_max)
    src = ea.src[p][em]
    flat = ea.dst_ext[p][em] - (v_max + 1)
    w = ea.weight[p][em] if ea.weight is not None else None
    return src, flat, w


def _build_edge_arrays(g: CSRGraph, asg: VertexAssignment, v_max: int,
                       align: int, spare_outbox: int = 0) -> EdgeArrays:
    """Construct the edge-parallel arrays + outbox maps for one direction.

    ``spare_outbox`` reserves that many unassigned outbox slots per
    (partition, peer) pair — headroom the dynamic layer (core/dynamic.py)
    assigns to inserted boundary edges targeting previously-unmessaged
    remote vertices, without changing ``o_max`` (shape stability is the
    zero-retrace contract).
    """
    P = asg.num_parts
    src_g = g.edge_sources()
    dst_g = g.col
    sp = asg.part_of[src_g]       # partition of each edge's source
    dp = asg.part_of[dst_g]       # partition of each edge's destination

    # Unique remote destinations per (src_part, dst_part): the outbox slots.
    remote_sets: List[List[np.ndarray]] = [[None] * P for _ in range(P)]
    o_req = 0
    for p in range(P):
        for q in range(P):
            if p == q:
                remote_sets[p][q] = np.empty(0, dtype=np.int64)
                continue
            m = (sp == p) & (dp == q)
            uniq = np.unique(dst_g[m])
            remote_sets[p][q] = uniq
            o_req = max(o_req, len(uniq))
    o_max = max(_round_up(o_req + spare_outbox, align), align)

    e_req = int(np.bincount(sp, minlength=P).max()) if len(sp) else 0
    e_max = max(_round_up(e_req, align), align)

    src = np.zeros((P, e_max), dtype=np.int32)
    dst_ext = np.full((P, e_max), v_max, dtype=np.int32)  # default → sink
    weight = (np.zeros((P, e_max), dtype=np.float32)
              if g.weights is not None else None)
    edge_mask = np.zeros((P, e_max), dtype=bool)
    edge_id = np.full((P, e_max), -1, dtype=np.int64)
    outbox_dst = np.full((P, P, o_max), v_max, dtype=np.int32)  # pad → sink
    outbox_mask = np.zeros((P, P, o_max), dtype=bool)
    num_edges = np.zeros(P, dtype=np.int64)

    for p in range(P):
        em = sp == p
        e_ids = np.flatnonzero(em)
        e_src = asg.local_id[src_g[em]].astype(np.int32)
        e_dst_g = dst_g[em]
        e_dp = dp[em]
        ext = np.empty(len(e_src), dtype=np.int32)
        local = e_dp == p
        ext[local] = asg.local_id[e_dst_g[local]]
        for q in range(P):
            if q == p:
                continue
            mq = e_dp == q
            if not mq.any() and len(remote_sets[p][q]) == 0:
                continue
            uniq = remote_sets[p][q]          # sorted by *global* id
            # Order slots by the peer's local id (paper §4.3.4(i): inboxes
            # sorted by vertex id for prefetch/cache efficiency on scatter).
            loc = asg.local_id[uniq]
            by_local = np.argsort(loc, kind="stable")
            inv = np.empty_like(by_local)
            inv[by_local] = np.arange(len(by_local))
            # Slot of each remote edge destination within the (p,q) outbox.
            idx = np.searchsorted(uniq, e_dst_g[mq])
            ext[mq] = v_max + 1 + q * o_max + inv[idx].astype(np.int32)
            k = len(uniq)
            outbox_dst[p, q, :k] = loc[by_local]
            outbox_mask[p, q, :k] = True
        # Sort edges by extended destination: local edges first, then boundary
        # — the paper's locality ordering (§4.3.1), and it makes the segment
        # reduction access pattern monotonic.
        order = np.argsort(ext, kind="stable")
        k = len(e_src)
        src[p, :k] = e_src[order]
        dst_ext[p, :k] = ext[order]
        edge_mask[p, :k] = True
        edge_id[p, :k] = e_ids[order]
        if weight is not None:
            weight[p, :k] = g.weights[em][order]
        num_edges[p] = k

    return EdgeArrays(src=src, dst_ext=dst_ext, weight=weight,
                      edge_mask=edge_mask, outbox_dst=outbox_dst,
                      outbox_mask=outbox_mask,
                      inbox_dst=np.ascontiguousarray(
                          outbox_dst.transpose(1, 0, 2)),
                      num_edges=num_edges, edge_id=edge_id)


def partition(g: CSRGraph, num_parts: int, strategy: str = RAND,
              cpu_edge_fraction: Optional[float] = None, seed: int = 0,
              include_reverse: bool = False,
              align: int = 8, spare_outbox: int = 0) -> PartitionedGraph:
    """Partition ``g`` into ``num_parts`` fixed-shape partitions.

    ``spare_outbox`` reserves unassigned outbox slots per peer pair for the
    dynamic layer's in-place edge inserts (see core/dynamic.py)."""
    asg = assign_vertices(g, num_parts, strategy, cpu_edge_fraction, seed)
    v_max = max(_round_up(int(asg.part_sizes.max()), align), align)

    fwd = _build_edge_arrays(g, asg, v_max, align, spare_outbox)
    rev = (_build_edge_arrays(g.reverse(), asg, v_max, align, spare_outbox)
           if include_reverse else None)

    deg = g.out_degrees().astype(np.float32)
    out_deg = np.zeros((num_parts, v_max), dtype=np.float32)
    vertex_mask = np.zeros((num_parts, v_max), dtype=bool)
    for p, l2g in enumerate(asg.l2g):
        out_deg[p, : len(l2g)] = deg[l2g]
        vertex_mask[p, : len(l2g)] = True

    total_e = max(g.num_edges, 1)
    boundary = int((asg.part_of[g.edge_sources()] !=
                    asg.part_of[g.col]).sum())
    slots = int(fwd.outbox_mask.sum())
    return PartitionedGraph(
        num_parts=num_parts, num_vertices=g.num_vertices,
        num_edges=g.num_edges, v_max=v_max, assignment=asg, fwd=fwd, rev=rev,
        out_deg=out_deg, vertex_mask=vertex_mask,
        alpha=fwd.num_edges / total_e,
        beta_no_reduction=boundary / total_e,
        beta_with_reduction=slots / total_e,
        source=g,
    )


# ---------------------------------------------------------------------------
# Fused-superstep block metadata (kernels/fused_superstep.py)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BlockMetadata:
    """Static per-edge-block metadata for the fused superstep kernel.

    ``partition`` sorts each partition's edges by extended destination, so a
    block of ``block_e`` consecutive edges touches a sorted run of segment
    ids.  This precomputes, per block: its distinct segment ids in
    ascending order (``ids``), each edge's rank among them (``local``), and
    the lane-aligned bound on distinct ids per block (``span`` ≤
    ``block_e``) — everything the one-hot reduction needs to be
    gather/scatter-free, whatever gaps the segment space has between
    blocks' ids.  Padding edges (``mask`` False) take the preceding real
    edge's segment so they never add a distinct id; the kernel masks their
    messages to the combine identity.
    """

    block_e: int
    span: int               # lane-aligned distinct-id bound the kernel uses
    span_req: int           # measured max distinct ids over blocks
    ids: np.ndarray         # [P, nb, span] int32: distinct ids (pad = -1)
    local: np.ndarray       # [P, e_pad] int32: rank of the edge's segment
    src: np.ndarray         # [P, e_pad] int32: src, zero-padded
    mask: np.ndarray        # [P, e_pad] int32: 1 for real edges
    weight: Optional[np.ndarray]  # [P, e_pad] f32 or None
    block_spans: np.ndarray  # [P, nb] int32: segment-id range of each block

    @property
    def num_blocks(self) -> int:
        return self.ids.shape[1]

    @property
    def e_pad(self) -> int:
        return self.src.shape[1]

    def span_histogram(self, bins: Sequence[int] = SPAN_HIST_BINS
                       ) -> np.ndarray:
        """Per-partition histogram of block spans.

        The degree-skew signal behind the fused/reference decision: a
        partition whose high-degree (HIGH strategy) vertices concentrate many
        distinct destinations into single blocks shows mass in the top bins,
        predicting span-bound overflow before the kernel is ever compiled.
        """
        edges = np.asarray(bins)
        return np.stack([np.histogram(row, bins=edges)[0]
                         for row in self.block_spans])

    def fused_ok(self, max_span: int) -> bool:
        """True when every block fits the kernel's span bound."""
        return self.span <= max_span

    def degree_skew(self, min_span: int = 513) -> float:
        """Fraction of span-histogram mass at spans ≥ ``min_span``.

        The hybrid planner's skew signal: blocks whose destinations span a
        wide segment range come from high-degree vertices concentrating many
        distinct neighbours — the graphs where a top-K dense split pays.
        ``min_span`` must be one of ``SPAN_HIST_BINS``.
        """
        if min_span not in SPAN_HIST_BINS:
            raise ValueError(f"min_span must be a bin edge, got {min_span}")
        hist = self.span_histogram(SPAN_HIST_BINS)
        total = max(int(hist.sum()), 1)
        return float(hist[:, SPAN_HIST_BINS.index(min_span):].sum()) / total


def build_block_metadata(ea: EdgeArrays, *, block_e: int = 1024,
                         lane: int = 128) -> BlockMetadata:
    """Preprocess one direction's edge arrays for the fused kernel.

    Numpy-only (runs once at partition time); the returned arrays are static
    data the engine hands to JAX alongside ``src``/``dst_ext``.
    """
    if block_e % lane:
        raise ValueError(f"block_e ({block_e}) must be a multiple of {lane}")
    P, e_max = ea.src.shape
    e_pad = max(_round_up(e_max, block_e), block_e)

    # Fill padding slots with the last real segment id (rows are sorted by
    # dst_ext, so a forward max-accumulate over masked ids is a fill-forward);
    # an empty partition collapses to segment 0.
    masked = np.where(ea.edge_mask, ea.dst_ext, -1)
    filled = np.maximum.accumulate(masked, axis=1)
    filled = np.maximum(filled, 0)
    filled = np.pad(filled, ((0, 0), (0, e_pad - e_max)), mode="edge")

    nb = e_pad // block_e
    blocks = filled.reshape(P, nb, block_e)
    block_spans = (blocks[:, :, -1] - blocks[:, :, 0] + 1).astype(np.int32)
    # Rows are sorted, so an id's rank in its block counts the id changes
    # before it.
    new_id = np.ones(blocks.shape, dtype=bool)
    new_id[:, :, 1:] = blocks[:, :, 1:] != blocks[:, :, :-1]
    rank = np.cumsum(new_id, axis=2, dtype=np.int32) - 1
    span_req = int(rank[:, :, -1].max()) + 1 if rank.size else 1
    span = max(_round_up(span_req, lane), lane)
    ids = np.full((P, nb, span), -1, dtype=np.int32)
    np.put_along_axis(ids, rank, blocks.astype(np.int32), axis=2)
    local = rank.reshape(P, e_pad)

    src = np.pad(ea.src, ((0, 0), (0, e_pad - e_max))).astype(np.int32)
    mask = np.pad(ea.edge_mask, ((0, 0), (0, e_pad - e_max))
                  ).astype(np.int32)
    weight = (np.pad(ea.weight, ((0, 0), (0, e_pad - e_max))
                     ).astype(np.float32) if ea.weight is not None else None)
    return BlockMetadata(block_e=block_e, span=span, span_req=span_req,
                         ids=ids, local=local, src=src, mask=mask,
                         weight=weight, block_spans=block_spans)


# ---------------------------------------------------------------------------
# Transposed (CSC-as-ELL) intra-partition layout: direction-optimized pull
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TransposedEll:
    """Per-partition transposed intra-edge layout for bottom-up traversal.

    The push arenas above are source-major (``src``/``dst_ext`` pairs, sorted
    by extended destination).  Direction-optimized supersteps additionally
    need the CSC view: for each *destination* row, its in-neighbour local
    source ids, packed ELL-style — ``col[p, v, k]`` is the k-th in-neighbour
    of local vertex ``v`` in partition ``p`` (sentinel ``v_max`` → the
    per-partition ⊕-identity sink column the engine appends to ``x``).

    The layout keeps the same clean-cut discipline the tier streamer relies
    on: rows *are* destinations, in ascending local id (destination-sorted by
    construction), grouped into ``lane``-aligned row blocks whose per-block
    metadata (``blk_kmax``/``blk_edges``) bounds each block's scan work —
    and since a row's slots never straddle a block boundary, every cut
    between row blocks is clean (no destination's reduction spans two
    blocks), so windowed execution combines pure ⊕-identities across cuts.

    Within a row, slots are ordered by in-neighbour *out-degree descending*
    (ties by local id): the bottom-up early exit terminates on the first
    frontier parent, and on scale-free graphs the high-degree neighbour is
    the likeliest to be reached already — the same ranking intuition as the
    hybrid degree split.

    ``deg_out``/``deg_bnd`` carry each local vertex's real total / boundary
    out-degree — the deterministic per-superstep ``edges_examined`` charges
    for the push direction and the always-push boundary leg.

    Only the *intra*-partition edges transpose: boundary edges keep their
    outbox-slot push path in both directions (the exchange is
    source-aggregated either way; see docs/traversal.md).
    """

    col: np.ndarray               # [P, v_max, kmax] int32 (sentinel = v_max)
    val: Optional[np.ndarray]     # [P, v_max, kmax] f32 ⊗ values, or None
    kreal: np.ndarray             # [P, v_max] int32 real in-slots per row
    deg_out: np.ndarray           # [P, v_max] int32 real out-degree
    deg_bnd: np.ndarray           # [P, v_max] int32 boundary out-degree
    kmax: int                     # shared in-degree bound (>= 1)
    lane: int                     # row-block alignment
    blk_kmax: np.ndarray          # [P, nb] max kreal per row block
    blk_edges: np.ndarray         # [P, nb] real intra edges per row block

    @property
    def num_blocks(self) -> int:
        return self.blk_kmax.shape[1]


def build_transposed_ell(ea: EdgeArrays, v_max: int, *,
                         lane: int = 128) -> TransposedEll:
    """Transpose one direction's intra-partition edges into ELL rows.

    Numpy preprocessing (runs once at bind time).  Tombstones/delta slots of
    a dynamic overlay are *not* reflected — the engine reconciles mutations
    into its own transposed arenas (hybrid) or keeps dynamic runs push-only
    (reference/fused); see core/bsp.py.
    """
    P, _ = ea.src.shape
    deg_out = np.zeros((P, v_max), dtype=np.int32)
    deg_bnd = np.zeros((P, v_max), dtype=np.int32)
    intra_edges = []            # per partition: (dst, src, w) intra arrays
    kmax = 1
    for p in range(P):
        em = ea.edge_mask[p]
        np.add.at(deg_out[p], ea.src[p][em], 1)
        bm = em & (ea.dst_ext[p] > v_max)
        np.add.at(deg_bnd[p], ea.src[p][bm], 1)
        im = em & (ea.dst_ext[p] < v_max)
        dst = ea.dst_ext[p][im]
        src = ea.src[p][im]
        w = ea.weight[p][im] if ea.weight is not None else None
        if len(dst):
            kmax = max(kmax, int(np.bincount(dst, minlength=1).max()))
        intra_edges.append((dst, src, w))

    col = np.full((P, v_max, kmax), v_max, dtype=np.int32)
    val = (np.zeros((P, v_max, kmax), dtype=np.float32)
           if ea.weight is not None else None)
    kreal = np.zeros((P, v_max), dtype=np.int32)
    for p, (dst, src, w) in enumerate(intra_edges):
        if not len(dst):
            continue
        # slot order: source out-degree descending, ties by (src, arrival)
        order = np.lexsort((np.arange(len(dst)), src,
                            -deg_out[p][src].astype(np.int64), dst))
        dst, src = dst[order], src[order]
        w = w[order] if w is not None else None
        counts = np.bincount(dst, minlength=v_max)
        slots = np.arange(len(dst)) - np.repeat(
            np.cumsum(counts) - counts, counts)[: len(dst)]
        # np.repeat over counts yields rows in ascending dst order — which
        # is exactly the sort order above, so slots align with (dst, src).
        col[p, dst, slots] = src
        if val is not None:
            val[p, dst, slots] = w
        kreal[p] = counts.astype(np.int32)

    v_pad = max(_round_up(v_max, lane), lane)
    nb = v_pad // lane
    kreal_pad = np.pad(kreal, ((0, 0), (0, v_pad - v_max)))
    blocks = kreal_pad.reshape(P, nb, lane)
    return TransposedEll(
        col=col, val=val, kreal=kreal, deg_out=deg_out, deg_bnd=deg_bnd,
        kmax=kmax, lane=lane,
        blk_kmax=blocks.max(axis=2).astype(np.int32),
        blk_edges=blocks.sum(axis=2).astype(np.int32))


def memory_footprint_bytes(pg: PartitionedGraph, state_bytes: int = 4,
                           vid_bytes: int = 4,
                           eid_bytes: int = 4,
                           dynamic=None, tier_plan=None) -> dict:
    """Per-partition memory footprint, the analogue of paper Table 5.

    Actual-size formula from §4.3.3:
    ``eid*|Vp| + vid*|Ep| (+ w*|Ep|) + (vid+s)*|Vi| + (vid+s)*|Vo|``.

    ``dynamic`` (a ``core.dynamic.DynamicGraph`` wrapping ``pg``, or any
    object with ``delta_slots``/``directions``/``weighted`` attributes) adds
    the resident delta-slot and tombstone buffers per direction — without it
    the serving driver's capacity planning under-reports a mutating graph's
    true residency.

    Each partition's record carries a per-tier split alongside ``total``:
    ``tier`` (``"hbm"`` or ``"host"``, from ``tier_plan`` — all-hbm without
    one), ``hbm`` and ``host`` byte subtotals with ``hbm + host == total``.
    A host-tier partition keeps its *graph* bytes — and its dynamic
    delta/tombstone overlay, which streams with the base blocks — in host
    DRAM; its vertex state and outbox/inbox slots stay device-resident
    (the exchange and scatter phases always run on device).  Capacity
    planning against device memory must therefore sum the ``hbm`` figures
    only (see :func:`memory_residency_bytes` and graph_serve's admission)
    — counting a flat ``total`` over-counts host-tier bytes against HBM.
    """
    P = pg.num_parts
    res = {}
    cold = set() if tier_plan is None else set(int(p)
                                               for p in tier_plan.cold)
    w_bytes = 4 if pg.fwd.weight is not None else 0
    for p in range(P):
        vp = int(pg.assignment.part_sizes[p])
        ep = int(pg.fwd.num_edges[p])
        vo = int(pg.fwd.outbox_mask[p].sum())          # remote vertices we msg
        vi = int(pg.fwd.outbox_mask[:, p].sum())       # local verts msg'd to
        res[p] = dict(
            graph=eid_bytes * vp + (vid_bytes + w_bytes) * ep,
            outbox=(vid_bytes + state_bytes) * vo,
            inbox=(vid_bytes + state_bytes) * vi,
            state=state_bytes * vp,
        )
        if dynamic is not None:
            d_max = int(dynamic.delta_slots)
            ndir = int(dynamic.directions)
            dw = 4 if dynamic.weighted else 0
            # delta slots: src + dst_ext (+ weight) per direction
            res[p]["delta"] = ndir * d_max * (2 * vid_bytes + dw)
            # tombstone masks: one byte per base edge slot per direction
            tomb = pg.fwd.e_max + (pg.rev.e_max if pg.rev is not None else 0)
            res[p]["tombstone"] = tomb
        res[p]["total"] = sum(res[p].values())
        host = 0
        if p in cold:
            host = (res[p]["graph"] + res[p].get("delta", 0)
                    + res[p].get("tombstone", 0))
        res[p]["tier"] = "host" if p in cold else "hbm"
        res[p]["hbm"] = res[p]["total"] - host
        res[p]["host"] = host
    return res


def memory_residency_bytes(pg: PartitionedGraph, tier_plan=None,
                           state_bytes: int = 4, dynamic=None) -> dict:
    """Aggregate device-vs-host residency of a (possibly tiered) layout.

    Sums :func:`memory_footprint_bytes`'s per-tier figures and adds the
    streaming double-buffer (two in-flight windows) to the device side —
    the honest capacity numbers ``ServeSession.report()`` and the serving
    driver's admission check consume: ``hbm_bytes`` is what actually
    occupies device memory, ``host_bytes`` what lives in the pinned host
    arena, ``total_bytes`` their sum.
    """
    per = memory_footprint_bytes(pg, state_bytes=state_bytes,
                                 dynamic=dynamic, tier_plan=tier_plan)
    hbm = sum(rec["hbm"] for rec in per.values())
    host = sum(rec["host"] for rec in per.values())
    if tier_plan is not None:
        hbm += int(tier_plan.stream_buffer_bytes)
    return dict(hbm_bytes=int(hbm), host_bytes=int(host),
                total_bytes=int(hbm + host))


# ---------------------------------------------------------------------------
# Tiered (out-of-core) memory plan: docs/memory.md
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class WindowSchedule:
    """One direction's clean-cut streaming windows over the cold partitions.

    Every window is a contiguous run of at most ``win_blocks`` edge blocks
    of one cold partition, cut only at *clean* block boundaries — boundaries
    no destination run straddles — so each extended segment id receives its
    real contributions from exactly one window and the cross-window combine
    only ever adds the reduction identity: that is the whole bitwise-parity
    argument (edges are ``dst_ext``-sorted per partition; see
    docs/memory.md).  Windows have a *fixed* device shape
    ``win_e = win_blocks * block_e`` (short windows are sink-padded), so
    one compiled trace serves the entire schedule and the resident loop
    never retraces.
    """

    block_e: int
    win_blocks: int
    part: np.ndarray     # [W] int32 partition id of each window
    start: np.ndarray    # [W] int64 first edge slot covered
    count: np.ndarray    # [W] int64 real edge slots covered (<= win_e)

    @property
    def win_e(self) -> int:
        return self.win_blocks * self.block_e

    @property
    def num_windows(self) -> int:
        return len(self.part)


def _clean_cut_windows(ea: EdgeArrays, cold, block_e: int,
                       win_blocks: int) -> WindowSchedule:
    """Greedy clean-cut schedule: per cold partition, walk the blocks and
    cut each window at the latest clean boundary within ``win_blocks``."""
    part, start, count = [], [], []
    for p in cold:
        p = int(p)
        k = int(ea.num_edges[p])
        if k == 0:
            continue
        nb_used = -(-k // block_e)
        dst = ea.dst_ext[p]
        cur = 0
        while cur < nb_used:
            want = min(cur + win_blocks, nb_used)
            b = want
            while b > cur:
                i = b * block_e
                if i >= k or dst[i - 1] != dst[i]:
                    break                        # clean boundary
                b -= 1
            if b == cur:
                run = int(np.max(np.bincount(
                    dst[cur * block_e: min(k, want * block_e)])))
                raise ValueError(
                    f"partition {p}: a destination run of {run} edges "
                    f"spans more than win_blocks*block_e = "
                    f"{win_blocks * block_e} edge slots, so no clean "
                    f"window cut exists; raise win_blocks (or block_e) "
                    f"past the longest destination run")
            part.append(p)
            start.append(cur * block_e)
            count.append(min(k, b * block_e) - cur * block_e)
            cur = b
    return WindowSchedule(
        block_e=block_e, win_blocks=win_blocks,
        part=np.asarray(part, dtype=np.int32),
        start=np.asarray(start, dtype=np.int64),
        count=np.asarray(count, dtype=np.int64))


@dataclasses.dataclass
class TierPlan:
    """The two-tier residency decision ``perf_model.choose_tier_split``
    made for one partitioned graph.

    ``hot`` partitions keep their edge arenas device-resident exactly as
    before; ``cold`` partitions' arenas live in host DRAM and stream
    through the superstep in the double-buffered windows of ``fwd`` /
    ``rev``.  Byte figures use the *padded* device-arena measure (stacked
    ``[P, e_max]`` rows all cost the same), so ``hbm_bytes`` — hot arenas
    plus the two window buffers — is exactly what the tiered engine
    allocates and is ``<= hbm_budget_bytes`` by construction.
    """

    hbm_budget_bytes: int
    hot: np.ndarray                      # sorted int32, device-resident
    cold: np.ndarray                     # sorted int32, host-resident
    fwd: WindowSchedule
    rev: Optional[WindowSchedule]
    hbm_bytes: int                       # hot arenas + stream_buffer_bytes
    host_bytes: int                      # cold arenas (pinned host DRAM)
    streamed_bytes_per_superstep: int
    stream_buffer_bytes: int             # the two in-flight window buffers
    table: List[dict]                    # perf_model.rank_tier_split table

    @property
    def window_count(self) -> int:
        return self.fwd.num_windows + (self.rev.num_windows
                                       if self.rev is not None else 0)


def _arena_bytes_per_edge(weighted: bool, fused: bool) -> int:
    """Device bytes per padded edge slot: src + dst_ext (+ weight), plus
    the fused flavor's block metadata (blk_src/local/mask (+ weight_blk))."""
    b = 8 + (4 if weighted else 0)
    if fused:
        b += 12 + (4 if weighted else 0)
    return b


def build_tier_plan(pg: PartitionedGraph, hbm_budget_bytes: int, *,
                    block_e: int = 1024, win_blocks: int = 8,
                    fused: bool = True, dynamic=None) -> TierPlan:
    """Emit the :class:`TierPlan` for ``pg`` under an HBM budget.

    ``perf_model.choose_tier_split`` picks the HBM/host boundary (densest
    partitions stay hot — the MXU-friendly dense blocks the paper keeps on
    the GPU side); this derives the clean-cut window schedules for both
    directions and the arena byte accounting.  ``fused=False`` plans the
    reference-flavor arena only (no block metadata); ``dynamic`` adds the
    tombstone/delta overlay of a DynamicGraph to the cold arena and stream
    figures (the overlay streams with its base blocks).
    """
    from repro.core import perf_model

    P = pg.num_parts
    weighted = pg.fwd.weight is not None
    per_edge = _arena_bytes_per_edge(weighted, fused)
    win_e = win_blocks * block_e

    def _dir_bytes(ea: EdgeArrays) -> int:
        e_pad = max(_round_up(ea.e_max, block_e), block_e)
        b = (8 + (4 if weighted else 0)) * ea.e_max
        if fused:
            b += ((12 + (4 if weighted else 0)) * e_pad
                  + 4 * (e_pad // block_e))
        if dynamic is not None:
            b += ea.e_max                      # tombstone overlay, 1 B/slot
        return b

    part_bytes = np.full(P, _dir_bytes(pg.fwd), dtype=np.int64)
    if pg.rev is not None:
        part_bytes += _dir_bytes(pg.rev)
    if dynamic is not None:
        dw = 4 if dynamic.weighted else 0
        part_bytes += int(dynamic.directions) * int(dynamic.delta_slots) \
            * (8 + dw)
    window_bytes = per_edge * win_e + 4 * win_blocks \
        + (win_e if dynamic is not None else 0)

    part_edges = np.asarray(pg.fwd.num_edges, dtype=np.int64).copy()
    if pg.rev is not None:
        part_edges += np.asarray(pg.rev.num_edges, dtype=np.int64)
    hot, table = perf_model.choose_tier_split(
        part_bytes, int(hbm_budget_bytes), part_edges=part_edges,
        window_bytes=window_bytes)
    hot = np.asarray(sorted(hot), dtype=np.int32)
    cold = np.asarray([p for p in range(P) if p not in set(hot.tolist())],
                      dtype=np.int32)

    fwd_sched = _clean_cut_windows(pg.fwd, cold, block_e, win_blocks)
    rev_sched = (_clean_cut_windows(pg.rev, cold, block_e, win_blocks)
                 if pg.rev is not None else None)
    buffers = 0 if len(cold) == 0 else 2 * window_bytes
    hot_bytes = int(part_bytes[hot].sum()) if len(hot) else 0
    host_bytes = int(part_bytes[cold].sum()) if len(cold) else 0
    return TierPlan(
        hbm_budget_bytes=int(hbm_budget_bytes), hot=hot, cold=cold,
        fwd=fwd_sched, rev=rev_sched,
        hbm_bytes=hot_bytes + buffers, host_bytes=host_bytes,
        streamed_bytes_per_superstep=host_bytes,
        stream_buffer_bytes=buffers, table=table)
