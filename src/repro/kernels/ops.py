"""jit'd public wrappers around the Pallas kernels.

These handle shape padding / alignment (callers see arbitrary shapes, the
kernels see 128-aligned tiles), dtype policy (bf16 compute, f32 accumulate),
interpret-mode selection (CPU container → interpret=True, real TPU → False),
and the CSR→ELL / CSR→dense-block packing used by the hybrid engine.
"""
from __future__ import annotations

import collections
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.graph import CSRGraph
from repro.kernels import dense_spmv as _dense
from repro.kernels import ell_spmv as _ell
from repro.kernels import flash_attention as _flash


def pin(x: jax.Array) -> jax.Array:
    """``lax.optimization_barrier``: pins an FMA-contraction seam.

    Kernel callers (``hybrid_spmv``, the tiered path) and ``apply_fn``s
    that must round bitwise-identically across backends use it so XLA
    cannot fuse a multiply-add across the seam."""
    return jax.lax.optimization_barrier(x)


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# The compute path each kernel call site took, counted as calls are traced:
# ``(site, path)`` with path "mosaic" (the compiled Pallas kernel),
# "interpret" (the Pallas interpreter, off-TPU) or "xla" (the plain XLA
# chain a fused site takes when its blocks do not fit the kernel).  Paths
# are static per trace, so this is what every run of that trace executes.
KERNEL_PATHS: collections.Counter = collections.Counter()


def _record(site: str, interpret: bool) -> None:
    KERNEL_PATHS[(site, "interpret" if interpret else "mosaic")] += 1


def _pad_to(x, mult: int, axis: int, value=0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------
# dense-block SpMV
# ---------------------------------------------------------------------------

def dense_spmv_op(x: jax.Array, a: jax.Array, *, block: int = 256,
                  interpret: bool | None = None) -> jax.Array:
    """y = x @ a for arbitrary [M, K] × [K, N]; pads K and N to tiles."""
    if interpret is None:
        interpret = _interpret_default()
    m, k = x.shape
    _, n = a.shape
    bk = min(block, max(128, 1 << (k - 1).bit_length()))
    bn = min(block, max(128, 1 << (n - 1).bit_length()))
    xp = _pad_to(x, bk, 1)
    ap = _pad_to(_pad_to(a, bk, 0), bn, 1)
    _record("dense_spmv", interpret)
    y = _dense.dense_spmv(xp, ap, block_n=bn, block_k=bk,
                          interpret=interpret)
    return y[:, :n]


def dense_spmv_minplus_op(x: jax.Array, a: jax.Array, *, block: int = 256,
                          interpret: bool | None = None) -> jax.Array:
    """y[m, n] = min_k x[m, k] + a[k, n]; pads K and N with +inf."""
    if interpret is None:
        interpret = _interpret_default()
    m, k = x.shape
    _, n = a.shape
    bk = min(block, max(128, 1 << (k - 1).bit_length()))
    bn = min(block, max(128, 1 << (n - 1).bit_length()))
    xp = _pad_to(x, bk, 1, value=jnp.inf)
    ap = _pad_to(_pad_to(a, bk, 0, value=jnp.inf), bn, 1, value=jnp.inf)
    _record("dense_spmv_minplus", interpret)
    y = _dense.dense_spmv_minplus(xp, ap, block_n=bn, block_k=bk,
                                  interpret=interpret)
    return y[:, :n]


# ---------------------------------------------------------------------------
# ELL SpMV
# ---------------------------------------------------------------------------

def csr_to_ell(g: CSRGraph, combine: str | None = None,
               semiring: str | None = None,
               transpose: bool = True) -> Tuple[np.ndarray, np.ndarray, int]:
    """Pack a CSR graph into ELLPACK (numpy preprocessing).

    ``transpose=True`` packs *in*-edges per vertex (pull form: y[v] reduces
    over in-neighbours), which is the natural SpMV orientation.  Sentinel
    slots point at index ``num_vertices`` (callers append a ⊕-identity slot
    to x) with ⊗-identity values.

    Two value policies, kept separate for back-compat:

    - legacy ``combine=``: exactly the pre-semiring packing — ``"sum"`` →
      1.0 per edge (multiplicity counts, weights ignored), ``"min"`` →
      weights (1.0 unweighted).
    - explicit ``semiring=``: ``plus_times`` → weight (1 unweighted),
      ``min_plus`` → weight (0 unweighted: the message carries the
      distance, the edge adds nothing), ``min`` → 0 (values unused by the
      kernel).  The hybrid engine passes explicit weights, so the
      unweighted fallbacks only matter for direct callers.
    """
    sr = _ell.resolve_semiring(combine, semiring)
    legacy = semiring is None
    gg = g.reverse() if transpose else g
    deg = gg.out_degrees()
    kmax = max(int(deg.max()) if len(deg) else 1, 1)
    n = gg.num_vertices
    mul_ident = _ell.SEMIRINGS[sr][2]
    col = np.full((n, kmax), n, dtype=np.int32)
    val = np.full((n, kmax), mul_ident, dtype=np.float32)
    # Vectorized ELL pack: each edge's (row, slot) from its rank within the
    # CSR row, then one fancy-indexed scatter instead of an O(V) Python loop.
    rows = np.repeat(np.arange(n, dtype=np.int64), deg)
    slots = np.arange(gg.num_edges, dtype=np.int64) - \
        np.repeat(gg.row_ptr[:-1], deg)
    col[rows, slots] = gg.col
    if sr == "plus_times" and legacy:
        val[rows, slots] = 1.0
    elif sr == "min" and not legacy:
        val[rows, slots] = 0.0
    elif gg.weights is not None:
        val[rows, slots] = gg.weights
    else:
        unweighted = 1.0 if sr == "plus_times" or legacy else 0.0
        val[rows, slots] = unweighted
    return col, val, kmax


def _chunks(a, fill, xp=np):
    """``[V, K]`` rows as ``[nc, kp, C]``: chunks of ``C`` rows (``CHUNK``,
    or fewer lanes, a multiple of 128, for a small ELL), slots padded to
    ``kp = ceil8(K)`` (at least 8), padding filled with ``fill``."""
    slots = _ell.SLOTS
    v, k = a.shape
    c = min(_ell.CHUNK, max(128, -(-v // 128) * 128))
    nc = -(-v // c)
    kp = max(slots, -(-k // slots) * slots)
    a = xp.pad(a, ((0, nc * c - v), (0, kp - k)), constant_values=fill)
    return a.reshape(nc, c, kp).transpose(0, 2, 1)


def sliced_ell(col: np.ndarray, val: np.ndarray | None, sentinel: int,
               mul_ident: float):
    """Pack a rectangular ``[V, K]`` ELL, rows in their final order and
    each row's real (non-``sentinel``) slots first, as ``csr_to_ell``
    packs them, into the sliced layout of ``kernels/ell_spmv.py`` (numpy
    preprocessing).

    Chunk ``c`` of ``C`` rows gets the width ``w_c``: its longest row,
    rounded up to 8, and at least 8.  Returns ``(col [Σ w_c, C] int32,
    val [Σ w_c, C] f32 or None, widths [nc] int32)``; padding rows and
    slots are sentinels with ⊗-identity values."""
    slots = _ell.SLOTS
    t = _chunks(col, sentinel)                                  # [nc, kp, C]
    nc, kp, c = t.shape
    length = np.pad((col != sentinel).sum(axis=1), (0, nc * c - len(col)))
    longest = length.reshape(nc, c).max(axis=1)
    widths = np.maximum(slots, -(-longest // slots) * slots).astype(np.int32)
    keep = np.arange(kp)[None, :] < widths[:, None]             # [nc, kp]
    col = np.ascontiguousarray(t[keep])                         # [Σw, C]
    if val is not None:
        val = np.ascontiguousarray(_chunks(val, mul_ident)[keep])
    return col, val, widths


def rect_as_sliced(col: jax.Array, val: jax.Array | None, sentinel,
                   mul_ident: float):
    """A rectangular ``[V, K]`` ELL (numpy or traced) as the sliced layout
    with every chunk at the full width ``ceil8(K)``: the same rows, folded
    in the same order, so ``y`` is bitwise what a packed layout of the same
    rows gives.  Returns ``(col, val, widths)`` as ``sliced_ell``."""

    def pack(a, fill):
        t = _chunks(a, fill, jnp)
        return t.reshape(-1, t.shape[2]), t.shape

    col, (nc, kp, _) = pack(col, sentinel)
    if val is not None:
        val, _ = pack(val, mul_ident)
    return col, val, np.full(nc, kp, np.int32)


def step_tables(widths: np.ndarray):
    """The sliced kernels' per-step tables: each 8-slot block's chunk and
    its block index within the chunk, in storage order (int32)."""
    nb = np.asarray(widths, np.int64) // _ell.SLOTS
    chunk_of = np.repeat(np.arange(len(nb)), nb)
    blk_of = np.arange(int(nb.sum())) - np.repeat(np.cumsum(nb) - nb, nb)
    return (jnp.asarray(chunk_of, jnp.int32),
            jnp.asarray(blk_of, jnp.int32))


def _sliced(col, val, x, widths, mul_ident: float):
    """Gather the ELL's source values ``x[col]`` in XLA, in the sliced
    layout: a rectangular ``col [V, K]`` (``widths`` None) is sliced at
    full width first.  Sentinel slots point at x's last column, the
    ⊕-identity sink.  Returns ``(g [Q, S, C], val, widths)``."""
    if widths is None:
        col, val, widths = rect_as_sliced(col, val, x.shape[1] - 1,
                                          mul_ident)
    g = jnp.take(x, col, axis=1, mode="clip")            # [Q, S, C]
    return g, val, widths


def ell_spmv_op(col: jax.Array, val: jax.Array, x: jax.Array, *,
                combine: str | None = None, semiring: str | None = None,
                widths: np.ndarray | None = None,
                interpret: bool | None = None) -> jax.Array:
    """ELL SpMV through the sliced kernel.

    ``col``/``val`` are a rectangular ``[V, K]`` ELL (``widths`` None) or
    a sliced one (``[Σ w_c, C]`` with its per-chunk ``widths``, from
    ``sliced_ell``), whose V rows are x's: its sentinel, x's last column,
    is V.  ``x`` may be ``[x_len]`` (one query, returns ``[V]``) or
    ``[Q, x_len]`` (query batch, returns ``[Q, V]``); the topology is
    shared across Q.
    """
    if interpret is None:
        interpret = _interpret_default()
    sr = _ell.resolve_semiring(combine, semiring)
    squeeze = x.ndim == 1
    if squeeze:
        x = x[None]
    v = col.shape[0] if widths is None else x.shape[1] - 1
    with obs.phase(obs.ELL):
        with obs.phase("bsp.gather"):
            g, val, widths = _sliced(col, None if sr == "min" else val, x,
                                     widths, _ell.SEMIRINGS[sr][2])
        _record("ell_spmv", interpret)
        with obs.phase("bsp.reduce"):
            y = _ell.ell_spmv(*step_tables(widths), g, val, semiring=sr,
                              chunks=len(widths),
                              interpret=interpret)[:, :v]
    return y[0] if squeeze else y


def bottomup_scan_op(col: jax.Array, val: jax.Array | None, x: jax.Array,
                     kreal: jax.Array, *, semiring: str,
                     early_exit: bool = False, skip: jax.Array | None = None,
                     widths: np.ndarray | None = None,
                     interpret: bool | None = None):
    """Bottom-up pull scan through the sliced kernel.

    ``col`` [V, K] in-neighbour ids (sentinel = x_len-1), or a sliced ELL
    with its ``widths`` as in ``ell_spmv_op``; ``val`` of the same layout
    (``min_plus``) or None (``min``); ``x`` [Q, x_len] with the
    ⊕-identity sink appended per row; ``kreal`` [V] real slot counts.
    Returns ``(y [Q, V], scanned [Q, V] int32)`` — the row reduction
    (bitwise equal to ``ell_spmv_op``'s) plus the early-exit scan-work
    model (kernels/bottomup.py).

    ``skip`` [Q, V] bool (uniform-frontier programs only, alongside
    ``early_exit``) marks rows whose value is already final — under
    message uniformity a vertex's first write is its fixpoint value, so
    a sequential bottom-up pass visits only the still-unvisited rows
    (Beamer's frontier loop) and skipped rows charge zero scanned slots.
    The reduction still covers them (that is the bitwise-parity
    guarantee); only the work model changes.
    """
    from repro.kernels import bottomup as _bu

    if interpret is None:
        interpret = _interpret_default()
    v = kreal.shape[0]
    squeeze = x.ndim == 1
    if squeeze:
        x = x[None]
        if skip is not None and skip.ndim == 1:
            skip = skip[None]
    with obs.phase(obs.ELL):
        with obs.phase("bsp.gather"):
            g, val, widths = _sliced(col, val, x, widths,
                                     _ell.SEMIRINGS[semiring][2])
        _record("bottomup_scan", interpret)
        with obs.phase("bsp.reduce"):
            lanes = len(widths) * g.shape[2]
            krealp = _pad_to(kreal.astype(jnp.int32), lanes, 0)[None]
            y, scanned = _bu.bottomup_scan(
                *step_tables(widths), g, val, krealp, semiring=semiring,
                early_exit=early_exit, chunks=len(widths),
                interpret=interpret)
            y, scanned = y[:, :v], scanned[:, :v]
        if skip is not None and early_exit:
            scanned = jnp.where(skip, 0, scanned)
    if squeeze:
        return y[0], scanned[0]
    return y, scanned


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def flash_attention_op(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool = True, window: int = 0,
                       block_q: int = 512, block_k: int = 512,
                       interpret: bool | None = None) -> jax.Array:
    """[B, H, S, D] attention; repeats KV heads for GQA; pads S and D."""
    if interpret is None:
        interpret = _interpret_default()
    b, h, s, d = q.shape
    kv_heads = k.shape[1]
    if kv_heads != h:
        rep = h // kv_heads
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, s, d)
    vf = v.reshape(b * h, s, d)
    out = _flash.flash_attention(qf, kf, vf, causal=causal, window=window,
                                 block_q=min(block_q, s),
                                 block_k=min(block_k, s),
                                 interpret=interpret)
    return out.reshape(b, h, s, d)


# ---------------------------------------------------------------------------
# sorted segment reduce (TOTEM message reduction)
# ---------------------------------------------------------------------------

def segment_reduce_op(msgs: jax.Array, seg_ids: np.ndarray,
                      num_segments: int, *, combine: str = "sum",
                      block_e: int = 1024, max_span: int = 4096,
                      interpret: bool | None = None) -> jax.Array:
    """Two-phase sorted segment reduce.

    ``seg_ids`` must be a *static* (numpy, sorted ascending) id array —
    it is preprocessing output in the engine (partition.py sorts edges by
    destination).  Falls back to plain ``jax.ops.segment_*`` when any
    block's segment-id span exceeds ``max_span`` (sparse/gappy data).
    """
    from repro.kernels import segment_reduce as _seg

    if interpret is None:
        interpret = _interpret_default()
    seg_ids = np.asarray(seg_ids)
    e = len(seg_ids)
    assert np.all(np.diff(seg_ids) >= 0), "seg_ids must be sorted"
    ident = 0.0 if combine == "sum" else np.inf

    pad = (-e) % block_e
    ids_p = np.concatenate([seg_ids,
                            np.full(pad, num_segments, seg_ids.dtype)])
    nb = len(ids_p) // block_e
    blocks = ids_p.reshape(nb, block_e)
    base = blocks[:, 0].astype(np.int32)                  # per-block min id
    span = int((blocks.max(axis=1) - base).max()) + 1
    if span > max_span:
        op = (jax.ops.segment_sum if combine == "sum"
              else jax.ops.segment_min)
        return op(msgs, jnp.asarray(seg_ids), num_segments=num_segments)

    span = max(8, -(-span // 8) * 8)
    local = (blocks - base[:, None]).astype(np.int32).reshape(-1)
    msgs_p = jnp.concatenate(
        [msgs.astype(jnp.float32),
         jnp.full((pad,), ident, jnp.float32)])
    partials = _seg.segment_reduce_blocks(
        msgs_p, jnp.asarray(local), span=span, block_e=block_e,
        combine=combine, interpret=interpret)            # [nb, span]

    # phase 2: merge block partials (blocks may share boundary segments)
    out_ids = (base[:, None] + np.arange(span)[None]).reshape(-1)
    out_ids = np.minimum(out_ids, num_segments)          # pad sink
    op = jax.ops.segment_sum if combine == "sum" else jax.ops.segment_min
    final = op(partials.reshape(-1), jnp.asarray(out_ids),
               num_segments=num_segments + 1)
    return final[:num_segments]


# ---------------------------------------------------------------------------
# shared two-phase plumbing of the fused and outbox kernels
# ---------------------------------------------------------------------------

def _pad_blocks(arrays, block_e: int, ids: jax.Array):
    """Pad edge arrays ``[..., e_pad]`` (and the ``[..., nb, span]`` id
    table) to whole kernel steps of ``BLOCKS_PER_STEP`` blocks when there
    are more blocks than one step takes; padding edges are masked out
    (zeros) and their blocks' ids are sinks (-1)."""
    from repro.kernels.fused_superstep import BLOCKS_PER_STEP

    if ids.shape[-2] <= BLOCKS_PER_STEP:
        return arrays, ids
    step_e = BLOCKS_PER_STEP * block_e
    arrays = [None if a is None else _pad_to(a, step_e, a.ndim - 1)
              for a in arrays]
    return arrays, _pad_to(ids, BLOCKS_PER_STEP, ids.ndim - 2, value=-1)


def _merge_partials(partials: jax.Array, ids: jax.Array, num_segments: int,
                    combine: str) -> jax.Array:
    """Phase 2: ⊕-merge block partials ``[G, nb, span]`` into
    ``[G, num_segments]`` (blocks may share a boundary segment); pad
    columns (id -1) drop into a sink."""
    seg_op = jax.ops.segment_sum if combine == "sum" else jax.ops.segment_min
    g = partials.shape[0]
    ids = jnp.where(ids >= 0, ids, num_segments)
    offs = (jnp.arange(g, dtype=jnp.int32) * (num_segments + 1)).reshape(
        (g,) + (1,) * (ids.ndim - 1))
    acc = seg_op(partials.ravel(), (ids + offs).ravel(),
                 num_segments=g * (num_segments + 1))
    return acc.reshape(g, num_segments + 1)[:, :num_segments]


# ---------------------------------------------------------------------------
# source-side outbox aggregation (distributed hybrid boundary leg, §3.4)
# ---------------------------------------------------------------------------

def outbox_reduce_op(x: jax.Array, src: jax.Array, local: jax.Array,
                     mask: jax.Array, ids: jax.Array, weight, *,
                     num_slots: int, combine: str = "sum", weight_op=None,
                     span: int, block_e: int = 256, max_span: int = 4096,
                     interpret: bool | None = None) -> jax.Array:
    """Reduce boundary messages into the flat outbox-slot space.

    ``x`` is one shard's per-query per-vertex message matrix ``[Q, x_len]``
    (+ identity sink at the end of each row; a 1-D ``x`` is treated as
    ``Q=1``); ``src``/``local``/``mask``/``ids``/``weight`` follow
    ``hybrid.shard_degree_split`` — boundary edges sorted by flat slot id
    with each block's distinct slot ids and per-edge ranks, arriving as
    *operands* so each shard carries its own maps under ``shard_map`` (and
    shared across the query batch).  ``weight_op`` is the EdgeMessage's ⊗
    ("add"/"mul"/None).  Returns the [Q, num_slots] aggregated outboxes
    (⊕-identity for unused slots), or [num_slots] for 1-D input.

    Takes the plain gather → ``jax.ops.segment_*`` chain (recorded as the
    "xla" path in ``KERNEL_PATHS``) when ``span`` exceeds ``max_span`` or
    the VMEM budget for the kernel's [block_e, span] intermediates.
    """
    from repro.kernels import fused_superstep as _fused
    from repro.kernels import outbox_reduce as _obox

    if interpret is None:
        interpret = _interpret_default()
    ident = 0.0 if combine == "sum" else jnp.inf
    squeeze = x.ndim == 1
    if squeeze:
        x = x[None]
    nb = src.shape[0] // block_e

    def apply_weight(msgs):
        if weight_op == "add":
            return msgs + weight
        if weight_op == "mul":
            return msgs * weight
        return msgs

    if span > fused_span_limit(block_e, combine, max_span):
        KERNEL_PATHS[("outbox_reduce", "xla")] += 1
        # Reference chain: each edge's flat slot id from its block's table.
        slot = jnp.take_along_axis(ids, local.reshape(nb, block_e), axis=1)
        with obs.phase("bsp.gather"):
            msgs = apply_weight(jnp.take(x, src, axis=1))   # [Q, e_pad]
            msgs = jnp.where(mask > 0, msgs, ident)
        acc = _merge_partials(msgs[:, None, :], slot.reshape(1, -1),
                              num_slots, combine)
        return acc[0] if squeeze else acc

    _record("outbox_reduce", interpret)
    x_pad = _pad_to(x, _fused.TILE, 1, value=ident)
    (src, local, mask, weight), ids = _pad_blocks(
        [src, local, mask, weight if weight_op is not None else None],
        block_e, ids)
    partials = _obox.outbox_reduce_blocks(
        x_pad, src, local, mask, weight, combine=combine,
        weight_op=weight_op, span=span, block_e=block_e,
        interpret=interpret)                                # [Q, nb, span]
    acc = _merge_partials(partials, jnp.broadcast_to(
        ids[None], partials.shape), num_slots, combine)
    return acc[0] if squeeze else acc


# ---------------------------------------------------------------------------
# fused superstep compute phase (TOTEM gather + message + reduction)
# ---------------------------------------------------------------------------

# VMEM byte budget for the kernel's dominant [block_e, span] intermediates
# (the one-hot select, its hit mask, and for min a second select).
_VMEM_BLOCK_BUDGET = 8 << 20
# The whole kernel — the VMEM-resident partition state included — must fit
# this share of a v5e core's 128 MiB of VMEM (fused_superstep.vmem_bytes).
_VMEM_KERNEL_BUDGET = 96 << 20


def fused_span_limit(block_e: int, combine: str = "sum",
                     max_span: int = 4096) -> int:
    """Largest block span the fused kernel will compile for.

    The caller's ``max_span`` bounds reassociation span; on top of that the
    [block_e, span] intermediates must fit the VMEM budget — ``min`` combines
    materialize two such arrays, halving the limit.  Spans above this take
    the XLA chain (see ``fused_superstep_op``).
    """
    copies = 2 if combine == "min" else 1
    return min(max_span, _VMEM_BLOCK_BUDGET // (4 * block_e * copies))


def fused_superstep_op(msg_fn, vstate: jax.Array, weight, scal: jax.Array,
                       src: jax.Array, local: jax.Array, mask: jax.Array,
                       ids: jax.Array, dst_ext: jax.Array, *,
                       num_segments: int, combine: str = "sum", span: int,
                       block_e: int = 1024, max_span: int = 4096,
                       interpret: bool | None = None) -> jax.Array:
    """Fused compute phase: per-query accumulator [Q, Pl, num_segments].

    Inputs follow ``partition.build_block_metadata``: ``vstate`` is the
    stacked [Q, Pl, K, v] gathered-state matrix, ``scal`` [Q, Pl, S]
    carries (step, *per-query per-partition consts), ``src``/``local``/
    ``mask`` are the [Pl, e_pad] block arrays (shared across the query
    batch), ``ids`` [Pl, nb, span] each block's distinct segment ids, and
    ``span``/``block_e`` their static geometry.  ``msg_fn(vals, weight,
    scals) -> msgs`` is elementwise/broadcast-safe, so the same callable
    runs on [be, 1]-shaped values inside the kernel and on
    [Q, Pl, e_max]-shaped values in the XLA chain.

    Takes the reference gather → message → ``jax.ops.segment_*`` chain
    (recorded as the "xla" path in ``KERNEL_PATHS``) when the block span
    exceeds ``fused_span_limit`` or the kernel's whole VMEM footprint,
    resident state included, exceeds the VMEM budget.
    """
    from repro.kernels import fused_superstep as _fused

    if interpret is None:
        interpret = _interpret_default()
    q, pl_count, n_keys = vstate.shape[:3]
    ident = 0.0 if combine == "sum" else jnp.inf
    seg_op = jax.ops.segment_sum if combine == "sum" else jax.ops.segment_min
    v_pad = -(-vstate.shape[3] // _fused.TILE) * _fused.TILE
    n_edge = 4 if weight is not None else 3
    vmem = _fused.vmem_bytes(n_keys, v_pad, block_e, span, n_edge)

    if (span > fused_span_limit(block_e, combine, max_span)
            or vmem > _VMEM_KERNEL_BUDGET):
        KERNEL_PATHS[("fused_superstep", "xla")] += 1
        # Reference path expressed through the elementwise form.
        e_max = dst_ext.shape[1]
        with obs.phase("bsp.gather"):
            src_b = jnp.broadcast_to(src[None, :, :e_max],
                                     (q, pl_count, e_max))
            vals = tuple(
                jnp.take_along_axis(vstate[:, :, k_, :], src_b, axis=2)
                for k_ in range(n_keys))
            scals = tuple(scal[:, :, j:j + 1] for j in range(scal.shape[2]))
            w = weight[:, :e_max] if weight is not None else None
            msgs = msg_fn(vals, w, scals).astype(jnp.float32)
            msgs = jnp.where(mask[:, :e_max] > 0, msgs, ident)
        offs = (jnp.arange(q * pl_count, dtype=jnp.int32)
                * num_segments).reshape(q, pl_count, 1)
        acc = seg_op(msgs.ravel(), (dst_ext[None] + offs).ravel(),
                     num_segments=q * pl_count * num_segments)
        return acc.reshape(q, pl_count, num_segments)

    _record("fused_superstep", interpret)
    vstate = _pad_to(vstate, _fused.TILE, 3)
    (src, local, mask, weight), ids = _pad_blocks(
        [src, local, mask, weight], block_e, ids)
    partials = _fused.fused_superstep_blocks(
        vstate, scal, src, local, mask, weight, msg_fn=msg_fn,
        combine=combine, span=span, block_e=block_e,
        interpret=interpret)                             # [Q, Pl, nb, span]
    nb = partials.shape[2]
    acc = _merge_partials(
        partials.reshape(q * pl_count, nb, span),
        jnp.broadcast_to(ids[None], (q,) + ids.shape).reshape(
            q * pl_count, nb, span), num_segments, combine)
    return acc.reshape(q, pl_count, num_segments)
