"""Sliced-ELL SpMV Pallas kernel — the sparse/VPU path of the hybrid engine.

The low-degree remainder of a degree-partitioned graph has a tight degree
bound, so ELLPACK is the natural layout: up to K neighbour ids per row,
sentinel-padded.  Padding every row to the widest one wastes slots, so the
rows are stored **sliced** (SELL-C-σ, Kreutzer et al., SIAM J. Sci. Comput.
36(5), 2014): rows are cut into chunks of ``C`` (``CHUNK``, 512, the
kernel's lane block) and chunk ``c`` is padded only to its own width
``w_c``, its longest row rounded up to ``SLOTS`` (8).  Chunk after chunk, the chunks' slot rows make
one ``col[Σ w_c, C]`` (and ``val``) array: chunk ``c`` owns slot rows
``off_c .. off_c + w_c`` and lane ``r`` of them is its row ``c·C + r``.
When the rows are ordered longest first (``core/hybrid.degree_split``), the
chunks' widths follow the degree distribution and the padding nearly
vanishes.  A rectangular ``[V, K]`` ELL is the sliced one with every chunk
at width ``ceil8(K)`` (``ops.rect_as_sliced``).

The per-slot source values ``x[col]`` are gathered by XLA ahead of the
kernel (an arbitrary-index gather does not lower inside Mosaic) into a
``[Q, Σ w_c, C]`` array; the gather's cost follows its index count, the
sliced slot count.  The kernel's 1-D grid walks the ``Σ w_c / 8`` blocks of
8 slot rows in storage order; two int32 step tables, read by scalar
prefetch, give each step its chunk (the output block the index map picks)
and its block index within the chunk (0 starts the row fold).  Consecutive
steps of one chunk keep the output block resident in VMEM, the
grouped-matmul pattern, so every step does work and none is skipped.

**One reduction order for every layout.** A row's slots are folded in
8-slot blocks, in slot order: each block is reduced on its own
(``row_reduce``), then folded into the row's value (``accumulate``).  A
row's extra blocks in a wider chunk hold only sentinel slots, whose
reduction is the exact ⊕-identity (``+0.0``, or ``+inf`` under ``min``),
so the same row gives bitwise the same ``y`` in any chunk width: sliced,
rectangular, or a window of a rectangular ELL.

Three semirings cover the TOTEM algorithms (paper §3.4 reduction classes):
  - ``plus_times``: y[v] = Σ_k x[col[v,k]] · val[v,k]      (PageRank, BC)
  - ``min_plus``:   y[v] = min_k x[col[v,k]] + val[v,k]    (BFS, SSSP)
  - ``min``:        y[v] = min_k x[col[v,k]]               (CC label prop)

``min`` is ``min_plus`` with all-zero values, but gets its own kernel so the
pure-propagation algorithms skip the add on the VPU.  Sentinel slots
(col == x_len-1, the padded sink) carry the ⊗-identity value (1/0/ignored)
and x's sink entry carries the ⊕-identity (0/+inf), so padding never
contributes.  ``combine="sum"|"min"`` remains as a back-compat alias for
``plus_times``/``min_plus``.

The value vector carries a leading **query-batch axis**: ``x[Q, x_len]`` →
``y[Q, V]``.  The topology (``col``/``val``) is shared across the batch:
each grid step reduces every query's ``(Q, 8, C)`` block against one
``(8, C)`` tile of ``val``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# semiring → (⊕ name, ⊕ identity, ⊗ identity for sentinel slots)
SEMIRINGS = {
    "plus_times": ("sum", 0.0, 1.0),
    "min_plus": ("min", float("inf"), 0.0),
    "min": ("min", float("inf"), 0.0),
}
_COMBINE_ALIAS = {"sum": "plus_times", "min": "min_plus"}
# Slot rows per grid step: the unit of a row's fold, and of chunk widths.
SLOTS = 8
# Rows per chunk: the kernel's lane block (an ELL of fewer rows takes the
# fewest lanes, a multiple of 128, that hold them).
CHUNK = 512


def resolve_semiring(combine: str | None, semiring: str | None) -> str:
    """Map the legacy ``combine`` name / explicit ``semiring`` to a key."""
    if semiring is not None:
        if semiring not in SEMIRINGS:
            raise ValueError(f"unknown semiring {semiring!r}")
        return semiring
    return _COMBINE_ALIAS[combine or "sum"]


def row_reduce(g, vals, semiring: str):
    """⊕ over the slot axis 1 of ``g [Q, bk, bv]`` ⊗ ``vals [bk, bv]``."""
    if semiring == "plus_times":
        return jnp.sum(g * vals[None], axis=1)
    if semiring == "min_plus":
        return jnp.min(g + vals[None], axis=1)
    return jnp.min(g, axis=1)


def accumulate(o_ref, part, semiring: str, first) -> None:
    """Fold one slot block's reduction into the resident output tile;
    ``first`` (a traced bool) starts the row fold."""

    @pl.when(first)
    def _init():
        o_ref[...] = part

    @pl.when(jnp.logical_not(first))
    def _fold():
        if semiring == "plus_times":
            o_ref[...] = o_ref[...] + part
        else:
            o_ref[...] = jnp.minimum(o_ref[...], part)


def step_specs(q: int, chunk: int, with_val: bool):
    """Block specs of the sliced grid: the step's ``(Q, 8, C)`` gathered
    block, its ``(8, C)`` values (``with_val``) and the ``(Q, C)`` output
    tile of the step's chunk.  Index maps take the step and the two
    scalar-prefetched tables (chunk of each step, block within it)."""
    g = pl.BlockSpec((q, SLOTS, chunk), lambda t, ch, bk: (0, t, 0))
    val = pl.BlockSpec((SLOTS, chunk), lambda t, ch, bk: (t, 0))
    out = pl.BlockSpec((q, chunk), lambda t, ch, bk: (0, ch[t]))
    return [g, val] if with_val else [g], out


def _ell_kernel(chunk_ref, blk_ref, g_ref, *rest, semiring: str):
    if semiring == "min":
        (o_ref,) = rest
        vals = None
    else:
        v_ref, o_ref = rest
        vals = v_ref[...]
    first = blk_ref[pl.program_id(0)] == 0
    accumulate(o_ref, row_reduce(g_ref[...], vals, semiring), semiring,
               first)


@functools.partial(jax.jit,
                   static_argnames=("semiring", "chunks", "interpret"))
def ell_spmv(chunk_of: jax.Array, blk_of: jax.Array, g: jax.Array,
             val: jax.Array | None, *, semiring: str, chunks: int,
             interpret: bool = False) -> jax.Array:
    """Row reduction of a sliced ELL's gathered slots, one 8-slot block per
    grid step.

    chunk_of, blk_of: [S/8] int32 per step: its chunk, and its block index
    within the chunk (``ops.step_tables``).  g: [Q, S, C] f32 source
    values per slot (``x[col]``); val: [S, C] edge values (None for
    ``min``).  Returns y: [Q, chunks·C] f32, chunk ``c`` at lanes
    ``c·C .. c·C + C``.
    """
    q, s, c = g.shape
    assert s % SLOTS == 0 and chunk_of.shape == (s // SLOTS,)
    in_specs, out_spec = step_specs(q, c, semiring != "min")
    args = [g] if semiring == "min" else [g, val]
    return pl.pallas_call(
        functools.partial(_ell_kernel, semiring=semiring),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(s // SLOTS,), in_specs=in_specs,
            out_specs=out_spec),
        out_shape=jax.ShapeDtypeStruct((q, chunks * c), jnp.float32),
        interpret=interpret,
    )(chunk_of, blk_of, *args)
