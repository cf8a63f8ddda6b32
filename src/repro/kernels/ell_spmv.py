"""ELLPACK SpMV Pallas kernel — the sparse/VPU path of the hybrid engine.

The low-degree remainder of a degree-partitioned scale-free graph has a tight
degree bound, so ELLPACK padding is cheap: ``col[V, K]`` holds up to K
neighbour ids per vertex (sentinel-padded), ``val[V, K]`` the edge values.
The per-slot source values ``x[col]`` are gathered by XLA ahead of the
kernel (an arbitrary-index gather does not lower inside Mosaic) into a
slot-major ``[Q, K, V]`` array; the kernel streams its ``(Q, bk, bv)``
blocks HBM→VMEM (grid pipelining double-buffers the DMA — the
latency-hiding role the GPU's hardware multithreading plays in the paper)
and reduces them across the slot axis into lane-dense ``(Q, bv)`` rows.
The gather moves ``Q × V × K`` values, i.e. the padded edge count.

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
each grid step ``(V/bv, K/bk)`` reduces every query's block against one
``(bk, bv)`` tile of ``val``, and the slot axis is innermost so the output
tile accumulates in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# semiring → (⊕ name, ⊕ identity, ⊗ identity for sentinel slots)
SEMIRINGS = {
    "plus_times": ("sum", 0.0, 1.0),
    "min_plus": ("min", float("inf"), 0.0),
    "min": ("min", float("inf"), 0.0),
}
_COMBINE_ALIAS = {"sum": "plus_times", "min": "min_plus"}
# VMEM bytes for one buffer of the [Q, bk, bv] gathered block.
BLOCK_BYTES = 4 << 20


def resolve_semiring(combine: str | None, semiring: str | None) -> str:
    """Map the legacy ``combine`` name / explicit ``semiring`` to a key."""
    if semiring is not None:
        if semiring not in SEMIRINGS:
            raise ValueError(f"unknown semiring {semiring!r}")
        return semiring
    return _COMBINE_ALIAS[combine or "sum"]


def slot_block(q: int, k: int, block_v: int) -> int:
    """Slots per grid step: all ``k`` when a ``[q, k, block_v]`` f32 block
    fits ``BLOCK_BYTES``, else the largest multiple of 8 that does.
    Depends on the batch and row width only, never on V, so every row
    reduces in the same order whatever the call's row count."""
    fit = BLOCK_BYTES // (4 * q * block_v)
    return k if k <= fit else max(8, fit // 8 * 8)


def row_reduce(g, vals, semiring: str):
    """⊕ over the slot axis 1 of ``g [Q, bk, bv]`` ⊗ ``vals [bk, bv]``."""
    if semiring == "plus_times":
        return jnp.sum(g * vals[None], axis=1)
    if semiring == "min_plus":
        return jnp.min(g + vals[None], axis=1)
    return jnp.min(g, axis=1)


def accumulate(o_ref, part, semiring: str) -> None:
    """Fold one slot block's reduction into the resident output tile."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = part

    @pl.when(k > 0)
    def _fold():
        if semiring == "plus_times":
            o_ref[...] = o_ref[...] + part
        else:
            o_ref[...] = jnp.minimum(o_ref[...], part)


def _ell_kernel(g_ref, *rest, semiring: str):
    if semiring == "min":
        (o_ref,) = rest
        vals = None
    else:
        v_ref, o_ref = rest
        vals = v_ref[...]
    accumulate(o_ref, row_reduce(g_ref[...], vals, semiring), semiring)


@functools.partial(jax.jit,
                   static_argnames=("semiring", "block_v", "block_k",
                                    "interpret"))
def ell_spmv(g: jax.Array, val_t: jax.Array | None, *, semiring: str,
             block_v: int, block_k: int, interpret: bool = False
             ) -> jax.Array:
    """Row reduction of gathered ELL slots over a (row-block, slot-block)
    grid.

    g: [Q, K, V] f32 source values per slot (``x[col.T]``); val_t: [K, V]
    edge values (None for ``min``).  Returns y: [Q, V] f32.  V must be a
    multiple of block_v and K of block_k (ops.py pads).
    """
    q, k, v = g.shape
    assert v % block_v == 0 and k % block_k == 0, "ops.ell_spmv_op pads"
    in_specs = [pl.BlockSpec((q, block_k, block_v), lambda i, j: (0, j, i))]
    args = [g]
    if semiring != "min":
        in_specs.append(pl.BlockSpec((block_k, block_v),
                                     lambda i, j: (j, i)))
        args.append(val_t)
    return pl.pallas_call(
        functools.partial(_ell_kernel, semiring=semiring),
        grid=(v // block_v, k // block_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((q, block_v), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((q, v), jnp.float32),
        interpret=interpret,
    )(*args)
