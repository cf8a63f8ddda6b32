"""Bottom-up (pull) traversal scan Pallas kernel — direction optimization.

Direction-optimized BFS (Sallinen/Gharaibeh/Ripeanu, arXiv 1503.04359) flips
dense-frontier supersteps from top-down push (every frontier vertex scatters
along its out-edges) to bottom-up pull: every destination row scans its
*in*-neighbours and stops at the first parent already in the frontier.  On a
scale-free graph the dense middle steps find a parent within a slot or two —
the in-neighbour slots are packed degree-descending, so slot 0 is the
neighbour most likely to be reached first — and the traversal examines a
small fraction of the edges the push direction would.

This kernel is the ELL ``min``/``min_plus`` SpMV (kernels/ell_spmv.py) with a
second output: alongside ``y[v] = ⊕_k x[col[v,k]] (⊗ val[v,k])`` it emits
``scanned[v]``, the number of slots a sequential early-exit scan of row ``v``
would examine:

  - ``early_exit=True`` (uniform-frontier programs — BFS, where every live
    message this superstep equals ``step+1``): ``min(first_hit + 1, kreal)``,
    where ``first_hit`` is the first slot whose gathered ``x`` is live
    (``< +inf``, the ⊕-identity of min combines).  Early exit is *exact*
    only under message uniformity: the first live parent's value IS the min.
  - ``early_exit=False`` (CC labels, SSSP distances — messages differ per
    parent): the full ``kreal[v]`` real slots.

The reduction itself always covers every slot (the same XLA gather and
slot-axis min as ``ell_spmv``, bitwise identical — that's the parity
guarantee); ``scanned`` is the deterministic *work model* of the sequential
scan a scalar core (or a chunked-K TPU kernel that breaks once a whole row
block has hit) would perform.  Under the same uniformity licence a row's
first write is its fixpoint value, so a sequential bottom-up visits only
still-unvisited rows — ``ops.bottomup_scan_op``'s ``skip`` mask zeroes the
charge for rows already holding a value.  The engine sums the result into
the per-query ``edges_examined`` counter — the observable the bench gates
on.

``kreal[v]`` is the row's real (non-sentinel) slot count; sentinel slots
gather the +inf sink and can never register a hit, so rows report at most
their real work.  The gathered values carry the query-batch axis and the
slot-major ``[Q, K, V]`` layout of ell_spmv, and the grid is the same.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ell_spmv import accumulate, row_reduce


def _bu_kernel(g_ref, *rest, semiring: str, early_exit: bool,
               block_k: int, k_total: int):
    if semiring == "min_plus":
        v_ref, kreal_ref, o_ref, s_ref = rest
        vals = v_ref[...]
    else:
        kreal_ref, o_ref, s_ref = rest
        vals = None
    g = g_ref[...]                                   # [Q, bk, bv]
    accumulate(o_ref, row_reduce(g, vals, semiring), "min")
    k = pl.program_id(1)
    kreal = jnp.broadcast_to(kreal_ref[...], s_ref.shape)
    if not early_exit:
        s_ref[...] = kreal
        return
    # A "hit" is a live *parent* (x finite), judged before the ⊗ add — the
    # scan stops on reaching any frontier in-neighbour.  Rows with no hit
    # keep k_total, which min(· + 1, kreal) turns into their kreal.
    idx = k * block_k + jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    first = jnp.min(jnp.where(g < jnp.inf, idx, k_total), axis=1)

    @pl.when(k == 0)
    def _init():
        s_ref[...] = first

    @pl.when(k > 0)
    def _fold():
        s_ref[...] = jnp.minimum(s_ref[...], first)

    @pl.when(k == pl.num_programs(1) - 1)
    def _finish():
        s_ref[...] = jnp.minimum(s_ref[...] + 1, kreal)


@functools.partial(jax.jit,
                   static_argnames=("semiring", "early_exit", "block_v",
                                    "block_k", "interpret"))
def bottomup_scan(g: jax.Array, val_t: jax.Array | None, kreal: jax.Array,
                  *, semiring: str, early_exit: bool = False,
                  block_v: int, block_k: int, interpret: bool = False):
    """Bottom-up scan over a (row-block, slot-block) grid.

    g: [Q, K, V] f32 gathered in-neighbour values (``x[col.T]``, the
    ⊕-identity sink at sentinel slots); val_t: [K, V] f32 (``min_plus``)
    or None (``min``); kreal: [1, V] int32 real slot counts.  Returns
    ``(y [Q, V] f32, scanned [Q, V] int32)``.  V must be a multiple of
    block_v and K of block_k (ops.py pads).
    """
    if semiring not in ("min", "min_plus"):
        raise ValueError(f"bottom-up scan needs a min combine, "
                         f"got {semiring!r}")
    q, k, v = g.shape
    assert v % block_v == 0 and k % block_k == 0, "ops.bottomup_scan_op pads"
    assert kreal.shape == (1, v)
    in_specs = [pl.BlockSpec((q, block_k, block_v), lambda i, j: (0, j, i))]
    args = [g]
    if semiring == "min_plus":
        in_specs.append(pl.BlockSpec((block_k, block_v),
                                     lambda i, j: (j, i)))
        args.append(val_t)
    in_specs.append(pl.BlockSpec((1, block_v), lambda i, j: (0, i)))
    out_spec = pl.BlockSpec((q, block_v), lambda i, j: (0, i))
    kernel = functools.partial(_bu_kernel, semiring=semiring,
                               early_exit=early_exit, block_k=block_k,
                               k_total=k)
    return pl.pallas_call(
        kernel,
        grid=(v // block_v, k // block_k),
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((q, v), jnp.float32),
                   jax.ShapeDtypeStruct((q, v), jnp.int32)],
        interpret=interpret,
    )(*args, kreal)
