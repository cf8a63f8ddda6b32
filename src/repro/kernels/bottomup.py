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
sliced ``[Q, Σ w_c, C]`` layout of ell_spmv, and the grid and the row fold
are the same; a row's first hit is the same slot in any chunk width, so
``scanned`` is too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ell_spmv import SLOTS, accumulate, row_reduce, step_specs

# A row with no hit keeps this first-hit slot; min(· + 1, kreal) turns it
# into the row's kreal.
_NO_HIT = 1 << 30


def _bu_kernel(chunk_ref, blk_ref, g_ref, *rest, semiring: str,
               early_exit: bool):
    if semiring == "min_plus":
        v_ref, kreal_ref, o_ref, s_ref = rest
        vals = v_ref[...]
    else:
        kreal_ref, o_ref, s_ref = rest
        vals = None
    t = pl.program_id(0)
    blk = blk_ref[t]
    first = blk == 0
    g = g_ref[...]                                   # [Q, 8, C]
    accumulate(o_ref, row_reduce(g, vals, semiring), "min", first)
    kreal = jnp.broadcast_to(kreal_ref[...], s_ref.shape)
    if not early_exit:
        s_ref[...] = kreal
        return
    # A "hit" is a live *parent* (x finite), judged before the ⊗ add — the
    # scan stops on reaching any frontier in-neighbour.
    idx = blk * SLOTS + jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    hit = jnp.min(jnp.where(g < jnp.inf, idx, _NO_HIT), axis=1)

    @pl.when(first)
    def _init():
        s_ref[...] = hit

    @pl.when(jnp.logical_not(first))
    def _fold():
        s_ref[...] = jnp.minimum(s_ref[...], hit)

    # The chunk's last block: the next step starts another chunk (or none).
    nxt = jnp.minimum(t + 1, pl.num_programs(0) - 1)
    last = jnp.logical_or(t == pl.num_programs(0) - 1, blk_ref[nxt] == 0)

    @pl.when(last)
    def _finish():
        s_ref[...] = jnp.minimum(s_ref[...] + 1, kreal)


@functools.partial(jax.jit,
                   static_argnames=("semiring", "early_exit", "chunks",
                                    "interpret"))
def bottomup_scan(chunk_of: jax.Array, blk_of: jax.Array, g: jax.Array,
                  val: jax.Array | None, kreal: jax.Array, *, semiring: str,
                  early_exit: bool = False, chunks: int,
                  interpret: bool = False):
    """Bottom-up scan over the sliced grid of ``ell_spmv``.

    chunk_of, blk_of: [S/8] int32 step tables (``ops.step_tables``); g:
    [Q, S, C] f32 gathered in-neighbour values (the ⊕-identity sink at
    sentinel slots); val: [S, C] f32 (``min_plus``) or None (``min``);
    kreal: [1, chunks·C] int32 real slot counts.  Returns
    ``(y [Q, chunks·C] f32, scanned [Q, chunks·C] int32)``.
    """
    if semiring not in ("min", "min_plus"):
        raise ValueError(f"bottom-up scan needs a min combine, "
                         f"got {semiring!r}")
    q, s, c = g.shape
    assert s % SLOTS == 0 and chunk_of.shape == (s // SLOTS,)
    assert kreal.shape == (1, chunks * c)
    in_specs, out_spec = step_specs(q, c, semiring == "min_plus")
    in_specs.append(pl.BlockSpec((1, c), lambda t, ch, bk: (0, ch[t])))
    args = [g] if semiring == "min" else [g, val]
    kernel = functools.partial(_bu_kernel, semiring=semiring,
                               early_exit=early_exit)
    shape = (q, chunks * c)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(s // SLOTS,), in_specs=in_specs,
            out_specs=[out_spec, out_spec]),
        out_shape=[jax.ShapeDtypeStruct(shape, jnp.float32),
                   jax.ShapeDtypeStruct(shape, jnp.int32)],
        interpret=interpret,
    )(chunk_of, blk_of, *args, kreal)
