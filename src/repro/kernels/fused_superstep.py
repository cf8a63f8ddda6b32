"""Fused BSP superstep compute phase — gather + edge message + segment
reduce in one Pallas kernel (paper §3.4, §4.3.1).

The reference compute phase is three HBM-bound passes: gather per-edge source
state (``take_along_axis``), materialize the ``[Pl, e_max]`` message array,
then scatter-reduce it over extended destination ids.  Edges are sorted by
destination at partition time, so — exactly as in ``segment_reduce.py`` — a
block of ``be`` consecutive edges reduces into a contiguous ``span`` of
segment ids.  This kernel runs the whole chain per (partition, edge-block)
grid cell without ever leaving VMEM:

  1. **gather** — the partition's ``[K, v_pad]`` stacked vertex state is
     VMEM-resident, laid out as ``[K, v_pad/128, 128]`` lane rows; per-edge
     source values are extracted with a masked-max select
     (``where(src == lane_id, state_row, -inf)`` + max) that sweeps the
     state one ``(8, 128)`` tile at a time into a ``[be, 128]`` running
     max, reduced across lanes once at the end.  A select/reduce rather
     than an MXU contraction because graph state legitimately contains
     ``+inf`` (BFS/SSSP/CC/BC distances), and ``0 * inf = nan`` would
     poison a multiply-accumulate gather.  State must not contain ``-inf``
     (no algorithm uses it).  The sweep costs ``be × v_pad`` selects per
     block, i.e. ``E_p × v_pad`` per query and superstep.
  2. **edge message** — the algorithm's elementwise ``edge_msg`` function is
     inlined on the gathered ``[be, 1]`` value columns (plus optional edge
     weight and per-partition scalars, read from SMEM); padding edges are
     masked to the combine identity.
  3. **reduce** — each edge's ``local`` id is its segment's rank among the
     block's distinct segments (``partition.build_block_metadata``), so a
     masked VPU sum or min over the ``[be, span]`` one-hot yields the
     block's ``[1, span]`` partials (lane-dense, no MXU rounding).

The ``[be]`` messages exist only between steps 2 and 3 in VMEM; the kernel's
HBM output is the ``[Q, Pl, nb, span]`` partials array (merged by a tiny
static segment reduce in ops.py — phase 2 of the two-phase scheme).

**Tiling**: every block obeys the TPU's (8, 128) rule.  Edge arrays are
viewed as ``[Pl, nb, be]`` and each grid step takes ``BLOCKS_PER_STEP`` (8)
edge blocks — all ``nb`` when fewer — as one ``(8, be)`` tile, transposed
in VMEM so each block's edges form a sublane column; the output tile is
``(8, span)``.

**Query-batch axis**: vertex state and per-partition scalars carry a leading
``Q`` axis (``vstate[Q, Pl, K, v_pad]``, ``scal[Q, Pl, S]``) and the grid is
``(Q, Pl, nb/8)`` with the batch outermost.  The edge topology
(``src``/``local``/``mask``/``weight``) stays ``[Pl, e_pad]`` — its block
index maps ignore the query coordinate, so a batch of Q concurrent
traversals reuses one copy of the graph structure; only the message values
grow with Q.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
TILE = 8 * LANES            # one (8, 128) f32 tile: the gather's state unit
BLOCKS_PER_STEP = 8         # edge blocks per grid step: one sublane tile


def blocks_per_step(nb: int) -> int:
    """Edge blocks per grid step: one full (8, be) sublane tile, or all
    ``nb`` blocks when fewer (a block dimension equal to the array's)."""
    return min(BLOCKS_PER_STEP, nb)


def gather_columns(ref, src, n_keys: int):
    """Masked-max gather of ``ref[k][src]`` for every key ``k``.

    ``ref``: VMEM ref ``[n_keys, R, 128]`` (R % 8 == 0) holding each key's
    values as 128-lane rows; ``src``: ``[be, 1]`` int32 column of flat
    indices.  Returns ``n_keys`` columns ``[be, 1]``.  Sweeps one (8, 128)
    tile per loop step into ``[be, 128]`` running maxima (pure elementwise
    work), then reduces across lanes once.
    """
    be = src.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def body(t, accs):
        row0 = pl.multiple_of(t * 8, 8)
        rel = src - t * TILE                                  # [be, 1]
        tiles = [ref[k, pl.ds(row0, 8), :] for k in range(n_keys)]
        accs = list(accs)
        for i in range(8):
            hit = rel == (i * LANES + lane)                   # [be, 128]
            for k in range(n_keys):
                accs[k] = jnp.maximum(
                    accs[k], jnp.where(hit, tiles[k][i:i + 1, :], -jnp.inf))
        return tuple(accs)

    init = tuple(jnp.full((be, LANES), -jnp.inf, jnp.float32)
                 for _ in range(n_keys))
    accs = jax.lax.fori_loop(0, ref.shape[1] // 8, body, init)
    return [jnp.max(a, axis=1, keepdims=True) for a in accs]


def reduce_block(msgs, local, span: int, combine: str):
    """``[1, span]`` partials of one block: ⊕ of ``msgs`` per local id.

    ``msgs``/``local``: ``[be, 1]`` columns, ``local`` in ``[0, span)``."""
    ident = 0.0 if combine == "sum" else jnp.inf
    hit = local == jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
    picked = jnp.where(hit, msgs, ident)                      # [be, span]
    if combine == "sum":
        return jnp.sum(picked, axis=0, keepdims=True)
    return jnp.min(picked, axis=0, keepdims=True)


def _fused_kernel(scal_ref, vstate_ref, src_ref, local_ref, mask_ref, *rest,
                  msg_fn, combine: str, span: int, n_scal: int,
                  has_weight: bool):
    if has_weight:
        weight_ref, o_ref = rest
    else:
        weight_ref, o_ref = None, rest[0]

    q, p = pl.program_id(0), pl.program_id(1)
    base = (q * pl.num_programs(1) + p) * n_scal
    scals = tuple(scal_ref[base + j] for j in range(n_scal))
    ident = 0.0 if combine == "sum" else jnp.inf
    n_keys = vstate_ref.shape[0]

    # (8, be) edge tiles → (be, 8): column j holds edge block j's edges.
    src_t = src_ref[...].T
    local_t = local_ref[...].T
    mask_t = mask_ref[...].T
    weight_t = weight_ref[...].T if has_weight else None
    for j in range(src_ref.shape[0]):
        vals = gather_columns(vstate_ref, src_t[:, j:j + 1], n_keys)
        weight = weight_t[:, j:j + 1] if has_weight else None
        msgs = msg_fn(tuple(vals), weight, scals).astype(jnp.float32)
        msgs = jnp.where(mask_t[:, j:j + 1] > 0, msgs, ident)
        o_ref[j:j + 1, :] = reduce_block(msgs, local_t[:, j:j + 1], span,
                                         combine)


def vmem_bytes(n_keys: int, v_pad: int, block_e: int, span: int,
               n_edge_arrays: int) -> int:
    """VMEM the fused kernel needs: double-buffered state, edge and output
    tiles plus the gather accumulators and the ``[be, span]`` one-hot."""
    state = 2 * 4 * n_keys * v_pad
    edges = 2 * 4 * n_edge_arrays * BLOCKS_PER_STEP * block_e
    out = 2 * 4 * BLOCKS_PER_STEP * span
    transposed = 4 * n_edge_arrays * block_e * LANES
    gather = 4 * (n_keys + 2) * block_e * LANES
    reduce = 3 * 4 * block_e * span
    return state + edges + out + transposed + gather + reduce


def compiler_params(vmem: int):
    """Mosaic params raising the scoped-VMEM limit to ``vmem`` plus
    headroom (v5e's scoped default is 16 MiB of its 128 MiB)."""
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(min(vmem * 3 // 2 + (4 << 20), 120 << 20)))


@functools.partial(jax.jit,
                   static_argnames=("msg_fn", "combine", "span", "block_e",
                                    "interpret"))
def fused_superstep_blocks(vstate: jax.Array, scal: jax.Array,
                           src: jax.Array, local: jax.Array,
                           mask: jax.Array, weight, *, msg_fn,
                           combine: str = "sum", span: int,
                           block_e: int = 1024,
                           interpret: bool = False) -> jax.Array:
    """Phase-1 fused partials.

    vstate: [Q, Pl, K, v_pad] f32 (v_pad % 1024 == 0); scal: [Q, Pl, S]
    f32 with scal[..., 0] = superstep and scal[..., 1:] per-query
    per-partition consts; src/local/mask (int32) and weight (f32 or None):
    [Pl, e_pad] with e_pad / block_e below 8 or a multiple of 8 — shared
    across the query batch.  ``msg_fn(vals_tuple, weight, scal_tuple) ->
    [be, 1]`` must be elementwise/broadcast-safe.  Returns
    [Q, Pl, e_pad/block_e, span].
    """
    q, pl_count, n_keys, v_pad = vstate.shape
    e_pad = src.shape[1]
    nb = e_pad // block_e
    rows = blocks_per_step(nb)
    assert e_pad % (rows * block_e) == 0 and nb % rows == 0
    assert v_pad % TILE == 0 and span % LANES == 0
    n_scal = scal.shape[2]
    has_weight = weight is not None

    kernel = functools.partial(
        _fused_kernel, msg_fn=msg_fn, combine=combine, span=span,
        n_scal=n_scal, has_weight=has_weight)

    def blocks(a):                                   # [Pl, nb, be] view
        return a.reshape(pl_count, nb, block_e)

    # Topology blocks ignore the query coordinate: one copy serves all Q.
    edge_spec = pl.BlockSpec((None, rows, block_e), lambda s, p, b: (p, b, 0))
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((None, None, n_keys, v_pad // LANES, LANES),
                     lambda s, p, b: (s, p, 0, 0, 0)),
        edge_spec, edge_spec, edge_spec,
    ]
    args = [scal.reshape(-1),
            vstate.reshape(q, pl_count, n_keys, v_pad // LANES, LANES),
            blocks(src), blocks(local), blocks(mask)]
    if has_weight:
        in_specs.append(edge_spec)
        args.append(blocks(weight))

    vmem = vmem_bytes(n_keys, v_pad, block_e, span, len(args) - 2)
    return pl.pallas_call(
        kernel,
        grid=(q, pl_count, nb // rows),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, rows, span),
                               lambda s, p, b: (s, p, b, 0)),
        out_shape=jax.ShapeDtypeStruct((q, pl_count, nb, span), jnp.float32),
        compiler_params=compiler_params(vmem),
        interpret=interpret,
    )(*args)
