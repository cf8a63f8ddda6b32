"""Source-side outbox aggregation Pallas kernel (paper §3.4, §4.3, Fig. 6).

The distributed hybrid engine routes every inter-partition edge through the
outbox-slot segment space of ``partition.py``: one slot per unique
(source-partition, remote-vertex) pair, so aggregation-β (the paper's §3.4
argument) is structural.  This kernel performs the whole boundary leg of the
compute phase in one pass per edge block, entirely in VMEM:

  1. **gather** — the shard's per-vertex message vector ``x`` (the
     ``EdgeMessage`` already evaluated once per vertex with the ⊗-identity
     weight) is VMEM-resident; per-edge source values come from the same
     tiled masked-max select as ``fused_superstep.gather_columns`` (graph
     state legitimately contains ``+inf``, so an MXU gather would produce
     ``0·inf = nan``; state never holds ``-inf``).
  2. **⊗ weight** — the semiring's weight application is inlined:
     ``add`` (min_plus relaxation) or ``mul`` (weighted plus_times);
     weightless programs skip it.
  3. **reduce** — boundary edges are pre-sorted by flat outbox slot id and
     each edge carries its slot's rank among the block's distinct slots, so
     a masked VPU sum or min over the ``[be, span]`` one-hot yields the
     block's partials (``fused_superstep.reduce_block``).

The per-edge boundary messages never exist in HBM — the ``all_to_all``
exchange afterwards moves ``β_with_reduction·|E|`` aggregated slot values
instead of per-edge messages.  Slot ids/bases arrive as *operands* (not
trace constants): under ``shard_map`` every shard carries its own static
maps, stacked on the mesh axis.

The message vector carries a leading **query-batch axis**: ``x[Q, x_pad]``
→ ``[Q, nb, span]`` partials over a ``(Q, nb/8)`` grid, eight edge blocks
(all ``nb`` when fewer) per step as one ``(8, be)`` tile (the TPU's
(8, 128) block rule).  The boundary maps
(``src``/``local``/``mask``/``weight``) are shared across the batch — a
batch of Q concurrent queries aggregates Q outboxes against one copy of
the slot topology.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_superstep import (LANES, TILE, blocks_per_step,
                                           compiler_params, gather_columns,
                                           reduce_block, vmem_bytes)


def _outbox_kernel(x_ref, src_ref, local_ref, mask_ref, *rest,
                   combine: str, weight_op, span: int):
    if weight_op is not None:
        w_ref, o_ref = rest
    else:
        w_ref, o_ref = None, rest[0]

    ident = 0.0 if combine == "sum" else jnp.inf
    # (8, be) edge tiles → (be, 8): column j holds edge block j's edges.
    src_t = src_ref[...].T
    local_t = local_ref[...].T
    mask_t = mask_ref[...].T
    w_t = w_ref[...].T if weight_op is not None else None
    for j in range(src_ref.shape[0]):
        (msgs,) = gather_columns(x_ref, src_t[:, j:j + 1], 1)
        if weight_op == "add":
            msgs = msgs + w_t[:, j:j + 1]
        elif weight_op == "mul":
            msgs = msgs * w_t[:, j:j + 1]
        msgs = jnp.where(mask_t[:, j:j + 1] > 0, msgs, ident)
        o_ref[j:j + 1, :] = reduce_block(msgs, local_t[:, j:j + 1], span,
                                         combine)


@functools.partial(jax.jit,
                   static_argnames=("combine", "weight_op", "span", "block_e",
                                    "interpret"))
def outbox_reduce_blocks(x: jax.Array, src: jax.Array, local: jax.Array,
                         mask: jax.Array, weight, *, combine: str,
                         weight_op=None, span: int, block_e: int = 256,
                         interpret: bool = False) -> jax.Array:
    """Phase-1 outbox partials.

    x: [Q, x_pad] f32 (x_pad % 1024 == 0); src/local/mask (int32) and
    weight (f32 or None): [e_pad] with e_pad / block_e below 8 or a multiple
    of 8 — shared across the query batch.  Returns [Q, e_pad/block_e, span]
    per-block slot partials (phase 2 in ops.py merges blocks sharing a
    boundary slot).
    """
    e_pad = src.shape[0]
    q, x_pad = x.shape
    nb = e_pad // block_e
    rows = blocks_per_step(nb)
    assert e_pad % block_e == 0 and nb % rows == 0 and x_pad % TILE == 0

    kernel = functools.partial(_outbox_kernel, combine=combine,
                               weight_op=weight_op, span=span)
    # Boundary-map blocks ignore the query coordinate: one copy serves all Q.
    edge_spec = pl.BlockSpec((rows, block_e), lambda s, b: (b, 0))
    in_specs = [pl.BlockSpec((None, 1, x_pad // LANES, LANES),
                             lambda s, b: (s, 0, 0, 0)),
                edge_spec, edge_spec, edge_spec]
    args = [x.reshape(q, 1, x_pad // LANES, LANES)] + [
        a.reshape(nb, block_e) for a in (src, local, mask)]
    if weight_op is not None:
        in_specs.append(edge_spec)
        args.append(weight.reshape(nb, block_e))

    vmem = vmem_bytes(1, x_pad, block_e, span, len(args) - 1)
    return pl.pallas_call(
        kernel,
        grid=(q, nb // rows),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, rows, span), lambda s, b: (s, b, 0)),
        out_shape=jax.ShapeDtypeStruct((q, nb, span), jnp.float32),
        compiler_params=compiler_params(vmem),
        interpret=interpret,
    )(*args)
