"""Dense-block SpMV Pallas kernel — the MXU path of the hybrid engine.

TOTEM's insight is to hand each part of a heterogeneous workload to the
processing element it fits best (paper §6.2).  On TPU the "CPU-like" element
for the high-degree vertex block is the **MXU**: the adjacency sub-matrix
among the top-degree vertices of a scale-free graph is dense enough that
SpMV-as-GEMM beats gather-based SpMV (see
``perf_model.mxu_crossover_density``).  The paper's cache-resident "visited"
bitmap (§6.3.2) maps to the VMEM residency of the value slice ``x``: the
x-block is re-used across all output tiles of a row stripe.

Computes ``y[M, N] = x[M, K] @ a[K, N]`` where ``a`` is the dense
adjacency block of the high-degree partition, ``x`` carries the per-vertex
values (rank / frontier levels / multi-source batch on the M axis).

Grid: ``(N/bn, K/bk)`` — the contraction (k) axis is innermost so the output
tile stays resident in VMEM while partial products accumulate (revolving
accumulator), and Pallas grid pipelining double-buffers the HBM→VMEM streams
of ``a`` — the TPU analogue of the paper's mapped-memory streaming (§8).
Tiles are 128-aligned for the 128×128 systolic array.

``dense_spmv_minplus`` is the tropical (min, +) twin for the traversal
algorithms (BFS/SSSP/CC): ``y[m, n] = min_k x[m, k] + a[k, n]`` with the same
grid/tiling, except the reduction runs on the VPU (the MXU only contracts
(+, ×)) — the dense block still wins on locality: ``a``'s tiles stream
HBM→VMEM once and ``x`` stays resident, vs. a random gather per edge.
Non-edges hold +inf, the ⊕-identity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dense_spmv_kernel(x_ref, a_ref, o_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # MXU matmul with f32 accumulation.  HIGHEST: Mosaic's default
    # contraction precision rounds f32 operands to bf16 (~3 significant
    # digits), which PageRank's per-vertex values do not survive.
    o_ref[...] += jnp.dot(x_ref[...], a_ref[...],
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_k", "interpret"))
def dense_spmv(x: jax.Array, a: jax.Array, *, block_n: int = 256,
               block_k: int = 256, interpret: bool = False) -> jax.Array:
    """``y = x @ a`` with explicit VMEM tiling.

    x: [M, K], a: [K, N], both f32. M is the value-channel
    axis (1 for plain SpMV, padded to 8 sublanes by ops.py).
    """
    m, k = x.shape
    k2, n = a.shape
    assert k == k2, (x.shape, a.shape)
    assert n % block_n == 0 and k % block_k == 0, (
        "ops.dense_spmv_op pads to block multiples")
    grid = (n // block_n, k // block_k)
    return pl.pallas_call(
        _dense_spmv_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, block_k), lambda j, kk: (0, kk)),
            pl.BlockSpec((block_k, block_n), lambda j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((m, block_n), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, a)


def _dense_minplus_kernel(x_ref, a_ref, o_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, jnp.inf)

    # VPU min-reduction over the contraction tile; the [m, bk, bn] candidate
    # cube stays in registers/VMEM for the small m this path uses.
    cand = jnp.min(x_ref[...][:, :, None] + a_ref[...][None, :, :], axis=1)
    o_ref[...] = jnp.minimum(o_ref[...], cand)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_k", "interpret"))
def dense_spmv_minplus(x: jax.Array, a: jax.Array, *, block_n: int = 256,
                       block_k: int = 256,
                       interpret: bool = False) -> jax.Array:
    """``y[m, n] = min_k x[m, k] + a[k, n]`` with explicit VMEM tiling.

    Same contract as :func:`dense_spmv` (ops.py pads to block multiples);
    padding entries of ``x``/``a`` must hold +inf.
    """
    m, k = x.shape
    k2, n = a.shape
    assert k == k2, (x.shape, a.shape)
    assert n % block_n == 0 and k % block_k == 0, (
        "ops.dense_spmv_minplus_op pads to block multiples")
    grid = (n // block_n, k // block_k)
    return pl.pallas_call(
        _dense_minplus_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, block_k), lambda j, kk: (0, kk)),
            pl.BlockSpec((block_k, block_n), lambda j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((m, block_n), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, a)
