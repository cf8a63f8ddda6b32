"""Compile rehearsals of every graph kernel for a TPU v5e, without the chip.

Each test compiles one Pallas kernel for a *described* v5e chip (the TPU
compiler is installed even where no chip is attached) at the shapes
``chip_smoke.py`` drives on the real chip, and asserts the Mosaic kernel
made it into the executable (``tpu_custom_call``).  What interpret mode
cannot see — (8, 128) block tiling, ops Mosaic cannot lower, scoped-VMEM
overflow — fails here at no chip time.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
this file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.algorithms.bfs import BFS_PROGRAM
from repro.algorithms.pagerank import make_pagerank_program
from repro.kernels import bottomup, dense_spmv, ell_spmv, fused_superstep
from repro.kernels import outbox_reduce

# chip_smoke.py's fused phase: RMAT scale 18, edge factor 16, 4 HIGH
# partitions — v_max 249,592 (the low-degree partition) padded to whole
# (8, 128) tiles, 1,025 edge blocks of 1,024 padded to whole steps of 8.
FUSED_V_PAD = 250_880
FUSED_E_PAD = 1_032 * 1_024
FUSED_SPAN = 1_024
BFS_Q = 4
# its hybrid phase: uniform scale 18 (ELL in-degree ≤ 36) with a 2,048
# vertex dense block, and the four-chip boundary leg of the same graph.
ELL_V = 1 << 18
ELL_K = 40
DENSE_K = 2_048
OUTBOX_X_PAD = 90_112
OUTBOX_E_PAD = 3_136 * 256
OUTBOX_SPAN = 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no topology"
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("alg", ["bfs_min", "pagerank_sum"])
def test_fused_superstep_compiles(one_chip, alg):
    if alg == "bfs_min":
        program, q = BFS_PROGRAM, BFS_Q
    else:
        program, q = make_pagerank_program(1 << 18), 1
    spec = program.edge_msg

    def msg_fn(vals, weight, scals):            # as core/bsp._compute_fused
        return spec.fn(dict(zip(spec.gather, vals)), weight, scals[0],
                       dict(zip(spec.consts, scals[1:])))

    keys, combine = len(spec.gather), program.combine
    edge = ((4, FUSED_E_PAD), I32)
    _compile(lambda v, s, a, b, c: fused_superstep.fused_superstep_blocks(
        v, s, a, b, c, None, msg_fn=msg_fn, combine=combine,
        span=FUSED_SPAN, block_e=1_024),
        one_chip, ((q, 4, keys, FUSED_V_PAD), F32), ((q, 4, 1), F32),
        edge, edge, edge)


@pytest.mark.parametrize("combine,q", [("min", BFS_Q), ("sum", 1)])
def test_outbox_reduce_compiles(one_chip, combine, q):
    edge = ((OUTBOX_E_PAD,), I32)
    _compile(lambda x, a, b, c: outbox_reduce.outbox_reduce_blocks(
        x, a, b, c, None, combine=combine, span=OUTBOX_SPAN, block_e=256),
        one_chip, ((q, OUTBOX_X_PAD), F32), edge, edge, edge)


def _sliced_compiles(sharding, kernel, semiring, q, widths):
    """Compile ``kernel`` (``ell_spmv`` or ``bottomup_scan``) over a sliced
    ELL of chunk ``widths``: the two step tables, the gathered
    ``[q, Σ w_c, 512]`` values, the edge values unless ``min``, and the
    bottom-up scan's per-row slot counts."""
    chunks, slots = len(widths), int(widths.sum())
    table = ((slots // ell_spmv.SLOTS,), I32)
    shapes = [table, table, ((q, slots, 512), F32)]
    with_val = semiring != "min"
    if with_val:
        shapes.append(((slots, 512), F32))
    kw = dict(semiring=semiring, chunks=chunks)
    if kernel is bottomup.bottomup_scan:
        shapes.append(((1, chunks * 512), I32))
        kw["early_exit"] = True

    def fn(a, b, g, *rest):
        val = rest[0] if with_val else None
        return kernel(a, b, g, val, *rest[with_val:], **kw)

    _compile(fn, sharding, *shapes)


# chip_smoke's rectangular ELL: every chunk at the full width ELL_K.
RECT_WIDTHS = np.full(ELL_V // 512, ELL_K, np.int32)
# uniform-s20's sliced ELL: 2**20 rows in 2,048 chunks, rows longest
# first, so the widths fall from 40 to 8 (Σ w_c ≈ 42.5 K slot rows).
SLICED_WIDTHS = np.repeat(np.array([40, 32, 24, 16, 8], np.int32),
                          [16, 200, 900, 800, 132])


@pytest.mark.parametrize("semiring", ["plus_times", "min_plus", "min"])
def test_ell_spmv_compiles(one_chip, semiring):
    q = 1 if semiring == "plus_times" else BFS_Q
    _sliced_compiles(one_chip, ell_spmv.ell_spmv, semiring, q, RECT_WIDTHS)


@pytest.mark.parametrize("semiring", ["min", "min_plus"])
def test_bottomup_scan_compiles(one_chip, semiring):
    _sliced_compiles(one_chip, bottomup.bottomup_scan, semiring, BFS_Q,
                     RECT_WIDTHS)


@pytest.mark.parametrize("semiring,q", [("plus_times", 1), ("min", 1),
                                        ("min_plus", BFS_Q)])
def test_sliced_ell_spmv_compiles(one_chip, semiring, q):
    _sliced_compiles(one_chip, ell_spmv.ell_spmv, semiring, q,
                     SLICED_WIDTHS)


@pytest.mark.parametrize("semiring,q", [("min", 1), ("min_plus", BFS_Q)])
def test_sliced_bottomup_scan_compiles(one_chip, semiring, q):
    _sliced_compiles(one_chip, bottomup.bottomup_scan, semiring, q,
                     SLICED_WIDTHS)


@pytest.mark.parametrize("op", ["dense_spmv", "dense_spmv_minplus"])
def test_dense_spmv_compiles(one_chip, op):
    fn = getattr(dense_spmv, op)
    _compile(lambda x, a: fn(x, a, block_n=256, block_k=256), one_chip,
             ((BFS_Q, DENSE_K), F32), ((DENSE_K, DENSE_K), F32))


@pytest.mark.parametrize("alg", ["pagerank", "bfs"])
def test_hybrid_loop_phases_on_the_chip(one_chip, alg):
    """The hybrid cells' whole loop as the chip compiles it: every
    gather, scatter, sort and kernel call carries a ``bsp.*`` phase, and the
    ELL kernel runs inside the ``bsp.ell`` leg."""
    from hlo_scopes import compiled_text, instructions, unphased
    from repro.algorithms.bfs import multi_source_state
    from repro.algorithms.pagerank import initial_state
    from repro.core import graph as G, partition as PT
    from repro.core.bsp import BSPEngine, batch_state

    pg = PT.partition(G.uniform(12, 16, seed=3), 4, PT.HIGH)
    engine = BSPEngine(pg, backend="hybrid", hybrid_k_dense=0,
                       interpret=False)
    if alg == "pagerank":
        program, steps = make_pagerank_program(pg.num_vertices), 3
        state, kernel = batch_state(initial_state(pg)), "ell_spmv"
    else:
        program, steps = BFS_PROGRAM, None
        state = {"level": jnp.asarray(multi_source_state(pg, [0]))}
        kernel = "bottomup_scan"
    rows = instructions(compiled_text(
        engine, program, state, steps, place=lambda tree: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)))
    assert unphased(rows) == []
    calls = [r for r in rows if r["target"] == "tpu_custom_call"]
    assert calls and all(r["name"].startswith(kernel + ".") for r in calls)
    assert all("/bsp.ell/bsp.reduce/" in r["op_name"] for r in calls)
