"""Per-kernel allclose vs ref.py oracles — shape/dtype sweeps + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import given, settings, st  # hypothesis or skip-stub shim

from repro.core import graph as G
from repro.core.hybrid import degree_split, hybrid_pagerank
from repro.algorithms import pagerank_reference
from repro.kernels import ops, ref

INTERP = dict(interpret=True)


# ---------------------------------------------------------------------------
# dense_spmv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [1, 8])
@pytest.mark.parametrize("k,n", [(128, 128), (256, 384), (300, 200),
                                 (512, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dense_spmv_shapes_dtypes(m, k, n, dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(m, k)), dtype=dtype)
    a = jnp.asarray(rng.random(size=(k, n)) < 0.05, dtype=dtype)
    got = ops.dense_spmv_op(x, a, **INTERP)
    want = ref.dense_spmv_ref(x, a)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@settings(max_examples=20, deadline=None)
@given(m=st.integers(1, 4), k=st.integers(1, 200), n=st.integers(1, 200),
       seed=st.integers(0, 2**31 - 1))
def test_dense_spmv_property(m, k, n, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(m, k)), dtype=jnp.float32)
    a = jnp.asarray(rng.normal(size=(k, n)), dtype=jnp.float32)
    got = ops.dense_spmv_op(x, a, **INTERP)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.dense_spmv_ref(x, a)),
                               rtol=1e-4, atol=1e-4)


def test_dense_spmv_asks_full_f32_precision():
    # Mosaic's default contraction rounds f32 operands to bf16 on the MXU;
    # interpret mode cannot show that, so check what the kernel asks for.
    from repro.kernels import dense_spmv

    x = jnp.ones((8, 128), jnp.float32)
    a = jnp.ones((128, 128), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x, a: dense_spmv.dense_spmv(
        x, a, block_n=128, block_k=128, **INTERP))(x, a)
    assert "precision=(Precision.HIGHEST, Precision.HIGHEST)" in str(jaxpr)


# ---------------------------------------------------------------------------
# ell_spmv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("combine", ["sum", "min"])
@pytest.mark.parametrize("v,kmax", [(64, 4), (500, 16), (1024, 3)])
def test_ell_spmv_shapes(combine, v, kmax):
    rng = np.random.default_rng(1)
    ident = 0.0 if combine == "sum" else np.inf
    col = rng.integers(0, v, size=(v, kmax)).astype(np.int32)
    pad = rng.random((v, kmax)) < 0.3
    col[pad] = v  # sentinel slot
    val = rng.uniform(0.5, 2.0, size=(v, kmax)).astype(np.float32)
    val[pad] = ident
    x = np.concatenate([rng.normal(size=v).astype(np.float32)
                        if combine == "sum"
                        else rng.uniform(0, 10, size=v).astype(np.float32),
                        [ident]])
    got = ops.ell_spmv_op(jnp.asarray(col), jnp.asarray(val), jnp.asarray(x),
                          combine=combine, **INTERP)
    want = ref.ell_spmv_ref(jnp.asarray(col), jnp.asarray(val),
                            jnp.asarray(x), combine=combine)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(v=st.integers(1, 300), kmax=st.integers(1, 8),
       seed=st.integers(0, 2**31 - 1))
def test_ell_spmv_property_sum(v, kmax, seed):
    rng = np.random.default_rng(seed)
    col = rng.integers(0, v + 1, size=(v, kmax)).astype(np.int32)
    val = np.where(col == v, 0.0,
                   rng.normal(size=(v, kmax))).astype(np.float32)
    x = np.concatenate([rng.normal(size=v), [0.0]]).astype(np.float32)
    got = ops.ell_spmv_op(jnp.asarray(col), jnp.asarray(val), jnp.asarray(x),
                          combine="sum", **INTERP)
    want = ref.ell_spmv_ref(jnp.asarray(col), jnp.asarray(val),
                            jnp.asarray(x), combine="sum")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("semiring", ["plus_times", "min_plus", "min"])
def test_ell_spmv_slot_tiling_matches_ref(semiring):
    """Rows wider than one 8-slot block fold over several grid steps, for
    a wide batch too; the accumulated rows still match the oracle."""
    from repro.kernels import ell_spmv as ell

    q, v, kmax = 32, 300, 72
    assert kmax > 8 * ell.SLOTS
    rng = np.random.default_rng(3)
    col = rng.integers(0, v + 1, size=(v, kmax)).astype(np.int32)
    mul_ident = ell.SEMIRINGS[semiring][2]
    val = np.where(col == v, mul_ident,
                   rng.uniform(0.5, 2.0, size=(v, kmax))).astype(np.float32)
    ident = ell.SEMIRINGS[semiring][1]
    x = np.concatenate([rng.uniform(0, 10, size=(q, v)),
                        np.full((q, 1), ident)], axis=1).astype(np.float32)
    got = np.asarray(ops.ell_spmv_op(col, val, x, semiring=semiring,
                                     **INTERP))
    g = x[:, col]                                        # [Q, V, K]
    if semiring == "plus_times":
        want = (g * val[None]).sum(axis=2)
    elif semiring == "min_plus":
        want = (g + val[None]).min(axis=2)
    else:
        want = g.min(axis=2)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_kernel_paths_record_the_compute_path():
    """Every kernel call site counts the path its trace took; a program
    without an EdgeMessage on a fused engine counts as that backend's XLA
    path."""
    import dataclasses

    from repro.algorithms.bfs import BFS_PROGRAM, bfs, multi_source_state
    from repro.core import partition as PT
    from repro.core.bsp import BSPEngine

    pg = PT.partition(G.rmat(7, 4, seed=2), 2, PT.RAND)
    kernel0 = ops.KERNEL_PATHS[("fused_superstep", "interpret")]
    bfs(BSPEngine(pg, backend="fused", **INTERP), 0)
    assert ops.KERNEL_PATHS[("fused_superstep", "interpret")] > kernel0

    plain = dataclasses.replace(BFS_PROGRAM, edge_msg=None)
    xla0 = ops.KERNEL_PATHS[("fused", "xla")]
    BSPEngine(pg, backend="fused", **INTERP).execute(
        plain, {"level": jnp.asarray(multi_source_state(pg, [0]))})
    assert ops.KERNEL_PATHS[("fused", "xla")] > xla0


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64),
                                           (False, 0)])
@pytest.mark.parametrize("s,d", [(256, 64), (512, 128)])
def test_flash_attention_matches_ref(causal, window, s, d):
    rng = np.random.default_rng(2)
    b, h, kv = 2, 4, 2
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), dtype=jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, kv, s, d)), dtype=jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, kv, s, d)), dtype=jnp.float32)
    got = ops.flash_attention_op(q, k, v, causal=causal, window=window,
                                 block_q=128, block_k=128, **INTERP)
    kr = jnp.repeat(k, h // kv, axis=1).reshape(b * h, s, d)
    vr = jnp.repeat(v, h // kv, axis=1).reshape(b * h, s, d)
    want = ref.attention_ref(q.reshape(b * h, s, d), kr, vr, causal=causal,
                             window=window).reshape(b, h, s, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), dtype=jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), dtype=jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), dtype=jnp.bfloat16)
    got = ops.flash_attention_op(q, k, v, block_q=128, block_k=128, **INTERP)
    want = ref.attention_ref(q.reshape(2, 256, 64), k.reshape(2, 256, 64),
                             v.reshape(2, 256, 64)).reshape(1, 2, 256, 64)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# hybrid two-engine step (integration: kernels + degree split)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k_dense", [0, 64, 256])
def test_hybrid_pagerank_matches_reference(k_dense):
    g = G.rmat(9, 8, seed=5)
    hg = degree_split(g, k_dense)
    assert hg.dense_edges + hg.sparse_edges == g.num_edges
    got = hybrid_pagerank(hg, num_iterations=10, interpret=True)
    want = pagerank_reference(g, num_iterations=10)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-6)


def test_degree_split_captures_skew():
    """On a scale-free graph a tiny dense block captures many edges."""
    g = G.rmat(10, 16, seed=4)
    hg = degree_split(g, 128)  # 128 of 1024 vertices
    assert hg.dense_fraction > 0.15
    overall_density = g.num_edges / g.num_vertices ** 2
    assert hg.dense_density > 10 * overall_density
    # above the MXU crossover: the dense path is the right engine for H×H
    from repro.core import perf_model
    assert hg.dense_density > perf_model.mxu_crossover_density()


# ---------------------------------------------------------------------------
# segment reduce (TOTEM message reduction)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("combine", ["sum", "min"])
@pytest.mark.parametrize("e,s", [(100, 10), (2048, 300), (5000, 50)])
def test_segment_reduce_matches_ref(combine, e, s):
    rng = np.random.default_rng(8)
    seg = np.sort(rng.integers(0, s, size=e)).astype(np.int32)
    msgs = jnp.asarray(rng.normal(size=e) if combine == "sum"
                       else rng.uniform(0, 100, size=e), jnp.float32)
    got = ops.segment_reduce_op(msgs, seg, s, combine=combine,
                                block_e=256, **INTERP)
    want = ref.segment_reduce_ref(msgs, jnp.asarray(seg), s, combine)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_segment_reduce_sparse_fallback():
    """Gappy ids exceed max_span → exact fallback path."""
    rng = np.random.default_rng(9)
    seg = np.sort(rng.choice(10**6, size=512, replace=False)).astype(np.int32)
    msgs = jnp.asarray(rng.normal(size=512), jnp.float32)
    got = ops.segment_reduce_op(msgs, seg, 10**6, combine="sum",
                                max_span=64, **INTERP)
    want = ref.segment_reduce_ref(msgs, jnp.asarray(seg), 10**6, "sum")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(e=st.integers(1, 600), s=st.integers(1, 80),
       seed=st.integers(0, 2**31 - 1))
def test_segment_reduce_property(e, s, seed):
    rng = np.random.default_rng(seed)
    seg = np.sort(rng.integers(0, s, size=e)).astype(np.int32)
    msgs = jnp.asarray(rng.normal(size=e), jnp.float32)
    got = ops.segment_reduce_op(msgs, seg, s, combine="sum", block_e=128,
                                **INTERP)
    want = ref.segment_reduce_ref(msgs, jnp.asarray(seg), s, "sum")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_segment_reduce_on_engine_outbox_data():
    """Integration: reduce a real partition's dst_ext exactly like the BSP
    engine's compute phase."""
    g = G.rmat(9, 8, seed=11)
    import repro.core.partition as PT
    pg = PT.partition(g, 2, PT.HIGH)
    p = 0
    n_edges = int(pg.fwd.num_edges[p])
    dst = pg.fwd.dst_ext[p, :n_edges]
    order = np.argsort(dst, kind="stable")
    msgs = jnp.asarray(
        np.random.default_rng(0).normal(size=n_edges), jnp.float32)
    got = ops.segment_reduce_op(msgs[order], dst[order], pg.seg_count,
                                combine="sum", **INTERP)
    want = ref.segment_reduce_ref(msgs[order], jnp.asarray(dst[order]),
                                  pg.seg_count, "sum")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
