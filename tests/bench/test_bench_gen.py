"""The benchmark's graph generators: seeded determinism, the degree
statistics of Kronecker and uniform graphs, the symmetry and label
scrambling of the Graph500 graph, and a canonical CSR."""
import numpy as np
import pytest

import bench_helpers  # noqa: F401  (puts bench on the path)
from bench import gen
from repro.core.graph import from_edge_list

KRON = dict(generator="kronecker", structure_seed=1, scale=12,
            edge_factor=16, a=0.57, b=0.19, c=0.19, undirected=True)
UNIFORM = dict(generator="uniform", structure_seed=1, scale=12,
               edge_factor=16, undirected=False)


@pytest.fixture(scope="module")
def kron():
    return gen.generate(KRON, 7)


@pytest.fixture(scope="module")
def uniform():
    return gen.generate(UNIFORM, 7)


@pytest.mark.parametrize("spec", [KRON, UNIFORM], ids=["kron", "uniform"])
def test_same_seed_same_graph(spec):
    a, b = gen.generate(spec, 2**31 + 5), gen.generate(spec, 2**31 + 5)
    c = gen.generate(spec, 2**31 + 6)
    assert np.array_equal(a.row_ptr, b.row_ptr)
    assert np.array_equal(a.col, b.col)
    assert not np.array_equal(a.col, c.col)


@pytest.mark.parametrize("spec", [KRON, UNIFORM], ids=["kron", "uniform"])
def test_seeds_rename_one_structure(spec):
    # Two seeds give the same graph with its vertices renamed: mapping the
    # one's edges through the ids of both gives the other's.
    a, b = gen.generate(spec, 11), gen.generate(spec, 2**31 + 12)
    assert not np.array_equal(a.ids, b.ids)
    to_b = np.empty_like(a.ids)
    to_b[a.ids] = b.ids
    mapped = from_edge_list(to_b[a.edge_sources()], to_b[a.col],
                            a.num_vertices)
    assert np.array_equal(mapped.row_ptr, b.row_ptr)
    assert np.array_equal(mapped.col, b.col)
    other = gen.generate(dict(spec, structure_seed=2), 11)
    assert not np.array_equal(np.sort(other.out_degrees()),
                              np.sort(a.out_degrees()))


def test_seed_beyond_32_bits_is_its_own():
    small = dict(UNIFORM, scale=6)
    a = gen.generate(small, 5)
    b = gen.generate(small, 5 + 2**32)
    assert not np.array_equal(a.col, b.col)
    with pytest.raises(ValueError):
        gen.seed_key(-1)


@pytest.mark.parametrize("which", ["kron", "uniform"])
def test_csr_is_canonical(which, request):
    g = request.getfixturevalue(which)
    ref = from_edge_list(g.edge_sources(), g.col, g.num_vertices)
    assert np.array_equal(ref.row_ptr, g.row_ptr)
    assert np.array_equal(ref.col, g.col)
    assert g.col.dtype == np.int32 and g.row_ptr.dtype == np.int64


def test_kronecker_sizes_and_symmetry(kron):
    n = 1 << KRON["scale"]
    assert kron.num_vertices == n
    assert kron.num_tuples == 16 * n
    assert kron.num_edges == 2 * 16 * n          # every tuple both ways
    pairs = np.stack([kron.edge_sources(), kron.col], axis=1)
    fwd = np.unique(pairs, axis=0, return_counts=True)
    rev = np.unique(pairs[:, ::-1], axis=0, return_counts=True)
    assert np.array_equal(fwd[0], rev[0]) and np.array_equal(fwd[1], rev[1])


def test_kronecker_degrees_are_skewed(kron):
    deg = kron.out_degrees()
    assert deg.mean() == pytest.approx(32.0)
    # Graph500's Kronecker graph: many isolated vertices, a heavy tail.
    assert 0.15 < np.mean(deg == 0) < 0.5
    assert deg.max() > 40 * deg.mean()


def test_kronecker_labels_are_scrambled(kron):
    # Unscrambled, degree falls with the number of 1-bits in a vertex id
    # (each 1 bit picks a less likely quadrant); the seeded scramble
    # removes that order.
    ids = np.arange(kron.num_vertices)
    ones = np.array([bin(i).count("1") for i in ids])
    deg = kron.out_degrees()
    low, high = deg[ones <= 3].mean(), deg[ones >= 9].mean()
    assert 0.5 < low / high < 2.0


@pytest.mark.parametrize("scale,vals", [(1, (0, 0)), (12, (7, 2**31 + 9)),
                                        (20, (2**32 - 1, 12345))])
def test_scramble_is_a_bijection(scale, vals):
    import jax.numpy as jnp

    ids = jnp.arange(1 << scale, dtype=jnp.int32)
    out = np.asarray(gen.scramble(ids, scale, jnp.uint32(vals[0]),
                                  jnp.uint32(vals[1])))
    assert np.array_equal(np.sort(out), np.arange(1 << scale))
    if scale > 1:
        assert np.mean(out == np.arange(1 << scale)) < 0.01


def test_uniform_degrees_are_poisson(uniform):
    n = 1 << UNIFORM["scale"]
    assert uniform.num_edges == 16 * n
    deg = uniform.out_degrees()
    assert deg.mean() == pytest.approx(16.0)
    assert 3.0 < deg.std() < 5.0                # Poisson(16): sqrt(16) = 4
    assert deg.max() < 50
    indeg = np.bincount(uniform.col, minlength=n)
    assert 3.0 < indeg.std() < 5.0


def test_distinct_edges_counts_duplicates_once():
    g = gen.GeneratedGraph(row_ptr=np.array([0, 3, 4, 4]),
                           col=np.array([1, 1, 2, 0], dtype=np.int32),
                           num_tuples=4, undirected=False)
    assert g.distinct_edges() == 3
