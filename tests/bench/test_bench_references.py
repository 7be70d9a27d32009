"""The benchmark's own generator and references against the repository's:
the Graph500 generator matches the repo's R-MAT degree statistics and CSR
layout, the PageRank reference equals ``reference_pagerank`` to float64
rounding, and the partition references equal the system's host path vertex
for vertex (the kernel path is bit-identical to it, pinned elsewhere)."""
import numpy as np
import pytest

import bench_util  # noqa: F401  (puts the repo root on sys.path)
from bench import reference
from bench.graph500 import csr_from_edges, graph500_csr
from repro.api import PartitionSpec, partition
from repro.graph.csr import CSRGraph


@pytest.fixture(scope="module")
def g14():
    return graph500_csr(7, 2**40 + 3, 14)


def test_csr_build_matches_from_edges():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    edges = rng.integers(0, 500, size=(6000, 2))
    edges[:50, 1] = edges[:50, 0]  # self loops are dropped
    indptr, cols = csr_from_edges(
        jnp.asarray(edges[:, 0], jnp.int32), jnp.asarray(edges[:, 1], jnp.int32),
        num_vertices=500,
    )
    want = CSRGraph.from_edges(edges, num_vertices=500)
    indptr = np.asarray(indptr)
    np.testing.assert_array_equal(indptr, want.indptr)
    np.testing.assert_array_equal(np.asarray(cols)[: indptr[-1]], want.indices)


def test_generator_matches_repo_rmat_degree_statistics(g14):
    from repro.graph.generators import rmat_graph

    indptr, indices = g14
    ours = np.diff(indptr)
    repo = rmat_graph(1 << 14, avg_degree=32, seed=5).degrees
    assert indices.shape[0] == indptr[-1]
    assert abs(ours.sum() / repo.sum() - 1) < 0.01
    assert abs((ours == 0).mean() - (repo == 0).mean()) < 0.01
    for q in (50, 90, 99):
        a, b = np.percentile(ours, q), np.percentile(repo, q)
        assert abs(a - b) <= max(2, 0.05 * b), (q, a, b)
    assert 0.7 < ours.max() / repo.max() < 1.4


def test_seed_relabels_one_fixed_graph():
    a = graph500_csr(11, 2**35 + 1, 10)
    b = graph500_csr(11, 2**35 + 1, 10)
    c = graph500_csr(11, 1, 10)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    # the same graph under other ids: same degrees, same edge count
    np.testing.assert_array_equal(np.sort(np.diff(a[0])), np.sort(np.diff(c[0])))
    assert a[1].shape == c[1].shape


def test_pagerank_reference_equals_repo_reference(g14):
    from repro.analytics.programs import reference_pagerank

    indptr, indices = g14
    want = reference_pagerank(CSRGraph(indptr, indices), 30)
    got = reference.pagerank(indptr, indices, 30)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=0)


def test_edge_cut_equals_repo_metric(g14):
    from repro.graph.metrics import edge_cut

    indptr, indices = g14
    part = np.random.default_rng(3).integers(0, 8, indptr.shape[0] - 1)
    assert reference.edge_cut(indptr, indices, part) == edge_cut(
        CSRGraph(indptr, indices), part
    )


@pytest.mark.parametrize("seed", [7, 2**33 + 9])
@pytest.mark.parametrize(
    "algo,params,ref,ref_args",
    [
        ("fennel", None, reference.fennel, {}),
        ("cuttana-parallel", {"num_shards": 4, "use_refinement": False},
         reference.cuttana_parallel, {"num_shards": 4}),
    ],
)
def test_partition_reference_equals_system(g14, seed, algo, params, ref, ref_args):
    indptr, indices = g14
    spec = PartitionSpec(algo=algo, k=8, epsilon=0.05, balance_mode="edge",
                         order="random", seed=seed, params=params)
    got = partition(CSRGraph(indptr, indices), spec).assignment
    want = ref(indptr, indices, 8, seed, epsilon=0.05, **ref_args)
    np.testing.assert_array_equal(got, want)
