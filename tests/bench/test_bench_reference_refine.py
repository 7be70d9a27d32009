"""The full CUTTANA reference (``bench/reference_refine.py``) against the
system: ``cuttana-parallel`` with refinement on, S=4, through
``repro.api.partition`` (the host histogram path on the CPU) and through the
same callable with the score kernel in interpret mode, vertex for vertex."""
import numpy as np
import pytest

import bench_util  # noqa: F401  (puts the repo root on sys.path)
from bench import reference_refine
from bench.graph500 import graph500_csr
from repro.api import PartitionSpec, partition
from repro.api.registry import build_spec_kwargs, get_info
from repro.graph.csr import CSRGraph

PARAMS = {"num_shards": 4, "use_refinement": True}


@pytest.mark.parametrize(
    "scale,seed", [(12, 5), (12, 2**33 + 9), (13, 2**40 + 7), (13, 424242)]
)
def test_reference_equals_full_cuttana(scale, seed):
    indptr, indices = graph500_csr(20240601, seed, scale)
    graph = CSRGraph(indptr, indices)
    spec = PartitionSpec(algo="cuttana-parallel", k=8, epsilon=0.05, balance_mode="edge",
                         order="random", seed=seed, params=PARAMS)
    res = partition(graph, spec)
    assert res.telemetry["refine_moves"] > 0
    info = get_info(spec.algo)
    kernel = info.resolve()(graph, 8, **build_spec_kwargs(info, spec), interpret=True)
    want = reference_refine.cuttana_refine(indptr, indices, 8, seed, num_shards=4, epsilon=0.05)
    np.testing.assert_array_equal(res.assignment, want)
    np.testing.assert_array_equal(kernel, want)


def test_refinement_lowers_the_cut_and_stops_at_maximal():
    """The reference's phase 2 alone: every trade lowers the cut, and none
    is left that would."""
    from bench import reference

    indptr, indices = graph500_csr(3, 5, 11)
    k, seed = 8, 11
    part, steps = reference_refine.phase1_supersteps(indptr, indices, k, seed, 4)
    np.testing.assert_array_equal(
        part, reference.cuttana_parallel(indptr, indices, k, seed, num_shards=4)
    )
    assert sorted(np.concatenate(steps).tolist()) == list(range(indptr.shape[0] - 1))
    s = 8
    sub_of, mass = reference_refine.subpartitions(indptr, indices, k, part, steps, s, 0.10)
    np.testing.assert_array_equal(sub_of // s, part)
    w = reference_refine.subpartition_graph(indptr, indices, sub_of, k * s)
    cap = 1.05 * indices.shape[0] / k
    home = np.repeat(np.arange(k), s)
    final, moves = reference_refine.greedy_trades(w, home, mass, k, cap)
    before = reference.edge_cut(indptr, indices, part)
    after = reference.edge_cut(indptr, indices, final[sub_of])
    assert moves > 0 and after < before
    again, more = reference_refine.greedy_trades(w, final, mass, k, cap)
    assert more == 0 and np.array_equal(again, final)
