"""Analytics engine correctness vs dense references + cost-model sanity."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analytics import (
    GraphEngine,
    localize,
    pagerank_program,
    cc_program,
    sssp_program,
    workload_cost,
)
from repro.analytics.programs import (
    reference_cc,
    reference_pagerank,
    reference_sssp,
)
from repro.core import get_partitioner
from repro.core.hdrf import partition_hdrf
from repro.graph import rmat_graph, road_graph


@pytest.fixture(scope="module")
def graph():
    return rmat_graph(1500, avg_degree=10, seed=3)


@pytest.fixture(scope="module")
def lg(graph):
    part = get_partitioner("cuttana")(graph, 4, balance_mode="edge", seed=0)
    return localize(graph, part, 4)


def test_localize_shapes_and_consistency(graph, lg):
    assert lg.local_count.sum() == graph.num_vertices
    # every real edge slot appears exactly once across devices
    real = (lg.rows != lg.v_max).sum()
    assert real == graph.indices.shape[0]
    # true halo messages == sum of send counts and matches comm-volume defn
    from repro.graph.metrics import communication_volume

    cv = communication_volume(graph, lg.part, lg.k)
    assert abs(lg.true_halo_messages() - cv * lg.k * graph.num_vertices) < 1e-6


def test_pagerank_matches_reference(graph, lg):
    eng = GraphEngine(lg, pagerank_program())
    got = eng.run_simulated(iters=15)
    want = reference_pagerank(graph, iters=15)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-9)
    # dangling (degree-0) vertices leak mass in both engine and reference;
    # what matters is agreement + positivity
    assert (got > 0).all()


def test_cc_matches_reference(graph, lg):
    eng = GraphEngine(lg, cc_program())
    got = eng.run_simulated(iters=30)
    want = reference_cc(graph, iters=30)
    np.testing.assert_allclose(got, want)


def test_sssp_matches_reference(graph, lg):
    eng = GraphEngine(lg, sssp_program(source=7))
    got = eng.run_simulated(iters=25)
    want = reference_sssp(graph, iters=25, source=7)
    finite = np.isfinite(want)
    np.testing.assert_allclose(got[finite], want[finite])
    assert (got[~finite] > 1e30).all()


def gather_sizes(hlo: str) -> list[int]:
    """Element count of every ``gather`` instruction's output in compiled HLO."""
    return [
        int(np.prod([int(d) for d in dims.split(",") if d]))
        for dims in re.findall(r"= \w+\[([\d,]*)\][^ ]* gather\(", hlo)
    ]


PROGRAM_FACTORIES = {
    "pagerank": pagerank_program,
    "cc": cc_program,
    "sssp": lambda: sssp_program(source=7),
}


@pytest.mark.parametrize("name", sorted(PROGRAM_FACTORIES))
def test_one_edge_gather_per_step(lg, name):
    """The step gathers one array by edge (the per-slot message), beside
    the ghost-table gather of the states to send."""
    eng = GraphEngine(lg, PROGRAM_FACTORIES[name]())
    state = jnp.asarray(eng.program.init_state(lg, eng.ctx))
    arrays = tuple(jnp.asarray(a) for a in eng.graph_arrays())
    sizes = gather_sizes(eng._sim_step.lower(state, *arrays).compile().as_text())
    edge_len, send_len = lg.k * lg.rows.shape[1], lg.send_gather.size
    assert edge_len != send_len
    assert sizes.count(edge_len) == 1, sizes
    assert sizes.count(send_len) == 1, sizes


def per_edge_run(lg, program, ctx, iters):
    """The step with the message applied per edge, after gathering each
    edge's source state and degree, in plain jnp."""

    def local(st, recv, rows, cols, deg_full):
        identity = jnp.asarray(program.identity, st.dtype)
        full = jnp.concatenate([st, recv.reshape(-1), identity[None]])
        msgs = program.message(full[cols], deg_full[cols])
        if program.reduce_kind == "sum":
            agg = jnp.zeros(lg.v_max + 1, st.dtype).at[rows].add(msgs)
        else:
            agg = jnp.full(lg.v_max + 1, identity).at[rows].min(msgs)
        return program.apply(st, agg[: lg.v_max], ctx)

    @jax.jit
    def step(state, rows, cols, deg_full, send_gather):
        send = state[jnp.arange(lg.k)[:, None, None], send_gather]
        recv = jnp.transpose(send, (1, 0, 2))
        return jax.vmap(local)(state, recv, rows, cols, deg_full)

    state = jnp.asarray(program.init_state(lg, ctx))
    arrays = tuple(jnp.asarray(a) for a in (lg.rows, lg.cols, lg.degrees_full, lg.send_gather))
    for _ in range(iters):
        state = step(state, *arrays)
    return np.asarray(state)


@pytest.mark.parametrize("name", sorted(PROGRAM_FACTORIES))
def test_per_slot_message_is_bit_identical_to_per_edge(lg, name):
    eng = GraphEngine(lg, PROGRAM_FACTORIES[name]())
    got = eng.run_simulated(iters=5)
    want = eng.gather_global(per_edge_run(lg, eng.program, eng.ctx, iters=5))
    assert np.array_equal(got, want)


def test_partition_quality_reduces_halo_traffic(graph):
    """The paper's whole point: better partitions -> less network."""
    k = 4
    rand = localize(graph, get_partitioner("random")(graph, k, seed=0), k)
    good = localize(
        graph, get_partitioner("cuttana")(graph, k, balance_mode="edge", seed=0), k
    )
    assert good.true_halo_messages() < rand.true_halo_messages()


def test_cost_model_orders_partitioners(graph):
    k = 4
    rand = workload_cost(graph, get_partitioner("random")(graph, k, seed=0), k, 30)
    cut = workload_cost(
        graph, get_partitioner("cuttana")(graph, k, balance_mode="edge", seed=0), k, 30
    )
    assert cut["network_s_per_iter"] < rand["network_s_per_iter"]
    assert cut["straggler_ratio"] < 1.5


def test_cost_model_vertex_cut(graph):
    ep = partition_hdrf(graph, 4, seed=0)
    res = workload_cost(graph, ep, 4, 10)
    assert res["total_s"] > 0
    assert res["straggler_ratio"] < 1.5  # edge partitioners balance edges


def test_engine_on_road_graph():
    g = road_graph(2000, seed=1)
    part = get_partitioner("fennel")(g, 4, seed=0)
    lg = localize(g, part, 4)
    got = GraphEngine(lg, pagerank_program()).run_simulated(iters=10)
    want = reference_pagerank(g, iters=10)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-9)
