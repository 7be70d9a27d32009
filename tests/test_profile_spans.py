"""The program's span surface (``repro.core.profile``): recorder totals, the
spans every engine-backed partitioner reports, the score path's slot
counters, and the named scopes of the vertex-program step."""
import re
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.analytics import GraphEngine, localize, pagerank_program
from repro.api import PartitionSpec, partition
from repro.core.fennel import partition as fennel_partition
from repro.core.parallel import partition_parallel
from repro.core.profile import SPANS, SpanRecorder
from repro.graph import rmat_graph
from repro.kernels.partition_score.ops import kernel_tiling

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))  # bench.trace reads op names back from HLO

from bench.trace import hlo_ops_from  # noqa: E402

SCORE = {"score.pack", "score.launch"}


@pytest.fixture(scope="module")
def graph():
    return rmat_graph(3000, avg_degree=10, seed=11)


def test_recorder_totals_counts_and_nesting():
    rec = SpanRecorder()
    for _ in range(3):
        with rec.span("partition.phase1"):
            with rec.span("engine.place"):
                pass
    assert rec.counts["partition.phase1"] == rec.counts["engine.place"] == 3
    assert rec.seconds["partition.phase1"] >= rec.seconds["engine.place"] > 0.0
    assert set(rec.to_dict()) == {"partition.phase1", "engine.place"}
    assert rec.to_dict()["engine.place"] == {"s": rec.seconds["engine.place"], "n": 3}


def test_a_span_counts_when_its_body_raises():
    rec = SpanRecorder()
    with pytest.raises(RuntimeError):
        with rec.span("engine.merge"):
            raise RuntimeError("boom")
    assert rec.counts["engine.merge"] == 1


def test_an_unknown_span_name_is_an_error():
    rec = SpanRecorder()
    with pytest.raises(ValueError, match="unknown span"):
        rec.span("engine.bogus")
    assert "engine.bogus" not in rec.seconds and len(rec.seconds) == len(SPANS)


def test_superstep_profile_is_a_view_of_the_span_totals():
    rec = SpanRecorder(keep=1)
    for _ in range(2):
        with rec.span("score.launch"):
            pass
        with rec.span("engine.place"):
            pass
        rec.end_superstep()
    prof = rec.profile(workers=3)
    assert prof["workers"] == 3 and prof["supersteps"] == 2
    assert prof["score_s"] == round(rec.seconds["score.launch"], 6)
    assert prof["place_s"] == round(rec.seconds["engine.place"], 6)
    assert len(prof["per_superstep"]) == 1


def test_a_span_lies_on_the_profiler_host_plane(tmp_path):
    from bench.trace import load

    rec = SpanRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with rec.span("score.launch"):
                jax.numpy.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    names = {e[0] for e in load(str(tmp_path)).host}
    assert "score.launch" in names


def test_fennel_reports_its_spans_and_slot_counters(graph):
    tel: dict = {}
    fennel_partition(graph, 4, order="random", seed=3, chunk=256, interpret=True,
                     telemetry=tel)
    spans = tel["spans"]
    assert {"partition.phase1", "engine.fetch", "engine.corr", "engine.place"} | SCORE <= set(spans)
    chunks = -(-graph.num_vertices // 256)
    for name in ("engine.fetch", "score.pack", "score.launch", "engine.place"):
        assert spans[name]["n"] == chunks, name
    assert tel["stream_seconds"] == spans["partition.phase1"]["s"]
    # the chunk's spans lie inside the streaming pass
    inner = sum(v["s"] for k, v in spans.items() if k != "partition.phase1")
    assert inner <= spans["partition.phase1"]["s"]
    # every neighbour slot is sent once; a launch pads rows and widths
    assert tel["score_slots_true"] == 2 * graph.num_edges
    assert tel["score_slots_padded"] >= tel["score_slots_true"]
    assert tel["score_slots_padded"] % kernel_tiling(256, 8)[2] == 0


def test_host_path_names_its_histogram(graph):
    tel: dict = {}
    fennel_partition(graph, 4, seed=3, use_pallas=False, telemetry=tel)
    assert "score.bincount" in tel["spans"] and not SCORE & set(tel["spans"])
    assert tel["score_slots_true"] == tel["score_slots_padded"] == 0


def test_cuttana_parallel_spans_and_profile(graph):
    tel: dict = {}
    partition_parallel(graph, 4, num_shards=4, max_workers=2, chunk=256, seed=3,
                       interpret=True, use_refinement=True, telemetry=tel)
    spans = tel["spans"]
    assert {
        "partition.phase1", "partition.phase2", "engine.ingest", "engine.prep",
        "engine.place", "engine.exchange", "engine.merge",
    } | SCORE <= set(spans)
    prof = tel["profile"]
    # merge_s keeps its interval: submit, notification fan-out, final flush
    assert prof["merge_s"] == round(spans["engine.merge"]["s"], 6)
    assert prof["place_s"] == round(spans["engine.place"]["s"], 6)
    assert prof["prep_s"] == round(spans["engine.prep"]["s"], 6)
    assert prof["score_s"] == round(
        sum(spans[n]["s"] for n in ("score.pack", "score.launch", "score.hubs") if n in spans), 6
    )
    assert spans["score.launch"]["n"] == spans["engine.place"]["n"] == tel["kernel_calls"]
    assert tel["phase1_seconds"] == spans["partition.phase1"]["s"]
    assert tel["phase2_seconds"] == spans["partition.phase2"]["s"]
    assert tel["score_slots_padded"] >= tel["score_slots_true"] == 2 * graph.num_edges
    assert set(prof) == {
        "workers", "supersteps", "prep_s", "score_s", "place_s", "exchange_s",
        "merge_s", "per_superstep",
    }


def test_spans_reach_the_partition_result(graph):
    res = partition(graph, PartitionSpec(algo="cuttana", k=4, seed=1))
    assert res.timings["phase1_seconds"] == res.telemetry["spans"]["partition.phase1"]["s"]
    assert "engine.place" in res.telemetry["spans"]


REFINE = ("refine.coarsen", "refine.init", "refine.best", "refine.apply")


@pytest.mark.parametrize("algo", ["cuttana", "cuttana-parallel"])
def test_phase2_names_its_parts(graph, algo):
    res = partition(graph, PartitionSpec(algo=algo, k=4, seed=1, order="random"))
    spans, moves = res.telemetry["spans"], res.telemetry["refine_moves"]
    assert moves > 0
    assert spans["refine.coarsen"]["n"] == spans["refine.init"]["n"] == 1
    # one pick per trade, and the last pick that finds none
    assert spans["refine.best"]["n"] == moves + 1
    assert spans["refine.apply"]["n"] == moves
    assert sum(spans[n]["s"] for n in REFINE) <= spans["partition.phase2"]["s"]
    assert res.timings["phase2_seconds"] == spans["partition.phase2"]["s"]
    off = partition(graph, PartitionSpec(algo=algo, k=4, seed=1, order="random",
                                         params={"use_refinement": False}))
    assert not set(REFINE) & set(off.telemetry["spans"])


def test_simulated_analytics_reports_compile_apart(graph):
    res = partition(graph, PartitionSpec(algo="fennel", k=2, seed=1))
    out = res.analytics(program="pagerank", iters=3, mode="simulated")
    assert out["compile_s"] > 0.0 and out["seconds"] > 0.0


def _scoped_ops(hlo: str) -> dict[str, set[str]]:
    return {s: hlo_ops_from(hlo, s) for s in ("vp.gather", "vp.reduce", "scatter")}


def test_vertex_program_step_names_its_phases(graph):
    k = 2
    lg = localize(graph, fennel_partition(graph, k, seed=0), k)
    eng = GraphEngine(lg, pagerank_program())
    arrays = [jax.numpy.asarray(a) for a in eng.graph_arrays()]
    state = jax.numpy.asarray(eng.program.init_state(lg, eng.ctx))
    sim = eng._sim_step.lower(state, *arrays).compile().as_text()
    lg1 = localize(graph, np.zeros(graph.num_vertices, dtype=np.int64), 1)
    mesh = Mesh(np.array(jax.devices()[:1]), ("w",))
    sharded = GraphEngine(lg1, pagerank_program()).lower_sharded(mesh, iters=2).compile().as_text()
    for hlo in (sim, sharded):
        ops = _scoped_ops(hlo)
        assert ops["vp.gather"] and ops["vp.reduce"]
        assert not ops["vp.gather"] & ops["vp.reduce"]
        # the reduce is the step's only scatter: a reader of scatter ops and
        # one of the reduce scope find the same time. The scatter's
        # reduction computation keeps unscoped parameters, which never run
        # as ops of their own.
        assert ops["scatter"] & ops["vp.reduce"]
        for name in ops["scatter"] - ops["vp.reduce"]:
            assert re.search(rf"%{re.escape(name)} = \S+ parameter\(", hlo), name
