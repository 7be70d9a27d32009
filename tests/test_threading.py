"""Threaded superstep determinism + autotune tests.

The parallel engine's contract is that the worker count NEVER changes the
assignment: shard tasks read frozen snapshots and write disjoint output
slices, so the merged superstep result is scheduling-independent. These
tests pin bit-parity for ``max_workers`` in {1, 2, 8} at fixed S across all
four stream orders and both parallel algorithms, and use the executor's
``JITTER`` hook to prove parity survives adversarial scheduling (a seeded
race on the merge reduction), not just the scheduler we happened to get.
"""
import json
import random

import numpy as np
import pytest

from repro.core import autotune, executor
from repro.core.parallel import fennel_parallel, partition_parallel
from repro.graph import rmat_graph

ORDERS = ("natural", "random", "bfs", "dfs")
ALGOS = {"cuttana-parallel": partition_parallel, "fennel-parallel": fennel_parallel}


@pytest.fixture(scope="module")
def graph():
    return rmat_graph(2000, avg_degree=8, seed=7)


# ------------------------------------------------------ worker-count parity
@pytest.mark.parametrize("algo", sorted(ALGOS))
@pytest.mark.parametrize("order", ORDERS)
def test_bit_parity_across_worker_counts(graph, algo, order):
    fn = ALGOS[algo]
    ref = fn(graph, 4, num_shards=4, max_workers=1, order=order, seed=0)
    for workers in (2, 8):
        got = fn(graph, 4, num_shards=4, max_workers=workers, order=order, seed=0)
        np.testing.assert_array_equal(
            got, ref, err_msg=f"{algo} order={order} max_workers={workers}"
        )


def test_parity_with_seeded_scheduling_jitter(graph):
    """Seeded-race regression on the merge reduction: random per-task sleeps
    shuffle shard completion order; the vectorised merge must still commute."""
    ref = {
        a: fn(graph, 4, num_shards=4, max_workers=1, seed=0)
        for a, fn in ALGOS.items()
    }
    executor.JITTER = random.Random(0xC0FFEE)
    try:
        for a, fn in ALGOS.items():
            got = fn(graph, 4, num_shards=4, max_workers=8, seed=0)
            np.testing.assert_array_equal(got, ref[a], err_msg=a)
    finally:
        executor.JITTER = None


# --------------------------------------------------------- profile telemetry
@pytest.mark.parametrize("algo", sorted(ALGOS))
def test_profile_telemetry(graph, algo):
    tel: dict = {}
    ALGOS[algo](
        graph, 4, num_shards=4, max_workers=2, chunk=128, seed=0, telemetry=tel
    )
    prof = tel["profile"]
    assert prof["workers"] == tel["max_workers"] == 2
    # the profiler records supersteps that place vertices; the stream-level
    # count also includes empty drain rounds of the buffered policy
    assert 1 <= prof["supersteps"] <= tel["supersteps"]
    for phase in ("prep", "score", "place", "exchange", "merge"):
        assert prof[f"{phase}_s"] >= 0.0
    # place_s is the main thread's wall of the shard-task fan-out and join
    assert prof["place_s"] == pytest.approx(tel["spans"]["engine.place"]["s"], abs=1e-6)
    assert prof["place_s"] <= tel["spans"]["partition.phase1"]["s"]
    rows = prof["per_superstep"]
    assert 1 <= len(rows) <= 64
    assert all(set(r) >= {"score", "place", "exchange", "merge"} for r in rows)
    # per-superstep rows sum (up to the cap) into the totals
    if prof["supersteps"] <= 64:
        total = sum(r["score"] for r in rows)
        assert total == pytest.approx(prof["score_s"], abs=1e-4)


def test_profile_serializes(graph):
    tel: dict = {}
    fennel_parallel(graph, 4, num_shards=2, telemetry=tel)
    json.dumps(tel["profile"])  # artifact-ready: plain floats/ints only


# ------------------------------------------------------------------ autotune
def test_choose_num_shards_knee():
    rows = [
        {"num_shards": 1, "stream_seconds": 1.00, "boundary_conflicts": 0},
        {"num_shards": 2, "stream_seconds": 0.60, "boundary_conflicts": 40},
        {"num_shards": 4, "stream_seconds": 0.52, "boundary_conflicts": 90},
        {"num_shards": 8, "stream_seconds": 0.50, "boundary_conflicts": 400},
    ]
    # 4 and 8 are within 10% of best (0.50); 2 is not; fewest conflicts wins
    assert autotune.choose_num_shards(rows) == 4
    assert autotune.choose_num_shards([]) is None
    assert autotune.choose_num_shards([{"num_shards": 2}]) is None  # no latency


def test_choose_chunk():
    rows = [
        {"chunk": 256, "stream_seconds": 0.40},
        {"chunk": 512, "stream_seconds": 0.30},
        {"chunk": 1024, "stream_seconds": 0.30},
    ]
    assert autotune.choose_chunk(rows) == 512  # tie -> smaller chunk
    assert autotune.choose_chunk([]) is None


def test_build_and_resolve_artifact(tmp_path, monkeypatch):
    art = autotune.build_artifact(
        {
            "cuttana-parallel": [
                {"num_shards": 1, "stream_seconds": 2.0, "boundary_conflicts": 0},
                {"num_shards": 4, "stream_seconds": 1.0, "boundary_conflicts": 10},
            ],
            "fennel-parallel": [
                {"num_shards": 1, "stream_seconds": 0.2, "boundary_conflicts": 0},
                {"num_shards": 2, "stream_seconds": 0.1, "boundary_conflicts": 5},
            ],
        },
        chunk_rows=[{"chunk": 256, "stream_seconds": 0.1}],
    )
    assert art["chosen"]["cuttana-parallel"]["num_shards"] == 4
    assert art["chosen"]["fennel-parallel"]["num_shards"] == 2
    assert art["chosen"]["default"]["num_shards"] == 2  # smallest knee
    p = tmp_path / "TUNING_partition.json"
    p.write_text(json.dumps(art))
    monkeypatch.setenv(autotune.ENV_PATH, str(p))
    t = autotune.resolve(0, 0, algo="cuttana-parallel")
    assert (t.num_shards, t.chunk) == (4, 256)
    assert t.source == f"artifact:{p}"
    # unknown algo falls back to the artifact default
    assert autotune.resolve(0, 512, algo="mystery").num_shards == 2
    # explicit knobs pass through untouched
    assert autotune.resolve(3, 64, algo="cuttana-parallel") == autotune.Tuning(
        3, 64, "explicit"
    )


def test_resolve_heuristic_fallback(tmp_path):
    # an explicit path overrides the whole search chain (env, cwd, repo
    # root - the committed repo-root artifact must not shadow this test)
    missing = tmp_path / "missing.json"
    t = autotune.resolve(
        0, 0, algo="fennel-parallel", num_vertices=100_000, path=missing
    )
    assert t.source == "heuristic"
    assert 1 <= t.num_shards <= 8
    assert t.chunk == 512
    # tiny graphs degrade to the sequential engine
    tiny = autotune.resolve(
        0, 512, algo="fennel-parallel", num_vertices=500, path=missing
    )
    assert tiny.num_shards == 1
    with pytest.raises(ValueError, match="num_shards"):
        autotune.resolve(-1, 512, algo="fennel-parallel")
    with pytest.raises(ValueError, match="chunk"):
        autotune.resolve(2, -5, algo="fennel-parallel")


# ------------------------------------------------------- executor primitives
def test_resolve_workers():
    assert executor.resolve_workers(1, 8) == 1
    assert executor.resolve_workers(16, 4) == 4  # clamped to S
    assert executor.resolve_workers(0, 4) >= 1  # auto
    with pytest.raises(ValueError, match="max_workers"):
        executor.resolve_workers(-2, 4)


def test_shard_pool_inline_and_chained():
    pool = executor.ShardPool(1, 4)
    assert pool.workers == 1 and pool._ex is None
    assert pool.submit(lambda a, b: a + b, 2, 3).result() == 5
    with pytest.raises(RuntimeError, match="boom"):
        pool.submit(_raise).result()
    order: list[int] = []
    f = None
    for i in range(4):
        f = pool.submit_after(f, order.append, i)
    f.result()
    assert order == [0, 1, 2, 3]
    pool.shutdown()


def test_shard_pool_chain_is_fifo_under_threads():
    pool = executor.ShardPool(2, 4)
    assert pool.workers == 2
    executor.JITTER = random.Random(42)
    try:
        order: list[int] = []
        f = None
        for i in range(32):
            f = pool.submit_after(f, order.append, i)
        f.result()
        assert order == list(range(32))
    finally:
        executor.JITTER = None
        pool.shutdown()


def _raise():
    raise RuntimeError("boom")
