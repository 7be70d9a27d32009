"""CUTTANA: prioritized buffered streaming + coarsened refinement (paper §III).

Phase 1 (Algorithm 1): stream vertices; vertices with degree >= D_max are
placed immediately (their premature-assignment risk is low, Thm. 1); the rest
enter a bounded priority buffer ordered by buffer score (Eq. 6). On overflow
the best-scored vertex is evicted and placed with the FENNEL/PowerLyra hybrid
score (Eq. 7). Placement of a vertex bumps the buffer score of its buffered
neighbours; a buffered vertex whose neighbourhood is fully assigned is evicted
immediately. Every placement also picks a *sub-partition* (Def. 2).

Phase 1 runs through :class:`repro.core.engine.StreamEngine`:
``use_buffer=True`` selects :class:`~repro.core.engine.BufferedPolicy`
(Algorithm 1 over the array-backed buffer), ``use_buffer=False`` the chunked
kernel-backed :class:`~repro.core.engine.ImmediatePolicy`. Both are
bit-identical to the seed loop kept in :mod:`repro.core.legacy`.

Phase 2: greedy trades on the coarsened sub-partition graph until maximal
(or early-stopped by ``thresh``), then vertices inherit their sub-partition's
final partition.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.base import FennelParams, PartitionState, finalize
from repro.core.engine import (
    BufferedPolicy,
    EngineConfig,
    FennelScorer,
    ImmediatePolicy,
    StreamEngine,
)
from repro.core.profile import SpanRecorder
from repro.core.refinement import Refiner, build_subpartition_graph
from repro.core.subpartition import SubPartitioner
from repro.graph.csr import CSRGraph


def _phase2_refine(
    graph: CSRGraph,
    subp: SubPartitioner,
    k: int,
    epsilon: float,
    balance_mode: str,
    thresh: float,
    max_moves: int | None = None,
    spans: SpanRecorder | None = None,
):
    """Merge + coarsen + refine (paper §III-B): build the sub-partition
    graph from phase-1's sub-assignments and run greedy trades. Shared by
    ``cuttana``, ``cuttana-batched``, ``cuttana-parallel`` (where it is the
    pass that reconciles shard-boundary vertices), and :func:`refine_any`.
    Records the spans ``refine.*`` in ``spans``.

    Returns ``(part, sub_part, moves, cut_improvement)``.
    """
    spans = spans or SpanRecorder()
    with spans.span("refine.coarsen"):
        w = build_subpartition_graph(graph, subp.sub_of, subp.kp)
    sub_part = np.repeat(np.arange(k, dtype=np.int64), subp.s)
    if balance_mode == "edge":
        size = subp.sub_e_counts.copy()
        total = float(graph.indices.shape[0])
    else:
        size = subp.sub_v_counts.copy()
        total = float(graph.num_vertices)
    with spans.span("refine.init"):
        refiner = Refiner(w, sub_part, size, k, epsilon, total_mass=total)
    stats = refiner.refine(thresh=thresh, max_moves=max_moves, spans=spans)
    sub_part = refiner.sub_part.copy()
    part = sub_part[subp.sub_of].astype(np.int32)
    return part, sub_part, stats.moves, stats.cut_improvement


@dataclasses.dataclass
class CuttanaResult:
    """Compat container for ``return_detail=True`` callers.

    Deprecated: the canonical surface is :func:`repro.api.partition`, which
    folds these fields into ``PartitionResult.telemetry`` / ``.timings`` so
    every algorithm returns one uniform type.
    """

    part: np.ndarray
    sub_of: np.ndarray
    sub_part: np.ndarray  # final partition of each sub-partition
    refine_moves: int
    refine_improvement: float
    phase1_seconds: float
    phase2_seconds: float


def partition(
    graph: CSRGraph,
    k: int,
    epsilon: float = 0.05,
    balance_mode: str = "edge",
    d_max: int = 1000,
    max_qsize: int | None = None,
    theta: float = 1.0,
    subparts_per_partition: int | None = None,
    use_buffer: bool = True,
    use_refinement: bool = True,
    thresh: float = 0.0,
    max_moves: int | None = None,
    fennel_params: FennelParams | None = None,
    order: str = "natural",
    seed: int = 0,
    return_detail: bool = False,
    chunk: int = 512,
    use_pallas: bool | None = None,
    interpret: bool = False,
    prefetch: str = "auto",
    strategy: str = "eq6",
    telemetry: dict | None = None,
):
    """Full CUTTANA partitioner. Ablations: ``use_buffer=False`` /
    ``use_refinement=False`` reproduce the paper's Table III rows
    (both off == plain FENNEL with Eq. 7 scoring).

    ``strategy`` selects the buffer-eviction priority
    (:mod:`repro.core.priority`); the default ``"eq6"`` is the paper's
    Eq. 6 and bit-identical to the pre-strategy-layer engine.

    ``telemetry`` (if given) receives engine counters, phase wall times, and
    refinement stats; ``return_detail=True`` is the compat flag that instead
    returns the legacy :class:`CuttanaResult`."""
    n = graph.num_vertices
    if max_qsize is None:
        max_qsize = max(1024, n // 10)  # paper: 1e6 for 10^7..10^8-vertex graphs
    if subparts_per_partition is None:
        # paper: K'/K = 4096 for big graphs; scale down for small ones so that
        # sub-partitions still hold >= ~8 vertices on average.
        subparts_per_partition = int(max(8, min(4096, n // (8 * k))))

    params = fennel_params or FennelParams(hybrid=(balance_mode == "edge"))
    state = PartitionState.create(graph, k, epsilon, balance_mode, seed)
    subp = SubPartitioner(
        graph,
        k,
        subparts_per_partition,
        epsilon=max(epsilon, 0.10),
        balance_mode=balance_mode,
        seed=seed,
    )
    policy = (
        BufferedPolicy(max_qsize, d_max, theta, strategy=strategy)
        if use_buffer
        else ImmediatePolicy()
    )
    # phase 1 includes engine construction: StreamEngine computes
    # stream_order there, which the seed loop counted inside phase 1
    spans = SpanRecorder()
    with spans.span("partition.phase1"):
        engine = StreamEngine(
            graph,
            state,
            FennelScorer(graph, k, params, balance_mode),
            policy,
            subpartitioner=subp,
            order=order,
            seed=seed,
            config=EngineConfig(
                chunk=chunk, use_pallas=use_pallas, interpret=interpret,
                prefetch=prefetch,
            ),
            spans=spans,
        )
        engine.run()

    part = finalize(state)
    sub_of = subp.sub_of.copy()
    kp = subp.kp
    sub_part = np.repeat(np.arange(k, dtype=np.int64), subp.s)

    moves, improvement = 0, 0.0
    with spans.span("partition.phase2"):
        if use_refinement and k > 1:
            part, sub_part, moves, improvement = _phase2_refine(
                graph, subp, k, epsilon, balance_mode, thresh, max_moves, spans
            )
    phase1_s = spans.seconds["partition.phase1"]
    phase2_s = spans.seconds["partition.phase2"]

    if telemetry is not None:
        telemetry.update(engine.telemetry)
        telemetry.update(
            phase1_seconds=phase1_s,
            phase2_seconds=phase2_s,
            refine_moves=moves,
            refine_improvement=improvement,
            subpartitions=int(kp),
            spans=spans.to_dict(),
        )
    if return_detail:
        return CuttanaResult(
            part=part,
            sub_of=sub_of,
            sub_part=sub_part,
            refine_moves=moves,
            refine_improvement=improvement,
            phase1_seconds=phase1_s,
            phase2_seconds=phase2_s,
        )
    return part


def partition_buffcut(
    graph: CSRGraph,
    k: int,
    epsilon: float = 0.05,
    balance_mode: str = "edge",
    d_max: int = 1000,
    strategy: str = "gain",
    max_qsize: int | None = None,
    theta: float = 1.0,
    subparts_per_partition: int | None = None,
    use_refinement: bool = True,
    thresh: float = 0.0,
    max_moves: int | None = None,
    order: str = "natural",
    seed: int = 0,
    chunk: int = 512,
    prefetch: str = "auto",
    telemetry: dict | None = None,
) -> np.ndarray:
    """``cuttana-buffcut``: CUTTANA's engine with a prioritized (non-Eq.-6)
    buffer-eviction strategy - ``"gain"`` (default) or ``"completeness"``.
    The registry/spec layer rejects ``strategy="eq6"`` here (that spec
    spells ``algo="cuttana"``); this entry point exists so the variant's
    own defaults are the callable's defaults."""
    return partition(
        graph, k, epsilon=epsilon, balance_mode=balance_mode, d_max=d_max,
        max_qsize=max_qsize, theta=theta,
        subparts_per_partition=subparts_per_partition,
        use_refinement=use_refinement, thresh=thresh, max_moves=max_moves,
        order=order, seed=seed, chunk=chunk, prefetch=prefetch,
        strategy=strategy, telemetry=telemetry,
    )


def refine_any(
    graph: CSRGraph,
    part: np.ndarray,
    k: int,
    epsilon: float = 0.05,
    balance_mode: str = "edge",
    subparts_per_partition: int | None = None,
    thresh: float = 0.0,
    seed: int = 0,
) -> np.ndarray:
    """Paper §III-B: refinement applies to *any* partitioner's output.

    Builds sub-partitions by re-streaming vertices inside their fixed
    partition assignment, then runs phase-2 trades.
    """
    n = graph.num_vertices
    if subparts_per_partition is None:
        subparts_per_partition = int(max(8, min(4096, n // (8 * k))))
    subp = SubPartitioner(
        graph, k, subparts_per_partition, balance_mode=balance_mode, seed=seed
    )
    indptr, indices = graph.indptr, graph.indices
    for v in range(n):
        nbrs = indices[indptr[v] : indptr[v + 1]]
        subp.assign(v, int(part[v]), nbrs, nbrs.size)
    refined, _, _, _ = _phase2_refine(graph, subp, k, epsilon, balance_mode, thresh)
    return refined
