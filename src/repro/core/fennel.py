"""FENNEL streaming vertex partitioner (Tsourakakis et al., WSDM'14).

This is the paper's primary baseline *and* the scoring core CUTTANA builds on
(paper Eq. 7). ``hybrid=True`` + ``balance_mode="edge"`` reproduces the
edge-balanced variant the paper added to FENNEL for its RQ2 study.

Phase-1 runs through :class:`repro.core.engine.StreamEngine` (chunked
kernel-backed scoring, bit-identical to the seed per-vertex loop kept in
:mod:`repro.core.legacy`).
"""
from __future__ import annotations

import numpy as np

from repro.core.base import FennelParams, PartitionState, finalize
from repro.core.engine import EngineConfig, FennelScorer, ImmediatePolicy, StreamEngine
from repro.core.profile import SpanRecorder
from repro.graph.csr import CSRGraph


def partition(
    graph: CSRGraph,
    k: int,
    epsilon: float = 0.05,
    balance_mode: str = "vertex",
    params: FennelParams | None = None,
    order: str = "natural",
    seed: int = 0,
    chunk: int = 512,
    use_pallas: bool | None = None,
    interpret: bool = False,
    prefetch: str = "auto",
    telemetry: dict | None = None,
) -> np.ndarray:
    params = params or FennelParams()
    state = PartitionState.create(graph, k, epsilon, balance_mode, seed)
    spans = SpanRecorder()
    with spans.span("partition.phase1"):
        engine = StreamEngine(
            graph,
            state,
            FennelScorer(graph, k, params, balance_mode),
            ImmediatePolicy(),
            order=order,
            seed=seed,
            config=EngineConfig(
                chunk=chunk, use_pallas=use_pallas, interpret=interpret,
                prefetch=prefetch,
            ),
            spans=spans,
        )
        engine.run()
    if telemetry is not None:
        telemetry.update(engine.telemetry)
        telemetry["stream_seconds"] = spans.seconds["partition.phase1"]
        telemetry["spans"] = spans.to_dict()
    return finalize(state)
