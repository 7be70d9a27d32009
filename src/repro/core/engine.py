"""Unified batched streaming engine: one scoring core for every partitioner.

Every streaming vertex partitioner in this repo is "a stream loop + a scoring
rule + a placement discipline" (paper §III-A; cf. Faraj & Schulz's buffered
streaming framing). :class:`StreamEngine` factors that shape into a single
hot path:

* the stream is consumed in chunks of ``C`` vertices; all ``C x K``
  assigned-neighbour histograms for a chunk come from ONE call to the fused
  :mod:`repro.kernels.partition_score` kernel (Pallas on TPU, jnp reference
  elsewhere) instead of a per-vertex ``bincount``;
* a light host loop applies assignments in stream order. In ``exact`` mode
  the chunk histograms are incrementally corrected as in-chunk neighbours
  get assigned, so results are *bit-identical* to the classic per-vertex
  loops preserved in :mod:`repro.core.legacy` (parity-tested in
  ``tests/test_engine.py``). With ``exact=False`` histograms are left
  one-chunk stale (bulk-synchronous relaxation) and vertices above
  ``sample_cap`` neighbours are scored on a uniform sample with the
  histogram rescaled - the ``cuttana-batched`` speed/quality trade;
* scoring rules are pluggable :class:`Scorer` objects (FENNEL vertex /
  FENNEL-PowerLyra hybrid Eq. 7, LDG) that keep their balance penalty
  incrementally updated instead of recomputing a K-wide ``power`` per
  vertex;
* placement disciplines are pluggable :class:`PlacementPolicy` objects:
  :class:`ImmediatePolicy` (FENNEL / LDG / HeiStream batches / restream
  reassignment) or :class:`BufferedPolicy` - CUTTANA Algorithm 1 with the
  D_max bypass and the complete-eviction cascade, backed by the array-based
  :class:`~repro.core.buffer.PriorityBuffer`;
* the *sharded* policies (:class:`ShardedImmediatePolicy`,
  :class:`ShardedBufferedPolicy`) run S interleaved shard frontiers per
  bulk-synchronous superstep - one packed
  :func:`~repro.kernels.partition_score.fennel_scores_sharded` kernel call
  scores every shard's candidates, shard-local buffers/load views keep the
  supersteps independent, and the shared :class:`PartitionState` is
  exchanged only at superstep boundaries (the paper's parallel CUTTANA,
  relaxed consistency surfaced as ``boundary_conflicts`` telemetry).
  ``num_shards=1`` delegates to the sequential policies, so it stays
  bit-identical to the classic engine.

Extension points: implement ``Scorer`` for a new scoring rule (e.g. a
weighted-affinity variant) or ``PlacementPolicy`` for a new placement
discipline and wire them into a thin ``partition()`` wrapper - see
``src/repro/core/README.md``.

Out-of-core contract: every graph access in this module goes through the CSR
read surface (``indptr``/``indices`` slicing and fancy indexing, ``degrees``,
``num_vertices``), never through whole-graph materialization - so a
memory-mapped :class:`~repro.graph.external.ExternalCSRGraph` streams through
every policy with assignments bit-identical to the resident path (pinned in
``tests/test_outofcore.py``). Keep it that way: a chunk may gather the pages
it touches, but nothing here may copy ``indices`` wholesale.
"""
from __future__ import annotations

import dataclasses
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Protocol, runtime_checkable

import numpy as np

from repro.core.base import FennelParams, PartitionState
from repro.core.buffer import PriorityBuffer
from repro.core.executor import ShardPool
from repro.core.priority import BufferStats, make_priority
from repro.core.profile import SuperstepProfiler
from repro.core.subpartition import SubPartitioner
from repro.graph.csr import CSRGraph
from repro.graph.prefetch import BatchPrefetcher, PrefetchStats
from repro.graph.stream import ShardedStream, stream_order
from repro.kernels.partition_score.ops import (
    fennel_scores,
    fennel_scores_sharded,
    kernel_active,
    neighbor_histograms_host,
)

# widest dense neighbour axis a kernel call may use in exact mode; rows with
# higher degree are histogrammed exactly on host instead (Thm. 1 hubs are
# rare per chunk, so this bounds memory without sampling)
_EXACT_KERNEL_WIDTH = 1024

__all__ = [
    "Scorer",
    "FennelScorer",
    "LDGScorer",
    "PlacementPolicy",
    "ImmediatePolicy",
    "BufferedPolicy",
    "ShardedImmediatePolicy",
    "ShardedBufferedPolicy",
    "EngineConfig",
    "StreamEngine",
]


# ------------------------------------------------------------------ scorers
@runtime_checkable
class Scorer(Protocol):
    """Per-vertex scoring rule. ``scores`` is called once per placement with
    the vertex's assigned-neighbour histogram; implementations may cache the
    balance penalty and must keep it fresh through ``on_assign`` /
    ``on_unassign`` (every mass mutation the engine makes flows through
    these; if outside code mutates the state - e.g. an FM pass - call
    ``begin`` again)."""

    def begin(self, state: PartitionState) -> None: ...

    def scores(self, state: PartitionState, hist: np.ndarray) -> np.ndarray: ...

    def on_assign(self, state: PartitionState, p: int, deg: int) -> None: ...

    def on_unassign(self, state: PartitionState, p: int, deg: int) -> None: ...


class FennelScorer:
    """FENNEL Eq. 7: ``hist_i - alpha*gamma*size_i^(gamma-1)`` with
    ``size_i = |V_i|`` (vertex mode) or the PowerLyra hybrid mass
    ``(|V_i| + mu*E_i)/2`` (edge mode, ``params.hybrid``). Identical numbers
    to :func:`repro.core.base.make_fennel_score`, but the K-wide penalty is
    cached and only the assigned partition's entry is recomputed per
    placement."""

    def __init__(
        self,
        graph: CSRGraph,
        k: int,
        params: FennelParams | None = None,
        balance_mode: str = "vertex",
    ):
        params = params or FennelParams()
        n = max(graph.num_vertices, 1)
        m = max(graph.num_edges, 1)
        self.alpha = params.alpha_scale * np.sqrt(k) * m / (n**1.5)
        self.gamma = params.gamma
        self.mu = n / max(graph.indices.shape[0], 1)
        self.hybrid = params.hybrid and balance_mode == "edge"
        self._penalty: np.ndarray | None = None
        self._ag = float(self.alpha * self.gamma)
        self._gm1 = self.gamma - 1.0

    def begin(self, state: PartitionState) -> None:
        if self.hybrid:
            size = 0.5 * (state.v_counts + self.mu * state.e_counts)
        else:
            size = state.v_counts
        self._penalty = self.alpha * self.gamma * np.power(
            np.maximum(size, 0.0), self.gamma - 1.0
        )

    def scores(self, state: PartitionState, hist: np.ndarray) -> np.ndarray:
        return hist - self._penalty

    def _update(self, state: PartitionState, p: int) -> None:
        if self.hybrid:
            size = 0.5 * (state.v_counts[p] + self.mu * state.e_counts[p])
        else:
            size = state.v_counts[p]
        self._penalty[p] = self.alpha * self.gamma * np.power(
            np.maximum(size, 0.0), self.gamma - 1.0
        )

    def on_assign(self, state: PartitionState, p: int, deg: int) -> None:
        self._update(state, p)

    def on_unassign(self, state: PartitionState, p: int, deg: int) -> None:
        self._update(state, p)

    # ------------------------------------------------------ affine fast path
    def affine(self, state: PartitionState):
        """scores == hist * mul + add (mul None => 1). See ImmediatePolicy."""
        self.begin(state)
        return None, -self._penalty

    def affine_update(self, v_p: float, e_p: float):
        """New (mul_p, add_p) after partition p's counts became (v_p, e_p).
        Pure-python IEEE doubles: same values as the numpy path bit-for-bit
        (``x ** y`` and ``np.power`` both call libm ``pow``)."""
        if self.hybrid:
            size = 0.5 * (v_p + self.mu * e_p)
        else:
            size = v_p
        if size < 0.0:
            size = 0.0
        return None, -(self._ag * size**self._gm1)

    def affine_arrays(self, v_counts, e_counts):
        """Vectorised :meth:`affine_update`: ``(mul, add)`` for a whole load
        view at once (``mul`` None => 1). Elementwise over any shape, and the
        same libm ``pow`` as the scalar path. Stateless - safe to call from
        concurrent shard tasks."""
        if self.hybrid:
            size = 0.5 * (v_counts + self.mu * e_counts)
        else:
            size = np.asarray(v_counts, dtype=np.float64)
        return None, -(self._ag * np.power(np.maximum(size, 0.0), self._gm1))


class LDGScorer:
    """Linear Deterministic Greedy: ``hist_i * max(1 - size_i/C, 0)`` with a
    tiny negative load term for least-loaded tie-breaking (identical numbers
    to the seed :mod:`repro.core.ldg` loop)."""

    def __init__(self, graph: CSRGraph, k: int, balance_mode: str = "vertex"):
        self.balance_mode = balance_mode
        self._factor: np.ndarray | None = None
        self._cap = 0.0

    def _loads(self, state: PartitionState) -> np.ndarray:
        return state.v_counts if self.balance_mode == "vertex" else state.e_counts

    def begin(self, state: PartitionState) -> None:
        self._cap = (
            state.vertex_capacity
            if self.balance_mode == "vertex"
            else state.edge_capacity
        )
        self._factor = np.maximum(1.0 - self._loads(state) / self._cap, 0.0)

    def scores(self, state: PartitionState, hist: np.ndarray) -> np.ndarray:
        return hist * self._factor - 1e-9 * self._loads(state)

    def _update(self, state: PartitionState, p: int) -> None:
        self._factor[p] = np.maximum(1.0 - self._loads(state)[p] / self._cap, 0.0)

    def on_assign(self, state: PartitionState, p: int, deg: int) -> None:
        self._update(state, p)

    def on_unassign(self, state: PartitionState, p: int, deg: int) -> None:
        self._update(state, p)

    # ------------------------------------------------------ affine fast path
    def affine(self, state: PartitionState):
        self.begin(state)
        return self._factor, -(1e-9 * self._loads(state))

    def affine_update(self, v_p: float, e_p: float):
        lp = v_p if self.balance_mode == "vertex" else e_p
        if self._cap == 0.0:
            # edgeless graph in edge mode: numpy's 0/0 gives nan, which sinks
            # every score and triggers the least-loaded fallback; plain python
            # would raise instead, so reproduce the nan path explicitly
            return float("nan"), -(1e-9 * lp)
        f = 1.0 - lp / self._cap
        if f < 0.0:
            f = 0.0
        return f, -(1e-9 * lp)

    def affine_arrays(self, v_counts, e_counts):
        """Vectorised :meth:`affine_update` (see FennelScorer): stateless,
        elementwise, including the nan path for edgeless edge-mode graphs."""
        loads = np.asarray(
            v_counts if self.balance_mode == "vertex" else e_counts,
            dtype=np.float64,
        )
        if self._cap == 0.0:
            return np.full_like(loads, np.nan), -(1e-9 * loads)
        return np.maximum(1.0 - loads / self._cap, 0.0), -(1e-9 * loads)


# ------------------------------------------------------------------- config
@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Chunking/kernel knobs for the scoring core.

    ``exact=True``: in-chunk histogram corrections, no sampling - results
    match the sequential per-vertex loops bit-for-bit. ``exact=False``:
    histograms stale by one chunk, degree-capped sampling above
    ``sample_cap`` (only honoured in this mode).

    ``max_workers`` threads run the sharded policies' per-shard superstep
    tasks (``None``/``0`` = auto: ``min(num_shards, cpu_count)``); results
    are bit-identical for every worker count because shard tasks write
    disjoint buffers. ``wave`` is the vectorised placement width inside a
    shard task: candidates are scored ``wave`` at a time against a frozen
    penalty/histogram view, refreshed exactly between waves.

    ``prefetch`` controls the decode-ahead pipeline for out-of-core graphs:
    ``"auto"`` overlaps chunk/superstep decode with scoring only when the
    graph is memory-mapped, ``"on"`` forces it, ``"off"`` disables it AND the
    sharded ahead-of-time frontier expansion - the true synchronous baseline
    the out-of-core benchmarks compare against. The prefetcher consumes the
    identical fetch results in the identical order, so assignments are
    bit-identical across all three modes."""

    chunk: int = 512
    sample_cap: int = 512
    exact: bool = True
    use_pallas: bool | None = None
    interpret: bool = False
    max_workers: int | None = None
    wave: int = 128
    prefetch: str = "auto"


def _resolve_prefetch(mode: str, graph) -> tuple[bool, bool]:
    """``(decode_ahead, ahead_prep)`` for a prefetch mode: ``"on"`` forces
    the decode pipeline, ``"off"`` disables it and the sharded ahead-of-time
    frontier expansion, ``"auto"`` enables the pipeline only for mapped
    graphs (anything exposing ``backing == "mapped"``) and leaves ahead-prep
    on - resident runs keep their existing overlap."""
    if mode not in ("auto", "on", "off"):
        raise ValueError(f'prefetch must be "auto", "on" or "off", got {mode!r}')
    if mode == "on":
        return True, True
    if mode == "off":
        return False, False
    return getattr(graph, "backing", "resident") == "mapped", True


# ----------------------------------------------------------------- policies
@runtime_checkable
class PlacementPolicy(Protocol):
    def run(self, engine: "StreamEngine") -> None: ...


class ImmediatePolicy:
    """Place every stream vertex as soon as it arrives (FENNEL/LDG/HeiStream
    greedy phase). With ``reassign=True`` the stream *re-visits* already
    assigned vertices (restreaming): each vertex is pulled out of its current
    partition, rescored against the full assignment, and may move."""

    def __init__(self, reassign: bool = False):
        self.reassign = reassign

    def run(self, eng: "StreamEngine") -> None:
        if self.reassign and eng.subp is not None:
            # SubPartitioner has no unassign: re-adding an already-placed
            # vertex would double-count its sub-partition mass
            raise ValueError("reassign mode does not support a subpartitioner")
        if hasattr(eng.scorer, "affine"):
            self._run_affine(eng)
        else:
            self._run_generic(eng)

    # ------------------------------------------------- generic scorer path
    def _run_generic(self, eng: "StreamEngine") -> None:
        """Protocol-only path for custom scorers: per-vertex numpy scoring."""
        state = eng.state
        scorer = eng.scorer
        subp = eng.subp
        part_of = state.part_of
        v_counts, e_counts = state.v_counts, state.e_counts
        reassign = self.reassign
        for batch, degs, expanded in _iter_chunk_expansions(eng):
            nbr_views = _chunk_views(expanded[2], degs)
            hist, corr = eng.chunk_histograms(batch, degs, nbr_views, expanded)
            bl = batch.tolist()
            dl = degs.tolist()
            for i in range(len(bl)):
                v, deg = bl[i], dl[i]
                if reassign:
                    cur = int(part_of[v])
                    v_counts[cur] -= 1
                    e_counts[cur] -= deg
                    scorer.on_unassign(state, cur, deg)
                s = scorer.scores(state, hist[i])
                allowed = ~state.would_overflow(deg)
                if reassign:
                    allowed[cur] = True
                p = state.argmax_tiebreak(s, allowed)
                if reassign:
                    part_of[v] = p
                    v_counts[p] += 1
                    e_counts[p] += deg
                    scorer.on_assign(state, p, deg)
                    if corr is not None and p != cur:
                        dst, starts = corr
                        for j in dst[starts[i] : starts[i + 1]]:
                            hist[j, cur] -= 1.0
                            hist[j, p] += 1.0
                else:
                    state.assign(v, p, deg)
                    scorer.on_assign(state, p, deg)
                    if subp is not None:
                        subp.assign(v, p, nbr_views[i], deg)
                    if corr is not None:
                        dst, starts = corr
                        for j in dst[starts[i] : starts[i + 1]]:
                            hist[j, p] += 1.0
            if eng.on_chunk_end is not None:
                eng.on_chunk_end(eng, batch, nbr_views)

    # ------------------------------------------------- affine scorer path
    def _run_affine(self, eng: "StreamEngine") -> None:
        """Fast host loop for scorers exposing the affine contract
        ``scores == hist * mul + add``. The K-wide selection runs in plain
        Python over lists (for K <= a few hundred, numpy dispatch overhead
        dwarfs the arithmetic); canonical numpy state is written back once
        per chunk. Every operation is the same IEEE double computation as
        the generic path, so results stay bit-identical - parity-tested
        against :mod:`repro.core.legacy`."""
        state = eng.state
        scorer = eng.scorer
        subp = eng.subp
        part_of = state.part_of
        v_counts, e_counts = state.v_counts, state.e_counts
        reassign = self.reassign
        k = state.k
        krange = range(k)
        rng = state.rng
        vertex_mode = state.balance_mode == "vertex"
        cap = state.vertex_capacity if vertex_mode else state.edge_capacity
        neg_inf = float("-inf")
        sc = [neg_inf] * k  # per-vertex score buffer (neg_inf == disallowed)
        for batch, degs, expanded in _iter_chunk_expansions(eng):
            nbr_views = (
                _chunk_views(expanded[2], degs)
                if subp is not None or eng.on_chunk_end is not None
                else None
            )
            hist, corr = eng.chunk_histograms(batch, degs, nbr_views, expanded)
            H = hist.tolist()
            bl = batch.tolist()
            dl = degs.tolist()
            assigned = [0] * len(bl)
            # python mirrors of the balance state; canonical arrays are
            # flushed at chunk end (before any on_chunk_end hook), so hooks
            # may mutate state freely - affine() re-syncs next chunk
            mul_a, add_a = scorer.affine(state)
            mul = None if mul_a is None else mul_a.tolist()
            add = add_a.tolist()
            v_list = v_counts.tolist()
            e_list = e_counts.tolist()
            load = v_list if vertex_mode else e_list
            for i in range(len(bl)):
                v, deg = bl[i], dl[i]
                cur = -1
                if reassign:
                    cur = int(part_of[v])  # pre-pass value: writes deferred
                    v_list[cur] -= 1
                    e_list[cur] -= deg
                    u = scorer.affine_update(v_list[cur], e_list[cur])
                    if mul is not None:
                        mul[cur] = u[0]
                    add[cur] = u[1]
                row = H[i]
                inc = 1 if vertex_mode else deg
                best = neg_inf
                if mul is None:
                    for p in krange:
                        if load[p] + inc > cap and p != cur:
                            sc[p] = neg_inf
                            continue
                        s = row[p] + add[p]
                        sc[p] = s
                        if s > best:
                            best = s
                else:
                    for p in krange:
                        if load[p] + inc > cap and p != cur:
                            sc[p] = neg_inf
                            continue
                        s = row[p] * mul[p] + add[p]
                        sc[p] = s
                        if s > best:
                            best = s
                if best == neg_inf:
                    # every partition at capacity - least-loaded fallback,
                    # same rule as PartitionState.argmax_tiebreak
                    p = load.index(min(load))
                else:
                    thr = best - 1e-12
                    ties = [p for p in krange if sc[p] >= thr]
                    p = ties[0] if len(ties) == 1 else int(ties[rng.integers(len(ties))])
                assigned[i] = p
                v_list[p] += 1
                e_list[p] += deg
                u = scorer.affine_update(v_list[p], e_list[p])
                if mul is not None:
                    mul[p] = u[0]
                add[p] = u[1]
                if subp is not None:
                    subp.assign(v, p, nbr_views[i], deg)
                if corr is not None and p != cur:
                    dst, starts = corr
                    if reassign:
                        for j in dst[starts[i] : starts[i + 1]]:
                            rj = H[j]
                            rj[cur] -= 1.0
                            rj[p] += 1.0
                    else:
                        for j in dst[starts[i] : starts[i + 1]]:
                            H[j][p] += 1.0
            # flush deferred writes back into the canonical numpy state
            part_of[batch] = assigned
            v_counts[:] = v_list
            e_counts[:] = e_list
            if eng.on_chunk_end is not None:
                eng.on_chunk_end(eng, batch, nbr_views)


class BufferedPolicy:
    """CUTTANA Algorithm 1: vertices with degree >= D_max are placed
    immediately (Thm. 1); the rest enter the bounded priority buffer; on
    overflow the best-scored vertex is evicted and placed; placements bump
    buffered neighbours (vectorised through ``notify_many``) and fully-known
    vertices cascade out immediately.

    ``strategy`` selects the eviction priority (:mod:`repro.core.priority`):
    ``"eq6"`` (paper default, bit-identical to the pre-strategy engine),
    ``"completeness"``, or ``"gain"``."""

    def __init__(
        self,
        max_qsize: int,
        d_max: int,
        theta: float = 1.0,
        strategy: str = "eq6",
    ):
        self.max_qsize = int(max_qsize)
        self.priority_factory = lambda: make_priority(strategy, d_max, theta)
        prio = self.priority_factory()  # validates name eagerly
        self.strategy = prio.name
        self.d_max = prio.d_max
        self.theta = prio.theta
        self.buffer: PriorityBuffer | None = None

    def run(self, eng: "StreamEngine") -> None:
        state = eng.state
        prio = self.priority_factory()
        buf = PriorityBuffer(self.max_qsize, graph=eng.graph, priority=prio)
        self.buffer = buf
        part_of = state.part_of
        d_max = self.d_max
        track = prio.tracks_parts
        stats = BufferStats()

        def cascade(v: int, nbrs: np.ndarray) -> None:
            worklist = [(v, nbrs)]
            while worklist:
                u, un = worklist.pop()
                p = eng.place(u, un)
                for w in buf.notify_many(un, p if track else None):
                    worklist.append((w, buf.remove(w)))

        # admission reads neighbour rows a chunk at a time so the prefetcher
        # can decode chunk t+1 while chunk t's buffer churn runs; the
        # cascade/eviction rows stay data-dependent per-row reads
        for batch, degs, expanded in _iter_chunk_expansions(eng):
            views = _chunk_views(expanded[2], degs)
            for i, v in enumerate(batch.tolist()):
                if part_of[v] != -1:
                    continue  # already placed via complete-eviction cascade
                nbrs = views[i]
                if nbrs.size >= d_max:
                    stats.bypass += 1
                    cascade(v, nbrs)
                    continue
                nbr_parts = part_of[nbrs]
                assigned = int((nbr_parts != -1).sum())
                if assigned == nbrs.size and nbrs.size > 0:
                    cascade(v, nbrs)  # complete already
                    continue
                buf.push(v, nbrs, assigned, nbr_parts if track else None)
                stats.observe_len(len(buf))
                if buf.full:
                    u, un = buf.pop_best()
                    stats.evictions += 1
                    cascade(u, un)
        while len(buf):
            u, un = buf.pop_best()
            stats.drained += 1
            cascade(u, un)
        eng.telemetry.update(stats.to_telemetry(self.strategy))


# ------------------------------------------------------------------ helpers
def _expand_csr_batch(indptr, indices, batch, degs):
    """Flat neighbour expansion of a candidate batch: returns
    ``(rows, idx_in_row, cols)`` where flat position ``j`` is the
    ``idx_in_row[j]``-th neighbour (vertex id ``cols[j]``) of
    ``batch[rows[j]]``. Shared by the sequential chunk path, the superstep
    core, and the sharded buffer's admission scan."""
    rows = np.repeat(np.arange(batch.shape[0], dtype=np.int64), degs)
    offs = np.zeros(batch.shape[0], dtype=np.int64)
    np.cumsum(degs[:-1], out=offs[1:])
    idx_in_row = np.arange(rows.shape[0], dtype=np.int64) - offs[rows]
    cols = indices[np.repeat(indptr[batch], degs) + idx_in_row]
    return rows, idx_in_row, cols


def _chunk_views(cols, degs):
    """Per-row neighbour arrays from a flat chunk expansion - same values as
    slicing ``indices`` row by row, but without re-touching the graph."""
    if degs.shape[0] == 0:
        return []
    return np.split(cols, np.cumsum(degs[:-1]))


def _iter_chunk_expansions(eng: "StreamEngine"):
    """Yield ``(batch, degs, (rows, idx_in_row, cols))`` per stream chunk.

    The fetch touches only the immutable CSR read surface, so when the
    engine's prefetcher is enabled chunk t+1 is expanded (for a compressed
    mapped graph: varint-decoded) on the prefetch thread while chunk t is
    being scored. Inline and prefetched paths run the identical fetch in the
    identical order, so the consumed stream is bit-identical either way.
    """
    indptr, indices = eng.graph.indptr, eng.graph.indices
    ids = eng.ids
    chunk = eng.config.chunk

    def fetch(start):
        batch = ids[start : start + chunk]
        degs = (indptr[batch + 1] - indptr[batch]).astype(np.int64)
        return batch, degs, _expand_csr_batch(indptr, indices, batch, degs)

    starts = range(0, ids.shape[0], chunk)
    if not eng.prefetch_enabled:
        for s in starts:
            yield fetch(s)
        return
    pf = BatchPrefetcher(fetch, starts, stats=eng.prefetch_stats)
    try:
        yield from pf
    finally:
        pf.close()


# --------------------------------------------------------- sharded policies
def _check_num_shards(num_shards) -> int:
    s = int(num_shards)
    if s < 1 or s != num_shards:
        raise ValueError(f"num_shards must be a positive integer, got {num_shards!r}")
    return s


@dataclasses.dataclass
class _ShardPrep:
    """Frontier expansion for one shard's superstep batch.

    Everything here is derived from the immutable CSR plus the batch ids
    alone - no dependence on the evolving assignment - so preps can be (and
    are) computed on worker threads one superstep AHEAD of their use,
    overlapping superstep t's boundary exchange with t+1's expansion.
    """

    batch: np.ndarray  # int64[c] candidate ids (contiguous)
    degs: np.ndarray  # int64[c]
    rows: np.ndarray  # int64[nnz] local row index per neighbour slot
    idx_in_row: np.ndarray  # int64[nnz]
    cols: np.ndarray  # int64[nnz] neighbour ids
    corr_src: np.ndarray  # int64[nc] in-shard same-superstep pairs sorted by
    corr_dst: np.ndarray  # src; dst is placed later than src in shard order


def _prepare_shard(indptr, indices, batch) -> _ShardPrep:
    """Build one shard's :class:`_ShardPrep`. Stateless (the old shared
    scratch-array correction pass would race across threads) and touches the
    graph only through the CSR read surface."""
    batch = np.ascontiguousarray(batch, dtype=np.int64)
    degs = (indptr[batch + 1] - indptr[batch]).astype(np.int64)
    rows, idx_in_row, cols = _expand_csr_batch(indptr, indices, batch, degs)
    if cols.size:
        # in-shard same-superstep correction pairs via sorted membership
        # lookup: position of each neighbour id inside the batch, if any
        order = np.argsort(batch, kind="stable")
        sb = batch[order]
        loc = np.searchsorted(sb, cols)
        np.minimum(loc, sb.size - 1, out=loc)
        cpos = np.where(sb[loc] == cols, order[loc], -1)
        emask = (cpos >= 0) & (cpos < rows)
        src, dst = cpos[emask], rows[emask]
        o = np.argsort(src, kind="stable")
        src, dst = src[o], dst[o]
    else:
        src = dst = np.empty(0, dtype=np.int64)
    return _ShardPrep(batch, degs, rows, idx_in_row, cols, src, dst)


class _SuperstepRunner:
    """Bulk-synchronous superstep core shared by the sharded policies.

    Per superstep, every shard's candidate vertices are scored against the
    *superstep-start snapshot* of the shared :class:`PartitionState`, then
    each shard places its candidates against a local view (snapshot + its
    own deltas, with the remaining per-partition capacity split evenly
    across shards). Assignments and loads are exchanged only at the
    superstep boundary - the paper's relaxed-consistency parallel design.
    Same-shard same-superstep neighbours are corrected exactly between
    placement waves; cross-shard ones are not, and are counted as
    ``boundary_conflicts`` for the merge + coarsen + refine pass to
    reconcile.

    Concurrency model: each shard is one task on a :class:`ShardPool`. A
    task reads only snapshot arrays (``part_of``, the superstep-start load
    vectors) and its own :class:`_ShardPrep`, and writes only its disjoint
    slices of the superstep's assignment/histogram buffers - tasks commute,
    so assignments are bit-identical for every ``max_workers``. The merge
    back into shared state is a vectorised bincount reduction on the main
    thread; the sub-partition merge is a FIFO-chained pool task that may
    overlap the next superstep's scoring.
    """

    def __init__(
        self,
        eng: "StreamEngine",
        sharded: ShardedStream,
        reassign: bool = False,
        need_cols: bool = False,
        need_parts: bool = False,
    ):
        if not hasattr(eng.scorer, "affine_arrays"):
            raise ValueError(
                "sharded policies require a scorer with the affine contract "
                "(scores == hist * mul + add); got "
                f"{type(eng.scorer).__name__}"
            )
        if reassign and eng.subp is not None:
            # same contract as ImmediatePolicy: SubPartitioner has no unassign
            raise ValueError("reassign mode does not support a subpartitioner")
        self.eng = eng
        self.sharded = sharded
        self.reassign = reassign
        self.need_cols = need_cols
        self.need_parts = need_parts
        state = eng.state
        self.k = state.k
        self.shard_of = sharded.shard_of(eng.graph.num_vertices)
        self.step_mark = np.full(eng.graph.num_vertices, -1, dtype=np.int64)
        self.step = 0
        self.sync_rounds = 0
        self.boundary_conflicts = 0
        self.vertex_mode = state.balance_mode == "vertex"
        self.cap = (
            state.vertex_capacity if self.vertex_mode else state.edge_capacity
        )
        self.wave = max(int(eng.config.wave), 1)
        self.pool = ShardPool(eng.config.max_workers, sharded.num_shards)
        self.profile = SuperstepProfiler(workers=self.pool.workers)
        self.prefetch_ahead = eng.prefetch_ahead
        # with an inline (single-worker) pool, prepare_async would run on the
        # calling thread and the ahead-prep overlap would silently vanish; a
        # dedicated decode thread keeps the pipeline real on one core
        self._prefetch_ex: ThreadPoolExecutor | None = (
            ThreadPoolExecutor(1, thread_name_prefix="prefetch")
            if eng.prefetch_enabled and self.pool.workers == 1
            else None
        )
        self._subp_chain = None
        self._v0: np.ndarray | None = None
        self._e0: np.ndarray | None = None

    def close(self) -> None:
        """Flush the chained sub-partition merges and stop the pool. Must
        run before anything reads ``eng.subp`` state (phase 2)."""
        if self._subp_chain is not None:
            t0 = time.perf_counter()
            self._subp_chain.result()
            self.profile.add("merge", time.perf_counter() - t0)
            self._subp_chain = None
        if self._prefetch_ex is not None:
            self._prefetch_ex.shutdown(wait=True)
            self._prefetch_ex = None
        self.pool.shutdown()

    # ----------------------------------------------------------- prefetch
    def prepare_async(self, batches: list[np.ndarray]) -> list:
        """Submit per-shard frontier expansion; futures align with shards."""
        eng = self.eng
        indptr, indices = eng.graph.indptr, eng.graph.indices
        fn = _prepare_shard
        if eng.prefetch_enabled:
            stats = eng.prefetch_stats

            def fn(ip, ix, b):
                t0 = time.perf_counter()
                try:
                    return _prepare_shard(ip, ix, b)
                finally:
                    stats.record_decode(time.perf_counter() - t0)

        submit = (
            self._prefetch_ex.submit
            if self._prefetch_ex is not None
            else self.pool.submit
        )
        return [
            submit(fn, indptr, indices, b) if b.shape[0] else None
            for b in batches
        ]

    def wait_preps(
        self, futs: list | None, record: bool = False
    ) -> list[_ShardPrep | None] | None:
        if futs is None:
            return None
        hit = all(f is None or f.done() for f in futs)
        t0 = time.perf_counter()
        preps = [f.result() if f is not None else None for f in futs]
        wait = time.perf_counter() - t0
        self.profile.add("prep", wait)
        if record and self.eng.prefetch_enabled:
            self.eng.prefetch_stats.record_wait(wait, hit)
        return preps

    # -------------------------------------------------------- histogramming
    def _histograms_packed(self, preps, counts, total):
        """float64[total, K] histograms via ONE packed sharded kernel call
        (TPU / interpret path; the host path histograms inside shard tasks
        with :func:`neighbor_histograms_host` instead)."""
        eng = self.eng
        k = self.k
        part_of = eng.state.part_of
        indptr, indices = eng.graph.indptr, eng.graph.indices
        num_shards = len(counts)
        cmax = max(max(counts), 1)
        max_deg = max(
            (int(p.degs.max()) for p in preps if p is not None and p.degs.size),
            default=0,
        )
        kw = max(min(max_deg, _EXACT_KERNEL_WIDTH), 1)
        width = max(8, 1 << (kw - 1).bit_length())
        bounds = np.cumsum(np.asarray(counts, dtype=np.int64))
        starts = bounds - np.asarray(counts, dtype=np.int64)
        nbr3 = np.full((num_shards, cmax, width), -1, dtype=np.int32)
        over_rows: list[tuple[int, int]] = []
        for s, prep in enumerate(preps):
            if prep is None:
                continue
            over = np.flatnonzero(prep.degs > kw)
            if over.size:
                fmask = (prep.degs <= kw)[prep.rows]
                nbr3[s, prep.rows[fmask], prep.idx_in_row[fmask]] = (
                    part_of[prep.cols[fmask]]
                )
                over_rows.extend(
                    (int(starts[s] + i), int(prep.batch[i])) for i in over
                )
            else:
                nbr3[s, prep.rows, prep.idx_in_row] = part_of[prep.cols]
        out = np.asarray(
            fennel_scores_sharded(
                nbr3, np.zeros((num_shards, k), dtype=np.float32), 0.0, 1.5,
                use_pallas=eng.config.use_pallas, interpret=eng.config.interpret,
            ),
            dtype=np.float64,
        )
        hist = np.empty((total, k), dtype=np.float64)
        for s, c in enumerate(counts):
            if c:
                hist[starts[s] : bounds[s]] = out[s, :c]
        for gi, v in over_rows:
            # over-width hubs: exact host histogram (Thm. 1 regime)
            nbp = part_of[indices[indptr[v] : indptr[v + 1]]]
            hist[gi] = np.bincount(nbp[nbp >= 0], minlength=k)
        return hist

    # ------------------------------------------------------- per-shard task
    def _shard_task(self, prep: _ShardPrep, hist_rows, out, room):
        """One shard's superstep work: histogram (host path) + wave-
        vectorised placement. Reads only snapshot arrays and ``prep``;
        writes only this shard's ``hist_rows``/``out`` slices - safe and
        deterministic under any pool scheduling."""
        t0 = time.perf_counter()
        part_of = self.eng.state.part_of
        if hist_rows is None:
            hist_rows = neighbor_histograms_host(
                prep.rows, part_of[prep.cols], prep.batch.shape[0], self.k
            )
        old = part_of[prep.batch].astype(np.int64) if self.reassign else None
        t1 = time.perf_counter()
        self._place_shard(prep, hist_rows, out, room, old)
        t2 = time.perf_counter()
        return t1 - t0, t2 - t1, old

    def _place_shard(self, prep, hist, out, room, old):
        """Wave-vectorised placement of one shard's candidates.

        ``wave`` candidates are scored at a time against the superstep
        snapshot plus this shard's own running deltas: within a wave the
        balance penalty and in-shard neighbour histograms are frozen (the
        relaxation the supersteps already make across shards, one level
        down); between waves both are refreshed exactly. A wave whose picks
        would overshoot a partition's shard-local headroom is replayed per
        vertex against live loads (rare - caught by the bincount projection
        below), so the capacity rule is enforced exactly as sequentially.
        Ties break to the lowest partition index - deterministic without
        consuming shared rng state, which is what makes assignments
        independent of the worker count.
        """
        k = self.k
        scorer = self.eng.scorer
        c = prep.batch.shape[0]
        degf = prep.degs.astype(np.float64)
        inc = np.ones(c, dtype=np.float64) if self.vertex_mode else degf
        v_loc = self._v0.copy()
        e_loc = self._e0.copy()
        used = np.zeros(k, dtype=np.float64)
        wave = self.wave
        csrc, cdst = prep.corr_src, prep.corr_dst
        for g0 in range(0, c, wave):
            g1 = min(g0 + wave, c)
            g = g1 - g0
            rows_i = np.arange(g)
            hb = hist[g0:g1]
            mul, add = scorer.affine_arrays(v_loc, e_loc)
            sc = hb + add if mul is None else hb * mul + add
            incw = inc[g0:g1]
            fits = used + incw[:, None] <= room
            cur = None
            if old is not None:
                # pull each candidate out of its current partition in its
                # own row's view: staying put is always allowed, and cur's
                # penalty reflects the vertex's removal (sequential rule)
                cur = old[g0:g1]
                fits[rows_i, cur] = True
                smul, sadd = scorer.affine_arrays(
                    v_loc[cur] - 1.0, e_loc[cur] - degf[g0:g1]
                )
                own = hb[rows_i, cur]
                sc[rows_i, cur] = own + sadd if smul is None else own * smul + sadd
            masked = np.where(fits, sc, -np.inf)
            choice = masked.argmax(axis=1).astype(np.int64)
            best = masked[rows_i, choice]
            fallback = ~(best > -np.inf)  # -inf (or nan): headroom exhausted
            if fallback.any():
                loads_loc = v_loc if self.vertex_mode else e_loc
                choice[fallback] = int(loads_loc.argmin())
            add_w = np.bincount(choice, weights=incw, minlength=k)
            proj = used + add_w
            if cur is not None:
                proj = proj - np.bincount(cur, weights=incw, minlength=k)
            repaired = False
            if (proj > room).any():
                nf = np.flatnonzero(~fallback)
                # fallback-only overshoot mirrors the sequential fallback
                # (capacity is advisory there); real picks must not overshoot
                if nf.size and (proj > room)[choice[nf]].any():
                    repaired = True
                    self._repair_wave(
                        g0, g1, sc, incw, degf, room, used, v_loc, e_loc,
                        choice, cur,
                    )
            if not repaired:
                used += add_w
                v_loc += np.bincount(choice, minlength=k).astype(np.float64)
                e_loc += np.bincount(choice, weights=degf[g0:g1], minlength=k)
                if cur is not None:
                    used -= np.bincount(cur, weights=incw, minlength=k)
                    v_loc -= np.bincount(cur, minlength=k).astype(np.float64)
                    e_loc -= np.bincount(cur, weights=degf[g0:g1], minlength=k)
            out[g0:g1] = choice
            if csrc.size:
                lo = np.searchsorted(csrc, g0)
                hi = np.searchsorted(csrc, g1)
                if hi > lo:
                    d_ = cdst[lo:hi]
                    later = d_ >= g1
                    if later.any():
                        d_ = d_[later]
                        s_ = csrc[lo:hi][later] - g0
                        np.add.at(hist, (d_, choice[s_]), 1.0)
                        if cur is not None:
                            np.add.at(hist, (d_, cur[s_]), -1.0)

    def _repair_wave(
        self, g0, g1, sc, incw, degf, room, used, v_loc, e_loc, choice, cur
    ):
        """Scalar replay of one wave against live shard-local loads (frozen
        wave scores): only runs when the vectorised projection would
        overshoot, so the balance invariant is exactly the sequential one."""
        vertex_mode = self.vertex_mode
        for i in range(g1 - g0):
            inc_i = incw[i]
            f_i = used + inc_i <= room
            if cur is not None:
                f_i[cur[i]] = True
            m = np.where(f_i, sc[i], -np.inf)
            b = m.max()
            if b > -np.inf:
                p = int(m.argmax())
            else:
                p = int((v_loc if vertex_mode else e_loc).argmin())
            choice[i] = p
            d = degf[g0 + i]
            used[p] += inc_i
            v_loc[p] += 1.0
            e_loc[p] += d
            if cur is not None:
                q = cur[i]
                used[q] -= inc_i
                v_loc[q] -= 1.0
                e_loc[q] -= d

    # ----------------------------------------------------------- superstep
    def run_superstep(
        self,
        batches: list[np.ndarray],
        preps: list[_ShardPrep | None] | None = None,
    ) -> np.ndarray | None:
        """Score + place all shards' candidates concurrently, commit at the
        boundary via a vectorised reduction.

        Returns the flat neighbour-id array of everything placed (the
        buffered policy notifies every shard buffer with it; only built
        when ``need_cols``; with ``need_parts`` a ``(cols, parts)`` pair
        where ``parts[j]`` is the partition the owner of neighbour slot
        ``j`` was just placed in - partition-tracking buffer strategies
        feed it to ``notify_many``), or None when the superstep had no
        candidates.
        """
        eng = self.eng
        state = eng.state
        self.step += 1
        counts = [int(b.shape[0]) for b in batches]
        total = sum(counts)
        if total == 0:
            return None
        if preps is None:
            preps = self.wait_preps(self.prepare_async(batches))
        eng.telemetry["kernel_calls"] += 1
        k = self.k
        v_counts, e_counts = state.v_counts, state.e_counts
        loads0 = v_counts if self.vertex_mode else e_counts
        # remaining per-partition capacity split evenly across the shards
        # that actually place this superstep (empty batches - e.g. drained
        # cursors - must not starve the active ones): the merged superstep
        # cannot overshoot the balance condition any worse than the
        # sequential least-loaded fallback already can
        active = sum(1 for c in counts if c)
        room = np.maximum(self.cap - loads0, 0.0) / active
        self._v0 = v_counts.copy()
        self._e0 = e_counts.copy()
        bounds = np.cumsum(np.asarray(counts, dtype=np.int64))
        starts = bounds - np.asarray(counts, dtype=np.int64)
        assigned_flat = np.empty(total, dtype=np.int64)
        hist_all = None
        score_s = 0.0
        if eng._use_kernel:
            t_k = time.perf_counter()
            hist_all = self._histograms_packed(preps, counts, total)
            score_s += time.perf_counter() - t_k
        # fan out: one task per non-empty shard, each writing its disjoint
        # slice of assigned_flat (and mutating only its own hist rows)
        t_par = time.perf_counter()
        futs = []
        for s, prep in enumerate(preps):
            if prep is None:
                continue
            hist_rows = (
                hist_all[starts[s] : bounds[s]] if hist_all is not None else None
            )
            futs.append(
                self.pool.submit(
                    self._shard_task, prep, hist_rows,
                    assigned_flat[starts[s] : bounds[s]], room,
                )
            )
        place_s = 0.0
        olds = []
        for f in futs:
            h_s, p_s, old = f.result()
            score_s += h_s
            place_s += p_s
            if old is not None:
                olds.append(old)
        parallel_wall = time.perf_counter() - t_par
        # ------------------------------------------------ boundary exchange
        t_x = time.perf_counter()
        live = [p for p in preps if p is not None]
        big = np.concatenate([p.batch for p in live])
        degf = np.concatenate([p.degs for p in live]).astype(np.float64)
        if self.reassign:
            old_flat = np.concatenate(olds)
            v_counts -= np.bincount(old_flat, minlength=k).astype(np.float64)
            e_counts -= np.bincount(old_flat, weights=degf, minlength=k)
        state.part_of[big] = assigned_flat
        v_counts += np.bincount(assigned_flat, minlength=k).astype(np.float64)
        e_counts += np.bincount(assigned_flat, weights=degf, minlength=k)
        self.sync_rounds += 1
        self.step_mark[big] = self.step
        conflicts = 0
        for s, prep in enumerate(preps):
            if prep is None or prep.cols.size == 0:
                continue
            same_step = self.step_mark[prep.cols] == self.step
            conflicts += int((same_step & (self.shard_of[prep.cols] != s)).sum())
        # each conflicting edge appears once from either endpoint
        self.boundary_conflicts += conflicts // 2
        exchange_s = time.perf_counter() - t_x
        # ----------------------------------- overlapped sub-partition merge
        merge_s = 0.0
        if eng.subp is not None:
            t_m = time.perf_counter()
            rows_g = np.concatenate(
                [p.rows + starts[s] for s, p in enumerate(preps) if p is not None]
            )
            cols_g = np.concatenate([p.cols for p in live])
            degs_g = np.concatenate([p.degs for p in live])
            # FIFO-chained: superstep t's sub-placement may overlap t+1's
            # scoring (placement never reads sub-partition state), but
            # merges apply in superstep order and close() flushes the chain
            # before phase 2 reads it
            self._subp_chain = self.pool.submit_after(
                self._subp_chain, eng.subp.assign_superstep,
                big, assigned_flat, degs_g, rows_g, cols_g, self.wave,
            )
            merge_s = time.perf_counter() - t_m
        self.profile.record(
            score=score_s, place=place_s, exchange=exchange_s, merge=merge_s,
            parallel_wall=parallel_wall,
        )
        if self.need_cols:
            cols_all = np.concatenate([p.cols for p in live])
            if self.need_parts:
                # partition of the *placer*, aligned with its neighbour slots
                parts_all = np.concatenate(
                    [
                        assigned_flat[starts[s] : bounds[s]][p.rows]
                        for s, p in enumerate(preps)
                        if p is not None
                    ]
                )
                return cols_all, parts_all
            return cols_all
        return big

    def finalize_telemetry(self) -> None:
        self.profile.add_queue_wait(self.pool.queue_wait_s)
        self.eng.telemetry.update(
            supersteps=self.step,
            sync_rounds=self.sync_rounds,
            boundary_conflicts=self.boundary_conflicts,
            num_shards=self.sharded.num_shards,
            max_workers=self.pool.workers,
            profile=self.profile.to_dict(),
        )


class ShardedImmediatePolicy:
    """S interleaved shard frontiers placed per bulk-synchronous superstep.

    The FENNEL/LDG analogue of the paper's parallel CUTTANA: every superstep
    each shard advances its cursor by ``config.chunk`` vertices, all shards'
    chunks are scored in one packed kernel call, and the shared state is
    synchronized at the boundary. ``num_shards=1`` is *defined* as the
    sequential engine (delegates to :class:`ImmediatePolicy`), so every
    sequential parity guarantee carries over bit-for-bit.

    ``reassign=True`` is the restreaming mode (every vertex already holds an
    assignment; each superstep pulls its candidates out of their current
    partitions in the shard-local view and may move them) - the sharded
    counterpart of ``ImmediatePolicy(reassign=True)``.
    """

    def __init__(self, num_shards: int, reassign: bool = False):
        self.num_shards = _check_num_shards(num_shards)
        self.reassign = reassign

    def run(self, eng: "StreamEngine") -> None:
        if self.num_shards == 1:
            ImmediatePolicy(reassign=self.reassign).run(eng)
            eng.telemetry.update(
                supersteps=0, sync_rounds=0, boundary_conflicts=0, num_shards=1
            )
            return
        sharded = ShardedStream.from_ids(eng.ids, self.num_shards)
        runner = _SuperstepRunner(eng, sharded, reassign=self.reassign)
        try:
            steps = list(sharded.superstep_batches(eng.config.chunk))
            if not runner.prefetch_ahead:
                # prefetch="off": the true synchronous baseline - every
                # superstep expands its own frontier before scoring
                for batches in steps:
                    runner.run_superstep(batches)
            else:
                prefetched = runner.prepare_async(steps[0]) if steps else None
                for t, batches in enumerate(steps):
                    preps = runner.wait_preps(prefetched, record=True)
                    # overlap: expand superstep t+1's frontier while t scores,
                    # places and merges (expansion reads only the immutable CSR)
                    prefetched = (
                        runner.prepare_async(steps[t + 1])
                        if t + 1 < len(steps)
                        else None
                    )
                    runner.run_superstep(batches, preps)
        finally:
            runner.close()
        runner.finalize_telemetry()


class ShardedBufferedPolicy:
    """Parallel CUTTANA Algorithm 1: shard-local priority buffers around the
    bulk-synchronous superstep core.

    Each shard ingests ``config.chunk`` stream vertices per superstep into
    its own :class:`PriorityBuffer` (D_max bypasses and already-complete
    vertices become immediate candidates; overflow evicts the best-scored
    ones), all shards' candidates are placed through ONE packed kernel call,
    and at the boundary every shard's buffer is notified with the whole
    superstep's placements - cross-shard visibility arrives exactly one
    superstep late (relaxed consistency). Complete vertices surfacing at a
    boundary are placed in the next superstep; buffers drain chunk-at-a-time
    once their cursor is exhausted. ``num_shards=1`` delegates to the
    sequential :class:`BufferedPolicy` (bit-identical by construction).
    """

    def __init__(
        self,
        num_shards: int,
        max_qsize: int,
        d_max: int,
        theta: float = 1.0,
        strategy: str = "eq6",
    ):
        self.num_shards = _check_num_shards(num_shards)
        self.max_qsize = int(max_qsize)
        prio = make_priority(strategy, d_max, theta)  # validates name eagerly
        self.strategy = prio.name
        self.tracks_parts = prio.tracks_parts
        self.d_max = prio.d_max
        self.theta = prio.theta
        self.buffers: list[PriorityBuffer] | None = None

    def run(self, eng: "StreamEngine") -> None:
        if self.num_shards == 1:
            seq = BufferedPolicy(
                self.max_qsize, self.d_max, self.theta, strategy=self.strategy
            )
            seq.run(eng)
            self.buffers = [seq.buffer]
            eng.telemetry.update(
                supersteps=0, sync_rounds=0, boundary_conflicts=0, num_shards=1
            )
            return
        num_shards = self.num_shards
        graph = eng.graph
        indptr, indices = graph.indptr, graph.indices
        part_of = eng.state.part_of
        sharded = ShardedStream.from_ids(eng.ids, num_shards)
        track = self.tracks_parts
        runner = _SuperstepRunner(eng, sharded, need_cols=True, need_parts=track)
        chunk = max(int(eng.config.chunk), 1)
        bufs = [
            PriorityBuffer(
                self.max_qsize,
                graph=graph,
                priority=make_priority(self.strategy, self.d_max, self.theta),
            )
            for _ in range(num_shards)
        ]
        self.buffers = bufs
        pending: list[list[int]] = [[] for _ in range(num_shards)]
        cursors = [0] * num_shards
        d_max = self.d_max
        prefetch_on = eng.prefetch_enabled
        stats = eng.prefetch_stats
        # decode-ahead slots: shard -> (cursor snapshot, in-flight scan).
        # Each slot is written on the main thread between rounds and consumed
        # only by that shard's ingest task, so access stays disjoint.
        adm: dict[int, tuple[int, object]] = {}

        def scan(s: int, cursor: int):
            """Assignment-independent half of shard s's ingest: the stream
            slice and its (decoded) neighbour expansion. Reads only the
            immutable CSR, so it may overlap a superstep writing ``part_of``."""
            take = sharded.shards[s][cursor : cursor + chunk]
            if not take.shape[0]:
                return take, None, None
            tdegs = (indptr[take + 1] - indptr[take]).astype(np.int64)
            trows, _, tcols = _expand_csr_batch(indptr, indices, take, tdegs)
            return take, tdegs, (trows, tcols)

        def timed_scan(s: int, cursor: int):
            t0 = time.perf_counter()
            try:
                return scan(s, cursor)
            finally:
                stats.record_decode(time.perf_counter() - t0)

        def prefetch_scans():
            """Queue the next round's admission scans: once every ingest has
            returned, the round's cursors are final, so the next slices are
            known and can decode while the superstep scores and places."""
            ex = runner._prefetch_ex
            submit = ex.submit if ex is not None else runner.pool.submit
            for s in range(num_shards):
                if cursors[s] < sharded.shards[s].shape[0]:
                    adm[s] = (cursors[s], submit(timed_scan, s, cursors[s]))

        def ingest(s: int):
            """One shard's superstep ingest: admission scan + buffer churn.
            Touches only shard s's buffer/pending/cursor slots and reads the
            boundary-stable ``part_of``, so all S ingests run concurrently;
            per-shard counters come back for a deterministic main-thread sum.
            """
            cand = pending[s]
            pending[s] = []
            buf = bufs[s]
            pre = adm.pop(s, None)
            if pre is not None and pre[0] == cursors[s]:
                fut = pre[1]
                was_ready = fut.done()
                t0 = time.perf_counter()
                take, tdegs, texp = fut.result()
                stats.record_wait(time.perf_counter() - t0, was_ready)
            else:
                take, tdegs, texp = scan(s, cursors[s])
            cursors[s] += take.shape[0]
            evicted = drained_n = bypass_n = 0
            if take.shape[0]:
                trows, tcols = texp
                tparts = part_of[tcols]
                asg = np.bincount(
                    trows[tparts != -1], minlength=take.shape[0]
                )
                byp = tdegs >= d_max
                comp = (~byp) & (asg == tdegs) & (tdegs > 0)
                tl = take.tolist()
                al = asg.tolist()
                bypl = byp.tolist()
                compl = comp.tolist()
                if track:
                    toffs = np.zeros(take.shape[0] + 1, dtype=np.int64)
                    np.cumsum(tdegs, out=toffs[1:])
                for i in range(len(tl)):
                    if bypl[i]:
                        bypass_n += 1
                        cand.append(tl[i])
                    elif compl[i]:
                        cand.append(tl[i])
                    else:
                        buf.push(
                            tl[i],
                            None,
                            al[i],
                            tparts[toffs[i] : toffs[i + 1]] if track else None,
                        )
                while buf.full:
                    u, _ = buf.pop_best()
                    evicted += 1
                    cand.append(u)
            elif len(buf):
                # cursor exhausted: drain the buffer in score order,
                # chunk candidates per superstep
                for _ in range(max(chunk - len(cand), 0)):
                    if not len(buf):
                        break
                    u, _ = buf.pop_best()
                    drained_n += 1
                    cand.append(u)
            return (
                np.asarray(cand, dtype=np.int64),
                evicted, drained_n, bypass_n, len(buf),
            )

        def notify(s: int, placed_cols: np.ndarray, placed_parts=None):
            """Boundary: shard s's buffer learns about ALL placements.
            Mutates only shard s's buffer and pending slot."""
            buf = bufs[s]
            if not len(buf):
                return
            for w in buf.notify_many(placed_cols, placed_parts):
                buf.remove(w)
                pending[s].append(w)

        bstats = BufferStats()
        try:
            if prefetch_on:
                prefetch_scans()
            while True:
                t0 = time.perf_counter()
                results = [
                    f.result()
                    for f in [
                        runner.pool.submit(ingest, s) for s in range(num_shards)
                    ]
                ]
                runner.profile.add("prep", time.perf_counter() - t0)
                if prefetch_on:
                    prefetch_scans()
                batches = [r[0] for r in results]
                for _, ev, dr, by, blen in results:
                    bstats.evictions += ev
                    bstats.drained += dr
                    bstats.bypass += by
                    bstats.observe_len(blen)
                if all(b.shape[0] == 0 for b in batches):
                    exhausted = all(
                        cursors[s] >= sharded.shards[s].shape[0]
                        for s in range(num_shards)
                    )
                    if exhausted and not any(len(b) for b in bufs):
                        break
                    # everything ingested got buffered - still a superstep,
                    # no sync
                    runner.step += 1
                    continue
                res = runner.run_superstep(batches)
                cols, placed_parts = (
                    res if track and res is not None else (res, None)
                )
                if cols is not None and cols.size:
                    t1 = time.perf_counter()
                    for f in [
                        runner.pool.submit(notify, s, cols, placed_parts)
                        for s in range(num_shards)
                    ]:
                        f.result()
                    runner.profile.add("merge", time.perf_counter() - t1)
        finally:
            runner.close()
        eng.telemetry.update(bstats.to_telemetry(self.strategy))
        runner.finalize_telemetry()


# ------------------------------------------------------------------- engine
class StreamEngine:
    """Drives one streaming pass: ``scorer.begin`` then ``policy.run``.

    ``ids`` overrides the stream order (otherwise computed from
    ``order``/``seed``); ``subpartitioner`` hooks CUTTANA's Def. 2
    sub-placement into every commit; ``on_chunk_end(engine, batch,
    nbr_views)`` runs after each chunk in immediate mode (HeiStream's FM
    refinement uses it - mutate state there, then call
    ``engine.scorer.begin(engine.state)`` to refresh the penalty cache)."""

    def __init__(
        self,
        graph: CSRGraph,  # or any CSR read surface, e.g. ExternalCSRGraph
        state: PartitionState,
        scorer: Scorer,
        policy: PlacementPolicy,
        *,
        subpartitioner: SubPartitioner | None = None,
        order: str = "natural",
        seed: int = 0,
        ids: np.ndarray | None = None,
        config: EngineConfig | None = None,
        on_chunk_end: Callable[["StreamEngine", np.ndarray, list], None] | None = None,
    ):
        self.graph = graph
        self.state = state
        self.scorer = scorer
        self.policy = policy
        self.subp = subpartitioner
        self.config = config or EngineConfig()
        self.ids = stream_order(graph, order, seed) if ids is None else ids
        self.on_chunk_end = on_chunk_end
        self._use_kernel = kernel_active(self.config.use_pallas, self.config.interpret)
        # run counters consumed by repro.api's PartitionResult telemetry:
        # kernel_calls counts fused chunk-histogram calls, kernel_active says
        # whether they ran the Pallas kernel (else the host bincount),
        # single_place_calls the host-scored placements (buffered policy);
        # policies add their own
        self.telemetry: dict = {
            "kernel_calls": 0,
            "kernel_active": self._use_kernel,
            "single_place_calls": 0,
        }
        self.prefetch_enabled, self.prefetch_ahead = _resolve_prefetch(
            self.config.prefetch, graph
        )
        self.prefetch_stats = PrefetchStats()
        self._sample_rng = np.random.default_rng(seed)
        self._pos = np.full(graph.num_vertices, -1, dtype=np.int64)
        self._zero_sizes = np.zeros(state.k, dtype=np.float32)

    def run(self) -> PartitionState:
        self.scorer.begin(self.state)
        self.policy.run(self)
        if self.prefetch_enabled:
            self.telemetry.update(self.prefetch_stats.to_telemetry())
        # a compressed indices proxy reports exact varint-decode wall time;
        # prefer it over the prefetcher's coarser fetch-wall aggregate
        decode_s = getattr(self.graph.indices, "decode_seconds", None)
        if decode_s is not None:
            self.telemetry["decode_wall_s"] = round(float(decode_s), 6)
        return self.state

    # ------------------------------------------------- per-vertex placement
    def place(self, v: int, nbrs: np.ndarray) -> int:
        """Score + place one vertex against the *fresh* state (used by the
        buffered policy, whose placement order is data-dependent)."""
        state = self.state
        self.telemetry["single_place_calls"] += 1
        hist = state.neighbor_histogram(nbrs)
        scores = self.scorer.scores(state, hist)
        allowed = ~state.would_overflow(nbrs.size)
        p = state.argmax_tiebreak(scores, allowed)
        state.assign(v, p, nbrs.size)
        self.scorer.on_assign(state, p, nbrs.size)
        if self.subp is not None:
            self.subp.assign(v, p, nbrs, nbrs.size)
        return p

    # --------------------------------------------------- chunked histograms
    def chunk_histograms(
        self,
        batch: np.ndarray,
        degs: np.ndarray,
        nbr_views: list[np.ndarray] | None = None,
        expanded: tuple | None = None,
    ):
        """All C x K assigned-neighbour histograms for a chunk via one fused
        kernel call.

        Returns ``(hist float64[C, K], corr)`` where ``corr`` is ``None`` in
        stale mode, else ``(dst, starts)``: for chunk position ``i``,
        ``dst[starts[i]:starts[i+1]]`` lists the later chunk positions that
        have ``batch[i]`` as a neighbour - the rows to bump when ``batch[i]``
        is assigned (the stale-histogram correction that makes exact mode
        bit-identical to the sequential loops). ``expanded`` is an optional
        precomputed :func:`_expand_csr_batch` result for the chunk - the
        prefetch pipeline passes it so a compressed graph is decoded once,
        not once per consumer."""
        cfg = self.config
        state = self.state
        c = batch.shape[0]
        if c == 0:
            return np.zeros((0, state.k), dtype=np.float64), None
        self.telemetry["kernel_calls"] += 1
        max_deg = int(degs.max())
        w = max(max_deg, 1)
        if not cfg.exact:
            w = min(w, cfg.sample_cap)
        indptr, indices = self.graph.indptr, self.graph.indices
        if expanded is None:
            expanded = _expand_csr_batch(indptr, indices, batch, degs)
        rows, idx_in_row, cols = expanded
        part_of = state.part_of
        scale = None
        sampled: list[tuple[int, np.ndarray]] = []
        if not cfg.exact and max_deg > w:
            scale = np.ones(c, dtype=np.float64)
            for i in np.flatnonzero(degs > w):
                # degree-capped sampling (Thm. 1 regime): exact counts matter
                # least for exactly these vertices
                if nbr_views is not None:
                    nb = nbr_views[i]
                else:
                    v = batch[i]
                    nb = indices[indptr[v] : indptr[v + 1]]
                sel = self._sample_rng.choice(nb.size, size=w, replace=False)
                sampled.append((int(i), part_of[nb[sel]]))
                scale[i] = nb.size / w
        if self._use_kernel:
            kw = w
            over: np.ndarray | None = None
            if cfg.exact and kw > _EXACT_KERNEL_WIDTH:
                # bound the dense [C, width] matrix: power-law hubs would
                # otherwise blow it up (one degree-500k vertex => ~1 GB).
                # The few over-width rows get exact host histograms below.
                kw = _EXACT_KERNEL_WIDTH
                over = np.flatnonzero(degs > kw)
            # pad the neighbour axis to a power of two >= 8 so kernel shapes
            # stay stable across chunks (padding is -1 and never counted)
            width = max(8, 1 << (kw - 1).bit_length())
            nbr_parts = np.full((c, width), -1, dtype=np.int32)
            if sampled or over is not None:
                fmask = (degs <= kw)[rows]
                nbr_parts[rows[fmask], idx_in_row[fmask]] = part_of[cols[fmask]]
                for i, nbp in sampled:
                    nbr_parts[i, :kw] = nbp
            else:
                nbr_parts[rows, idx_in_row] = part_of[cols]
            hist = np.asarray(
                fennel_scores(
                    nbr_parts, self._zero_sizes, 0.0, 1.5,
                    use_pallas=cfg.use_pallas, interpret=cfg.interpret,
                ),
                dtype=np.float64,
            )
            if over is not None:
                for i in over.tolist():
                    v = batch[i]
                    nbp = part_of[indices[indptr[v] : indptr[v + 1]]]
                    hist[i] = np.bincount(nbp[nbp >= 0], minlength=state.k)
        else:
            # CPU: flat bincount companion of the kernel, identical counts
            if sampled:
                fmask = (degs <= w)[rows]
                hist = neighbor_histograms_host(
                    rows[fmask], part_of[cols[fmask]], c, state.k
                )
                for i, nbp in sampled:
                    hist[i] = np.bincount(nbp[nbp >= 0], minlength=state.k)
            else:
                hist = neighbor_histograms_host(rows, part_of[cols], c, state.k)
        if scale is not None:
            hist *= scale[:, None]
        corr = self._inchunk_corr(batch, rows, cols) if cfg.exact else None
        return hist, corr

    def _inchunk_corr(self, batch: np.ndarray, rows: np.ndarray, cols: np.ndarray):
        """``(dst, starts)`` in-chunk correction lists for a candidate batch:
        for position ``i``, ``dst[starts[i]:starts[i+1]]`` are the later
        positions whose histograms must bump when ``batch[i]`` is assigned.
        ``rows``/``cols`` are the batch's flat (position, neighbour-id) pairs;
        shared by the sequential exact path and the per-shard superstep loop
        (where cross-shard pairs are deliberately absent - that staleness is
        the relaxed-consistency trade, surfaced as ``boundary_conflicts``)."""
        c = batch.shape[0]
        pos = self._pos
        pos[batch] = np.arange(c, dtype=np.int64)
        cpos = pos[cols]
        emask = (cpos >= 0) & (cpos < rows)
        pos[batch] = -1
        src = cpos[emask]
        dst = rows[emask]
        o = np.argsort(src, kind="stable")
        src, dst = src[o], dst[o]
        starts = np.searchsorted(src, np.arange(c + 1)).tolist()
        return (dst.tolist(), starts)
