"""The program's one span surface: named main-thread wall-time spans.

``with spans.span(name):`` adds the main thread's ``perf_counter`` wall
time and a count to ``name``'s total, and opens a
``jax.profiler.TraceAnnotation`` of the same name, so under the JAX
profiler the span sits on the host plane on the same clock as the device
ops (a benchmark can name an idle gap of the device by the span around
it). Names come from the fixed tuple :data:`SPANS`; an unknown name is an
error.

Spans:

* ``partition.phase1`` / ``partition.phase2`` - a partitioner's streaming
  pass (engine construction and run) and its refinement; ``result.timings``
  takes ``phase1_seconds``/``phase2_seconds`` (``stream_seconds`` for the
  one-phase partitioners) from these totals.
* ``engine.order`` - the stream order, drawn as the engine is built.
* ``engine.fetch`` - the next chunk's neighbour expansion, or the wait on the
  prefetcher that expands it (chunked policies).
* ``engine.ingest`` - the shard buffers' ingest fan-out and join (parallel
  CUTTANA).
* ``engine.prep`` - superstep frontier expansion on the main thread: inline
  with one worker, else the wait on the expansion tasks.
* ``score.pack`` - host build of the padded neighbour-partition matrix.
* ``score.launch`` - the score-kernel call: host pad, dispatch, kernel and
  copy back.
* ``score.hubs`` - exact host histograms of rows wider than the kernel.
* ``score.bincount`` - the host histogram where no kernel runs (CPU).
* ``engine.corr`` - the exact mode's in-chunk correction lists.
* ``engine.place`` - placement: a chunk's placement loop and flush, or a
  superstep's shard-task fan-out and join.
* ``engine.exchange`` - the superstep boundary commit of assignments and
  loads, conflict count, and the placed neighbour slots for the buffers.
* ``engine.merge`` - the sub-partition merge submit, the buffers'
  notification fan-out and join, and the final flush of the merge chain.
* ``refine.coarsen`` - phase 2's sub-partition graph ``W`` (Def. 3).
* ``refine.init`` - the refiner's construction: ``M`` and the segment trees.
* ``refine.best`` - one pick of the next trade (``Refiner.best_move``).
* ``refine.apply`` - one trade and its updates (``Refiner.apply_move``).

Spans go on the thread that drives the job only, never in a pool task: a
span there would hide what the main thread waited on, and would need a
lock. Spans are per chunk, per superstep or per refinement move, never per
vertex or per wave.

The sharded policies' ``telemetry["profile"]`` is a view of the same
totals (:data:`PHASES`), with the first supersteps' rows.
"""
from __future__ import annotations

from time import perf_counter

from jax.profiler import TraceAnnotation

SPANS = (
    "partition.phase1",
    "partition.phase2",
    "engine.order",
    "engine.fetch",
    "engine.ingest",
    "engine.prep",
    "score.pack",
    "score.launch",
    "score.hubs",
    "score.bincount",
    "engine.corr",
    "engine.place",
    "engine.exchange",
    "engine.merge",
    "refine.coarsen",
    "refine.init",
    "refine.best",
    "refine.apply",
)

# superstep profile phase -> the spans whose main-thread wall it sums
PHASES = {
    "prep": ("engine.prep",),
    "score": ("score.pack", "score.launch", "score.hubs", "score.bincount"),
    "place": ("engine.place",),
    "exchange": ("engine.exchange",),
    "merge": ("engine.merge",),
}


class _Span:
    __slots__ = ("_rec", "_name", "_note", "_t0")

    def __init__(self, rec: "SpanRecorder", name: str):
        if name not in rec.seconds:
            raise ValueError(f"unknown span {name!r}; expected one of {SPANS}")
        self._rec = rec
        self._name = name

    def __enter__(self):
        # the annotation costs about as much as the rest of the span; open
        # it only while a profiler trace is being recorded
        self._note = TraceAnnotation(self._name) if TraceAnnotation.is_enabled() else None
        if self._note is not None:
            self._note.__enter__()
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc):
        dt = perf_counter() - self._t0
        if self._note is not None:
            self._note.__exit__(*exc)
        self._rec.seconds[self._name] += dt
        self._rec.counts[self._name] += 1
        return False


class SpanRecorder:
    """Totals of one job's spans (seconds and count per name), and the
    superstep rows of the sharded policies' profile."""

    def __init__(self, keep: int = 64):
        self.seconds = dict.fromkeys(SPANS, 0.0)
        self.counts = dict.fromkeys(SPANS, 0)
        self.supersteps = 0
        self._keep = int(keep)
        self._rows: list[dict] = []
        self._last = dict.fromkeys(PHASES, 0.0)

    def span(self, name: str) -> _Span:
        return _Span(self, name)

    def phase_seconds(self) -> dict[str, float]:
        return {p: sum(self.seconds[s] for s in names) for p, names in PHASES.items()}

    def end_superstep(self) -> None:
        """Close one superstep that placed vertices; the first ``keep`` get a
        row of the phase time spent since the previous row."""
        self.supersteps += 1
        if len(self._rows) < self._keep:
            now = self.phase_seconds()
            self._rows.append({p: round(now[p] - self._last[p], 6) for p in PHASES})
            self._last = now

    def to_dict(self) -> dict:
        """``{name: {"s": seconds, "n": count}}`` of the spans recorded."""
        return {
            name: {"s": self.seconds[name], "n": n}
            for name, n in self.counts.items() if n
        }

    def profile(self, workers: int) -> dict:
        """The superstep profile: main-thread wall per phase, summed over the
        job, and the first supersteps' rows."""
        out = {"workers": int(workers), "supersteps": self.supersteps}
        for p, s in self.phase_seconds().items():
            out[f"{p}_s"] = round(s, 6)
        out["per_superstep"] = list(self._rows)
        return out
