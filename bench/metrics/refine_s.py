"""Seconds per job of phase 2 (coarsen, the refiner's set-up and every
trade) on the main thread: the span ``partition.phase2``. None where the
program records no such span."""
from bench.spans import mean_span_seconds


def read(run):
    return mean_span_seconds(run, ("partition.phase2",))
