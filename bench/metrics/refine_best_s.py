"""Seconds per job spent picking phase 2's next trade: the span
``refine.best``, one per pick. None where the program records no such
span."""
from bench.spans import mean_span_seconds


def read(run):
    return mean_span_seconds(run, ("refine.best",))
