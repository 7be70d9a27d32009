"""Seconds per job of the score path on the main thread: the spans
``score.pack`` (host build of the neighbour-partition matrix),
``score.launch`` (host pad, dispatch, kernel and copy back) and
``score.hubs`` (host histograms of rows wider than the kernel). None where
the program records none of them."""
from bench.spans import mean_span_seconds


def read(run):
    return mean_span_seconds(run, ("score.pack", "score.launch", "score.hubs"))
