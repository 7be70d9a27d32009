"""Seconds per job of the stream engine's placement on the main thread: the
span ``engine.place`` (a chunk's placement loop and flush, or a superstep's
shard-task fan-out and join). None where the program records no such span."""
from bench.spans import mean_span_seconds


def read(run):
    return mean_span_seconds(run, ("engine.place",))
