"""Seconds per job of the shard buffers' ingest in parallel CUTTANA, on the
main thread: the span ``engine.ingest`` (the ingest fan-out and join). None
where the program records no such span."""
from bench.spans import mean_span_seconds


def read(run):
    return mean_span_seconds(run, ("engine.ingest",))
