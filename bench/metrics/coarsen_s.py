"""Seconds per job spent building phase 2's sub-partition graph ``W``
(Def. 3): the span ``refine.coarsen``. None where the program records no
such span."""
from bench.spans import mean_span_seconds


def read(run):
    return mean_span_seconds(run, ("refine.coarsen",))
