"""Seconds per job spent applying phase 2's trades and their updates: the
span ``refine.apply``, one per trade. None where the program records no
such span."""
from bench.spans import mean_span_seconds


def read(run):
    return mean_span_seconds(run, ("refine.apply",))
