"""Seconds per job of the superstep core's merge phase (buffer notifications
and the chained sub-partition merge), from the stream engine's profile:
main-thread host clock. None for policies without supersteps."""
import numpy as np


def read(run):
    merges = [
        r["telemetry"]["profile"]["merge_s"]
        for r in run.records
        if isinstance(r["telemetry"].get("profile"), dict)
    ]
    return float(np.mean(merges)) if merges else None
