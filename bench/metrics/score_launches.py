"""Score-kernel launches per job: the stream engine's ``kernel_calls``
counter, where the kernel path was the one that ran."""
import numpy as np


def read(run):
    calls = [
        r["telemetry"]["kernel_calls"]
        for r in run.records
        if r["telemetry"].get("kernel_active")
    ]
    return float(np.mean(calls)) if calls else None
