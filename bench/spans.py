"""Readers' helper for the program's own spans: each window job's
``telemetry["spans"]`` (``{name: {"s": seconds, "n": count}}``, the main
thread's wall time per span name, ``repro.core.profile``)."""
from __future__ import annotations

import numpy as np


def mean_span_seconds(run, names: tuple[str, ...]) -> float | None:
    """Seconds per job in the spans ``names`` (summed), averaged over the
    window's jobs; None where no job recorded any of them."""
    per_job = []
    for r in run.records:
        spans = r["telemetry"].get("spans") or {}
        if any(n in spans for n in names):
            per_job.append(sum(spans[n]["s"] for n in names if n in spans))
    return float(np.mean(per_job)) if per_job else None
