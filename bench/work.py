"""Logical work of the system's kernels, from the graph alone.

The work is what the algorithm has to read and write, not what a given
launch shape pads it to, so the same number stands whatever implements it.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; unknown is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}")
    return table[device_kind]


def score_histogram_bytes(num_vertices: int, num_directed_edges: int, k: int) -> int:
    """Bytes one partitioning job's neighbour histograms must move: a 4-byte
    part id for each of the 2|E| neighbour slots read, and a K-wide row of
    4-byte counts for each of the |V| vertices written."""
    return 4 * num_directed_edges + 4 * k * num_vertices
