"""Full CUTTANA jobs: ``repro.api.partition`` with phase-2 refinement on
(``cuttana-parallel``, ``use_refinement``), again and again on a Graph500
graph, each job with its own stream-order seed.

Traffic keys as in ``bench/jobs/partition.py`` (``algo``, ``params``,
``warm_rows``, ``warm_widths``); the reference takes its shard count from
``params["num_shards"]``. The warm-up job runs phase 1 alone: it meets
every score-kernel launch shape a window job meets, and phase 2 is host
numpy, which compiles nothing.

The check compares the final assignment vertex for vertex with the plain
reference, ``bench/reference_refine.py`` (``mismatched_vertices``, limit
0), for the first and the last window job. At scale 16 a job (about 29 s on
the v5e host) outlasts the window, so the window holds one job, which is
both: the check runs the reference once. Where a window holds more, both
are checked, as in the partition cells.

The control (``control``, read by ``python -m bench.control``) is the
reference with refinement's ties broken to the highest sub-partition id,
breaking the stated tie order; it is read as the vertices whose part
differs from the reference's.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import reference_refine
from bench.jobs import partition
from bench.jobs.partition import job_seed

# the smallest scale at which the control bites (32 vertices)
CONTROL_SCALE = 5


def _reference(config, traffic, indptr, indices, seed: int, tie: str = "low"):
    return reference_refine.cuttana_refine(
        indptr, indices, int(config["k"]), seed,
        num_shards=int(traffic["params"]["num_shards"]),
        epsilon=config["epsilon"], tie=tie,
    )


class Job(partition.Job):
    def _spec(self, index: int):
        spec = super()._spec(index)
        if index:
            return spec
        return dataclasses.replace(
            spec, params=dataclasses.replace(spec.params, use_refinement=False)
        )

    def check(self, records: list) -> dict:
        g = self.graph
        mismatch = 0
        for rec in (records[0], records[-1]) if len(records) > 1 else records:
            want = _reference(self.config, self.traffic, g.indptr, g.indices, rec["seed"])
            mismatch += int(np.count_nonzero(want != rec["assignment"]))
        return {"mismatched_vertices": {"value": mismatch, "limit": 0}}


def control(config, traffic, indptr, indices, seed: int) -> dict:
    """The control's reading for window job 1 of a run with ``seed``,
    beside the limit ``Job.check`` holds that number to."""
    s = job_seed(seed, 1)
    want = _reference(config, traffic, indptr, indices, s)
    got = _reference(config, traffic, indptr, indices, s, tie="high")
    return {"name": "mismatched_vertices", "limit": 0,
            "value": int(np.count_nonzero(got != want))}
