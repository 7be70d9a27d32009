"""Partitioning jobs: ``repro.api.partition`` on a Graph500 graph, again and
again, each job with its own stream-order seed.

Traffic keys: ``algo`` and ``params`` (the ``PartitionSpec`` of every job),
``warm_rows`` and ``warm_widths`` (the ranges of candidate rows and of
neighbour widths a score-kernel launch may have: set-up compiles every
launch shape they pad to, besides those the warm-up job meets),
``reference`` (``fennel`` or ``cuttana_parallel``: the plain reference in
``bench/reference.py`` that every answer must equal) and ``reference_args``
(its keyword arguments beyond the configuration's).

The control (``control``, read by ``python -m bench.control``) is the
reference with neighbour histograms that count at most the first 1024
neighbours of a vertex (the score kernel's widest launch), breaking "Eq. 7
histograms count every assigned neighbour"; it is read as the vertices whose
part differs from the reference's.
"""
from __future__ import annotations

import time

import numpy as np

from bench import reference
from bench.graph500 import graph500_csr

# the smallest scale at which the control has hubs wide enough to bite
CONTROL_SCALE = 16
NBR_CAP = 1024


def job_seed(seed: int, index: int) -> int:
    """The stream-order seed of job ``index`` of a run (0 is the warm-up)."""
    return int(np.random.SeedSequence([int(seed), index]).generate_state(1)[0])


def launch_shapes(rows: list[int], widths: list[int]) -> list[tuple[int, int]]:
    """Padded ``(rows, width)`` of every score-kernel launch for candidate
    counts in ``rows[0]..rows[1]`` and neighbour widths in
    ``widths[0]..widths[1]``: the stream engine rounds a width up to a power
    of two, and the kernel's tiling rule (``kernel_tiling``) pads both."""
    from repro.kernels.partition_score.ops import kernel_tiling

    shapes = set()
    for c in range(rows[0], rows[1] + 1):
        w = widths[0]
        while w <= widths[1]:
            shapes.add(kernel_tiling(c, w)[2:])
            w *= 2
    return sorted(shapes)


class Job:
    def __init__(self, config: dict, traffic: dict, seed: int, rehearse: bool = False):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.rehearse = rehearse
        self.k = int(config["k"])

    # ----------------------------------------------------------- set-up
    def setup(self) -> None:
        from repro.graph.csr import CSRGraph

        c = self.config
        t0 = time.perf_counter()
        indptr, indices = graph500_csr(
            c["graph_seed"], self.seed, c["scale"], c["edge_factor"], c["a"], c["b"], c["c"]
        )
        self.graph = CSRGraph(indptr=indptr, indices=indices)
        self.graph_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        if not self.rehearse:
            self._warm_kernel_shapes()
        self._partition(0)
        self.warm_s = time.perf_counter() - t0

    def _warm_kernel_shapes(self) -> None:
        """Compile the score kernel for every launch shape the traffic's
        ranges pad to, through the same entry and static arguments the
        stream engine uses."""
        from repro.kernels.partition_score import fennel_scores_sharded

        shards = int(self.traffic.get("params", {}).get("num_shards", 1))
        sizes = np.zeros((shards, self.k), dtype=np.float32)
        for rows, width in launch_shapes(self.traffic["warm_rows"], self.traffic["warm_widths"]):
            nbr = np.full((shards, rows, width), -1, dtype=np.int32)
            fennel_scores_sharded(nbr, sizes, 0.0, 1.5)

    def _spec(self, index: int):
        from repro.api import PartitionSpec

        c = self.config
        return PartitionSpec(
            algo=self.traffic["algo"], k=self.k, epsilon=c["epsilon"],
            balance_mode=c["balance_mode"], order=c["order"],
            seed=job_seed(self.seed, index), params=self.traffic.get("params"),
        )

    def _partition(self, index: int):
        from repro.api import partition

        spec = self._spec(index)
        if not self.rehearse:
            return partition(self.graph, spec)
        # a CPU rehearsal reaches the kernel path only in interpret mode,
        # which the spec cannot ask for: call the registry's callable with
        # the same keyword arguments, as the runner would
        from repro.api import PartitionResult
        from repro.api.registry import build_spec_kwargs, get_info

        info = get_info(spec.algo)
        telemetry: dict = {}
        out = info.resolve()(self.graph, spec.k, **build_spec_kwargs(info, spec),
                             telemetry=telemetry, interpret=True)
        return PartitionResult(spec=spec, graph=self.graph,
                               assignment=np.asarray(out), telemetry=telemetry)

    def devices(self):
        import jax

        return jax.devices()[:1]

    # ----------------------------------------------------------- window
    def run_one(self, index: int) -> dict:
        result = self._partition(index + 1)
        tel = dict(result.telemetry)
        if isinstance(tel.get("profile"), dict):
            tel["profile"] = {
                k: v for k, v in tel["profile"].items() if k != "per_superstep"
            }
        return {"seed": result.spec.seed, "assignment": result.assignment,
                "telemetry": tel}

    def end_to_end(self, records: list, window_s: float) -> dict:
        g = self.graph
        cuts = [reference.edge_cut(g.indptr, g.indices, r["assignment"]) for r in records]
        return {
            "partition_rate": len(records) * g.num_edges / window_s / 1e6,
            "edge_cut": float(np.mean(cuts)),
        }

    # ----------------------------------------------------------- checks
    def failed(self, records: list) -> int:
        """Jobs whose answer is no partition: wrong length, or a vertex left
        out or put in a part outside [0, k)."""
        n = self.graph.num_vertices
        return sum(
            1 for r in records
            if r["assignment"].shape != (n,)
            or r["assignment"].min() < 0 or r["assignment"].max() >= self.k
        )

    def check(self, records: list) -> dict:
        """The first and the last window job must each assign every vertex
        as the plain reference does: the first meets the system fresh from
        set-up, the last after every job before it."""
        mismatch = 0
        for rec in (records[0], records[-1]) if len(records) > 1 else records:
            ref = getattr(reference, self.traffic["reference"])(
                self.graph.indptr, self.graph.indices, self.k, rec["seed"],
                epsilon=self.config["epsilon"], **self.traffic.get("reference_args", {}),
            )
            mismatch += int(np.count_nonzero(ref != rec["assignment"]))
        return {"mismatched_vertices": {"value": mismatch, "limit": 0}}

    def info(self, records: list) -> dict:
        tel = records[0]["telemetry"]
        return {
            "num_vertices": self.graph.num_vertices,
            "num_edges": self.graph.num_edges,
            "graph_s": self.graph_s, "warm_s": self.warm_s,
            "kernel_active": tel.get("kernel_active"),
            "kernel_calls": sorted({r["telemetry"].get("kernel_calls") for r in records}),
        }


def partition_reading(config, traffic, indptr, indices, seed: int) -> int:
    """Vertices placed differently by the capped control and the reference,
    for window job 1 of a run with ``seed``."""
    fn = getattr(reference, traffic["reference"])
    args = dict(epsilon=config["epsilon"], **traffic.get("reference_args", {}))
    k, s = int(config["k"]), job_seed(seed, 1)
    want = fn(indptr, indices, k, s, **args)
    got = fn(indptr, indices, k, s, nbr_cap=NBR_CAP, **args)
    return int(np.count_nonzero(got != want))


def control(config, traffic, indptr, indices, seed: int) -> dict:
    """The control's reading for one seed, beside the limit ``Job.check``
    holds that number to."""
    return {"name": "mismatched_vertices", "limit": 0,
            "value": partition_reading(config, traffic, indptr, indices, seed)}
