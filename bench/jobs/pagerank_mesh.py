"""Sharded PageRank jobs: the compiled ``GraphEngine.build_sharded`` program
on a mesh of one device per partition, run again and again from the same
start state.

Set-up generates the graph, partitions it with ``repro.api.partition``
(traffic keys ``algo`` and ``params``), localizes it, places the local graph
on the mesh, compiles the program and runs it once. A job is one call of the
compiled program (``iters`` PageRank iterations, ``damping`` as configured)
ended with ``block_until_ready``. Every job's vector must match the float64
reference in ``bench/reference.py`` within the traffic's ``max_rel_err``.

The configuration's ``instance_seed`` fixes the vertex labels and the
partition's stream order, so every run localizes the same partition and
runs the same compiled shapes; the run's ``--seed`` draws nothing, since
PageRank has no input beyond the graph.
"""
from __future__ import annotations

import time

import numpy as np

from bench import reference
from bench.graph500 import graph500_csr
from bench.jobs.partition import job_seed
from bench.trace import hlo_ops_from


class Job:
    def __init__(self, config: dict, traffic: dict, seed: int, rehearse: bool = False):
        self.config, self.traffic = config, traffic  # the seed draws nothing here
        self.rehearse = rehearse
        self.k = int(config["k"])
        self.iters = int(config["pagerank_iters"])

    # ----------------------------------------------------------- set-up
    def setup(self) -> None:
        import jax
        from jax.sharding import Mesh

        from repro.analytics import GraphEngine, localize, pagerank_program
        from repro.api import PartitionSpec, partition
        from repro.graph.csr import CSRGraph

        c = self.config
        instance = int(c["instance_seed"])
        t0 = time.perf_counter()
        indptr, indices = graph500_csr(
            c["graph_seed"], instance, c["scale"], c["edge_factor"], c["a"], c["b"], c["c"]
        )
        self.graph = CSRGraph(indptr=indptr, indices=indices)
        self.graph_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        spec = PartitionSpec(
            algo=self.traffic["algo"], k=self.k, epsilon=c["epsilon"],
            balance_mode=c["balance_mode"], order=c["order"],
            seed=job_seed(instance, 0), params=self.traffic.get("params"),
        )
        result = partition(self.graph, spec)
        self.partition_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        lg = localize(self.graph, result.assignment, self.k)
        self.localize_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.engine = GraphEngine(lg, pagerank_program(damping=c["damping"]))
        self.mesh = Mesh(np.array(jax.devices()[: self.k]), ("w",))
        fn, sharding = self.engine.build_sharded(self.mesh, iters=self.iters)
        self.state0 = jax.device_put(
            self.engine.program.init_state(lg, self.engine.ctx), sharding
        )
        self.arrays = [jax.device_put(a, sharding) for a in self.engine.graph_arrays()]
        self.compiled = fn.lower(self.state0, *self.arrays).compile()
        hlo = self.compiled.as_text()
        self.has_all_to_all = "all-to-all" in hlo
        self.scatter_ops = hlo_ops_from(hlo, "scatter")
        self.compiled(self.state0, *self.arrays).block_until_ready()
        self.warm_s = time.perf_counter() - t0
        self.stats = self.engine.stats(self.iters)

    def devices(self):
        return list(self.mesh.devices.flat)

    # ----------------------------------------------------------- window
    def run_one(self, index: int) -> dict:
        out = self.compiled(self.state0, *self.arrays)
        out.block_until_ready()
        return {"out": out}

    def end_to_end(self, records: list, window_s: float) -> dict:
        return {"pagerank_iter_ms": window_s / (len(records) * self.iters) * 1e3}

    # ----------------------------------------------------------- checks
    def failed(self, records: list) -> int:
        """Jobs whose vector has a value that is not finite. Brings every
        job's vector to the host by global vertex id, and frees the
        program's device state for the reference that follows."""
        self.values = [
            self.engine.gather_global(np.asarray(r.pop("out"))) for r in records
        ]
        self.state0 = self.arrays = self.compiled = None
        return sum(1 for v in self.values if not np.isfinite(v).all())

    def check(self, records: list) -> dict:
        """Every job's vector against the float64 reference: the largest
        relative error over all vertices and jobs."""
        g = self.graph
        want = reference.pagerank(g.indptr, g.indices, self.iters, self.config["damping"])
        err = max(float(np.max(np.abs(v - want) / want)) for v in self.values)
        return {"max_rel_err": {"value": err, "limit": self.traffic["max_rel_err"]}}

    def info(self, records: list) -> dict:
        st = self.stats
        return {
            "num_vertices": self.graph.num_vertices,
            "num_edges": self.graph.num_edges,
            "graph_s": self.graph_s, "partition_s": self.partition_s,
            "localize_s": self.localize_s, "warm_s": self.warm_s,
            "all_to_all_in_program": self.has_all_to_all,
            "scatter_ops": sorted(self.scatter_ops),
            "true_halo_per_iter": st.true_halo_messages_per_iter,
            "padded_halo_per_iter": st.padded_halo_elements_per_iter,
            "max_local_edges": st.max_local_edges,
            "e_max": int(self.engine.lg.e_max),
        }
