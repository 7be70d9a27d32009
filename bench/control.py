"""Controls: the plain reference put in the system's place with one
guarantee broken, which the check of a cell has to refuse.

* Partition cells: the reference with neighbour histograms that count at
  most the first 1024 neighbours of a vertex (the score kernel's widest
  launch), breaking "Eq. 7 histograms count every assigned neighbour".
  Read as the vertices whose part differs from the reference's.
* The PageRank cell: the reference computed in bfloat16, the precision
  below the float32 state the configuration states. Read as the largest
  relative error against the float64 reference.

    python -m bench.control --workload <cell> --seeds 1,2,3

prints one JSON line per seed with the control's reading and the cell's
limit, at the cell's own size. It is not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from bench import reference
from bench.graph500 import graph500_csr
from bench.jobs.partition import job_seed

NBR_CAP = 1024


def pagerank_bf16(indptr, indices, iters: int, damping: float) -> np.ndarray:
    """The reference's power iteration with every value and sum in bfloat16."""
    import jax
    import jax.numpy as jnp

    n = indptr.shape[0] - 1
    bf = jnp.bfloat16
    deg = np.diff(indptr)
    src = jnp.asarray(np.repeat(np.arange(n, dtype=np.int32), deg))
    dst = jnp.asarray(indices)
    inv_deg = jnp.asarray(1.0 / np.maximum(deg, 1), dtype=bf)

    @jax.jit
    def run(x, src, dst, inv_deg):
        def step(_, x):
            agg = jax.ops.segment_sum((x * inv_deg)[dst], src, num_segments=n)
            return bf((1.0 - damping) / n) + bf(damping) * agg
        return jax.lax.fori_loop(0, iters, step, x)

    x = run(jnp.full(n, 1.0 / n, dtype=bf), src, dst, inv_deg)
    return np.asarray(x, dtype=np.float64)


def partition_reading(config, traffic, indptr, indices, seed: int) -> int:
    """Vertices placed differently by the capped control and the reference,
    for window job 1 of a run with ``seed``."""
    fn = getattr(reference, traffic["reference"])
    args = dict(epsilon=config["epsilon"], **traffic.get("reference_args", {}))
    k, s = int(config["k"]), job_seed(seed, 1)
    want = fn(indptr, indices, k, s, **args)
    got = fn(indptr, indices, k, s, nbr_cap=NBR_CAP, **args)
    return int(np.count_nonzero(got != want))


def pagerank_reading(config, indptr, indices) -> float:
    iters, damping = int(config["pagerank_iters"]), float(config["damping"])
    want = reference.pagerank(indptr, indices, iters, damping)
    got = pagerank_bf16(indptr, indices, iters, damping)
    return float(np.max(np.abs(got - want) / want))


def reading(config: dict, traffic: dict, seed: int) -> tuple[str, float]:
    """``(number, control's reading)`` for one seed at the config's size."""
    c = config
    # a configuration that fixes its instance draws nothing from the seed,
    # as its job does
    label = int(c.get("instance_seed", seed))
    indptr, indices = graph500_csr(
        c["graph_seed"], label, c["scale"], c["edge_factor"], c["a"], c["b"], c["c"]
    )
    if traffic["job"] == "partition":
        return "mismatched_vertices", partition_reading(c, traffic, indptr, indices, seed)
    return "max_rel_err", pagerank_reading(c, indptr, indices)


def main(argv=None) -> int:
    from bench.run import ROOT, Cell

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    cell = Cell.load(args.workload)
    config = cell.config
    for seed in (int(s) for s in args.seeds.split(",")):
        name, value = reading(config, cell.traffic, seed)
        print(json.dumps({"workload": cell.name, "seed": seed, "scale": config["scale"],
                          "control": name, "value": value}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
