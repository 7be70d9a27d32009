"""Controls: the plain reference put in the system's place with one
guarantee broken, which the check of a cell has to refuse.

Each job module (``bench/jobs/<job>.py``) owns its control: a function
``control(config, traffic, indptr, indices, seed)`` that returns the
number's name, the control's reading and the limit the job's check holds it
to, and ``CONTROL_SCALE``, the scale at which the tests read it. The
module's docstring says which guarantee its control breaks.

    python -m bench.control --workload <cell> --seeds 1,2,3

prints one JSON line per seed with the control's reading, at the cell's
own size. It is not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys

from bench.graph500 import graph500_csr
from bench.run import ROOT, Cell, job_module


def reading(config: dict, traffic: dict, seed: int) -> dict:
    """The control's ``{"name", "value", "limit"}`` for one seed at the
    config's size."""
    c = config
    # a configuration that fixes its instance draws nothing from the seed,
    # as its job does
    label = int(c.get("instance_seed", seed))
    indptr, indices = graph500_csr(
        c["graph_seed"], label, c["scale"], c["edge_factor"], c["a"], c["b"], c["c"]
    )
    return job_module(traffic).control(c, traffic, indptr, indices, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    cell = Cell.load(args.workload)
    config = cell.config
    for seed in (int(s) for s in args.seeds.split(",")):
        out = reading(config, cell.traffic, seed)
        print(json.dumps({"workload": cell.name, "seed": seed, "scale": config["scale"],
                          "control": out["name"], "value": out["value"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
