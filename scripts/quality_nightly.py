"""Nightly (non-gating) quality run on larger graphs.

The gated CI quality rows run on the small seeded datasets; this script is
the scheduled, non-gating companion that runs the same quality matrix on

1. a larger R-MAT than the gated suite ever sees (``--rmat-n``, default
   120k vertices), and
2. a graph pulled through the real dataset pipeline - a synthetic
   SNAP-style ``.txt.gz`` edge list served over ``file://`` into
   ``scripts/fetch_dataset.py`` (hermetic: no network on the critical
   path), converted to the compressed external CSR, and partitioned
   memory-mapped

and writes a JSON report for CI to upload as an artifact. It is the first
step toward the LiveJournal-scale run in ROADMAP: swap the synthetic
``file://`` source for a registered SNAP dataset URL once runners are
allowed to download one.

    PYTHONPATH=src python scripts/quality_nightly.py --out quality_nightly.json
"""
from __future__ import annotations

import argparse
import gzip
import json
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.api import PartitionSpec, partition  # noqa: E402
from repro.graph.generators import powerlaw_cluster_graph, rmat_graph  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
PARTITIONERS = [
    "cuttana", "cuttana-buffcut", "cluster+cuttana", "fennel", "ldg",
]


def quality_cells(tag: str, graph, k: int, seed: int) -> list[dict]:
    rows = []
    for balance in ("edge", "vertex"):
        for name in PARTITIONERS:
            spec = PartitionSpec(
                algo=name, k=k, epsilon=0.05, balance_mode=balance,
                order="random", seed=seed,
            )
            t0 = time.perf_counter()
            result = partition(graph, spec)
            rep = result.quality()
            row = dict(
                bench=f"quality-nightly/{tag}/{balance}/{name}",
                graph=tag, balance=balance, algo=name,
                seconds=time.perf_counter() - t0, **rep,
            )
            rows.append(row)
            print(
                f"{row['bench']:55s} ec={rep['edge_cut']:.4f} "
                f"cv={rep['comm_volume']:.4f} {row['seconds']:.1f}s",
                flush=True,
            )
    return rows


def fetched_file_graph(workdir: Path, n: int, seed: int):
    """Synthetic SNAP-style edge list through the real fetch -> convert ->
    mmap pipeline (file:// source, so the run is hermetic)."""
    from repro.graph.external import ExternalCSRGraph

    edges_gz = workdir / "nightly-edges.txt.gz"
    g = powerlaw_cluster_graph(n, avg_degree=14, seed=seed)
    with gzip.open(edges_gz, "wt") as fh:
        fh.write("# synthetic SNAP-style edge list (nightly)\n")
        np.savetxt(fh, g.edges_array(), fmt="%d")
    bin_path = workdir / "nightly.bin"
    # the child only converts and initialises no JAX backend, so it never
    # competes for the accelerator this process holds after partitioning
    # (pinned by tests/test_outofcore.py)
    subprocess.run(
        [
            sys.executable, str(REPO / "scripts" / "fetch_dataset.py"),
            "--url", edges_gz.resolve().as_uri(), "--name", "nightly-web",
            "--cache-dir", str(workdir / "cache"),
            "--convert", str(bin_path),
        ],
        check=True,
        env={**__import__("os").environ, "PYTHONPATH": str(REPO / "src")},
    )
    return ExternalCSRGraph(bin_path)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="quality_nightly.json")
    ap.add_argument("--rmat-n", type=int, default=120_000)
    ap.add_argument("--avg-degree", type=int, default=14)
    ap.add_argument("--file-n", type=int, default=60_000,
                    help="vertex count of the file://-pipeline graph")
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workdir", default="/tmp/quality-nightly")
    args = ap.parse_args()

    workdir = Path(args.workdir)
    workdir.mkdir(parents=True, exist_ok=True)
    rows: list[dict] = []

    g = rmat_graph(args.rmat_n, avg_degree=args.avg_degree, seed=args.seed)
    print(f"rmat-l: {g.num_vertices} vertices, {g.num_edges} edges", flush=True)
    rows += quality_cells("rmat-l", g, args.k, args.seed)

    gf = fetched_file_graph(workdir, args.file_n, args.seed)
    print(f"web-file: {gf.num_vertices} vertices, {gf.num_edges} edges",
          flush=True)
    rows += quality_cells("web-file", gf, args.k, args.seed)

    report = {
        "suites": {"quality-nightly": {"rows": rows}},
        "config": {
            "rmat_n": args.rmat_n, "avg_degree": args.avg_degree,
            "file_n": args.file_n, "k": args.k, "seed": args.seed,
        },
    }
    Path(args.out).write_text(json.dumps(report, indent=1, sort_keys=True))
    print(f"wrote {args.out} ({len(rows)} rows)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
