"""Out-of-core parity + latency study: partition from disk, bit-identically.

Generates one R-MAT, dumps it as a binary edge list, converts it to the
on-disk external CSR format (``repro.graph.external``), and partitions the
*same* graph twice per algorithm: once fully resident (``CSRGraph``), once
memory-mapped (``ExternalCSRGraph``). Assignments must be **bit-identical**
(the file-backed stream feeds the identical engine loops); the rows report
the stream-phase latency of both paths, the mapped-vs-resident graph bytes
from ``PartitionResult`` telemetry, and the process peak RSS - the
bench-trajectory gate (``benchmarks/run.py --baseline``) tracks the latency
columns across PRs.

Gated trajectory columns beyond the classic latency/quality pair:

* ``bytes_on_disk`` - the converted (v2 block-compressed) file size; a codec
  change that bloats the on-disk CSR fails the gate;
* ``peak_rss_mb`` - process high-water RSS per row; a streaming change that
  re-materializes the mapped graph in RAM fails the gate;
* ``superstep_ms`` - mean per-superstep wall of the sharded engine, from
  ``telemetry["profile"]``;
* the sharded algorithm additionally runs the mapped graph with
  ``prefetch="off"`` (``.../mapped-sync``): the decode-ahead pipeline must
  keep the default mapped row at-or-under its own baseline while the sync
  row documents what the prefetcher buys (assignments stay bit-identical
  across all three runs).
"""
from __future__ import annotations

import os
import tempfile
import time

import numpy as np

from benchmarks.common import emit
from repro.api import PartitionSpec, partition
from repro.graph.external import ExternalCSRGraph, convert_edge_list
from repro.graph.generators import rmat_graph

ALGOS = (
    ("fennel", None),
    ("cuttana", None),
    ("cuttana-parallel", {"num_shards": 4}),
)


def _peak_rss_bytes() -> int:
    """Process high-water RSS. Monotone within the process, so per-row
    values only bound the true footprint of a single run from above."""
    try:
        import resource

        rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return int(rss_kb) * 1024
    except Exception:  # pragma: no cover - non-POSIX fallback
        return 0


def _stream_seconds(result) -> float:
    t = result.timings
    return t.get("phase1_seconds", t.get("stream_seconds", t["total_s"]))


def _superstep_ms(result) -> float | None:
    """Mean per-superstep placement wall (the shard-task fan-out and join)
    from the sharded-engine profile, or None."""
    prof = result.telemetry.get("profile")
    if not isinstance(prof, dict) or not prof.get("supersteps"):
        return None
    return float(prof["place_s"]) / int(prof["supersteps"]) * 1e3


def run(n: int = 40_000, avg_degree: int = 12, k: int = 8, seed: int = 0):
    graph = rmat_graph(n, avg_degree=avg_degree, seed=seed)
    rows = []
    with tempfile.TemporaryDirectory() as td:
        edges_path = os.path.join(td, "edges.npy")
        np.save(edges_path, graph.edges_array())
        bin_path = os.path.join(td, "graph.bin")
        t0 = time.perf_counter()
        stats = convert_edge_list(edges_path, bin_path, num_vertices=n)
        convert_s = time.perf_counter() - t0
        ext = ExternalCSRGraph(bin_path)
        if not np.array_equal(np.asarray(ext.indptr), graph.indptr) or not (
            np.array_equal(np.asarray(ext.indices), graph.indices)
        ):
            raise AssertionError("converted CSR differs from the in-memory build")
        rows.append(dict(
            bench=f"outofcore/rmat{n}/convert", convert_seconds=convert_s,
            file_bytes=stats["file_bytes"], num_edges=stats["num_edges"],
            bytes_on_disk=stats["file_bytes"],
            raw_bytes=stats.get("raw_bytes"),
            compression_ratio=stats.get("compression_ratio"),
            format_version=stats["format_version"],
            peak_rss_mb=_peak_rss_bytes() / 2**20,
        ))
        emit(f"outofcore/rmat{n}/convert", convert_s * 1e6,
             f"file_bytes={stats['file_bytes']}")

        for algo, params in ALGOS:
            spec = PartitionSpec(
                algo=algo, k=k, balance_mode="edge", order="random",
                seed=seed, params=params,
            )
            variants = [("resident", graph, spec), ("mapped", ext, spec)]
            if params and "num_shards" in params:
                # the sharded engine also runs the mapped graph with the
                # decode-ahead pipeline forced off: the synchronous baseline
                # the prefetcher must beat (assignments stay bit-identical)
                sync_spec = spec.replace(
                    params={**params, "prefetch": "off"}
                )
                variants.append(("mapped-sync", ext, sync_spec))
            results = {}
            for backing, g, vspec in variants:
                result = partition(g, vspec)
                results[backing] = result
                secs = _stream_seconds(result)
                tel = result.telemetry
                row = dict(
                    bench=f"outofcore/rmat{n}/{algo}/{backing}",
                    algo=algo, backing=backing, stream_seconds=secs,
                    total_seconds=result.timings["total_s"],
                    edge_cut=result.quality()["edge_cut"],
                    peak_graph_bytes=tel["peak_graph_bytes"],
                    mapped_graph_bytes=tel["mapped_graph_bytes"],
                    compressed_graph_bytes=tel.get("compressed_graph_bytes", 0),
                    peak_rss_bytes=_peak_rss_bytes(),
                    peak_rss_mb=_peak_rss_bytes() / 2**20,
                    spec=vspec.to_dict(),
                )
                if backing != "resident":
                    row["bytes_on_disk"] = stats["file_bytes"]
                for key in ("prefetch_hit_rate", "decode_wall_s",
                            "prefetch_wait_s"):
                    if key in tel:
                        row[key] = tel[key]
                sstep = _superstep_ms(result)
                if sstep is not None:
                    row["superstep_ms"] = sstep
                rows.append(row)
                emit(
                    f"outofcore/rmat{n}/{algo}/{backing}", secs * 1e6,
                    f"graph_bytes={tel['peak_graph_bytes']};"
                    f"rss={_peak_rss_bytes()}",
                )
            for backing in results:
                if backing == "resident":
                    continue
                if not np.array_equal(
                    results["resident"].assignment, results[backing].assignment
                ):
                    raise AssertionError(
                        f"{algo}/{backing}: file-backed assignments differ "
                        f"from in-memory"
                    )
    return rows


if __name__ == "__main__":
    run()
