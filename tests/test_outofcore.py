"""Out-of-core graph subsystem tests.

Covers the on-disk format + two-pass converter (``repro.graph.external``),
file-backed vs in-memory partition parity, the API/CLI threading of
``source``/``--graph``/``peak_graph_bytes``, and the bench-trajectory
comparator that gates CI (``benchmarks/trajectory.py``).
"""
from __future__ import annotations

import json
import os
import struct

import numpy as np
import pytest

from repro.api import PartitionSpec, partition
from repro.graph.csr import CSRGraph
from repro.graph.external import (
    FORMAT_VERSION,
    HEADER_BYTES,
    MAGIC,
    ExternalCSRGraph,
    convert_csr,
    convert_edge_list,
    load_graph_file,
    load_graph_source,
    validate_source,
    write_external_csr,
)
from repro.graph.generators import rmat_graph

ORDERS = ("natural", "random", "bfs", "dfs")


@pytest.fixture(scope="module")
def graph():
    return rmat_graph(3000, avg_degree=10, seed=3)


@pytest.fixture(scope="module")
def graph_bin(graph, tmp_path_factory):
    path = tmp_path_factory.mktemp("ooc") / "graph.bin"
    convert_csr(graph, path)
    return str(path)


def _messy_edges(seed=0, n=400, m=4000):
    """Edge list with duplicates in both directions and self-loops."""
    rng = np.random.default_rng(seed)
    edges = rng.integers(0, n, size=(m, 2))
    dupes = edges[::5][:, ::-1]  # reversed duplicates
    loops = np.stack([np.arange(0, n, 7)] * 2, axis=1)
    return np.concatenate([edges, dupes, edges[::11], loops])


# ----------------------------------------------------------- format + reader
class TestFormat:
    def test_write_read_roundtrip(self, graph, graph_bin):
        ext = ExternalCSRGraph(graph_bin)
        assert ext.num_vertices == graph.num_vertices
        assert ext.num_edges == graph.num_edges
        assert np.array_equal(np.asarray(ext.indptr), graph.indptr)
        assert np.array_equal(np.asarray(ext.indices), graph.indices)
        assert np.array_equal(ext.degrees, graph.degrees)
        for v in (0, 1, graph.num_vertices - 1):
            assert np.array_equal(ext.neighbors(v), graph.neighbors(v))
            assert ext.degree(v) == graph.degree(v)

    def test_to_csr_materializes(self, graph, graph_bin):
        back = ExternalCSRGraph(graph_bin).to_csr()
        assert isinstance(back, CSRGraph)
        assert np.array_equal(back.indices, graph.indices)

    def test_memory_accounting(self, graph, graph_bin):
        ext = ExternalCSRGraph(graph_bin)
        assert ext.backing == "mapped"
        assert ext.nbytes_mapped == os.path.getsize(graph_bin)
        assert ext.nbytes_resident == 0  # nothing materialized yet
        _ = ext.degrees
        assert ext.nbytes_resident == ext.degrees.nbytes > 0

    def test_empty_graph(self, tmp_path):
        path = tmp_path / "empty.bin"
        write_external_csr(path, np.zeros(1, dtype=np.int64), np.empty(0, np.int32))
        ext = ExternalCSRGraph(path)
        assert ext.num_vertices == 0 and ext.num_edges == 0


class TestCorruption:
    def test_missing_file(self, tmp_path):
        with pytest.raises(ValueError, match="cannot open"):
            ExternalCSRGraph(tmp_path / "nope.bin")

    def test_too_small_for_header(self, tmp_path):
        path = tmp_path / "tiny.bin"
        path.write_bytes(b"XC")
        with pytest.raises(ValueError, match="smaller than"):
            ExternalCSRGraph(path)

    def test_bad_magic(self, tmp_path):
        path = tmp_path / "bad.bin"
        path.write_bytes(b"NOTAGRPH" + b"\0" * 100)
        with pytest.raises(ValueError, match="bad magic"):
            ExternalCSRGraph(path)

    def test_unsupported_version(self, tmp_path):
        path = tmp_path / "vers.bin"
        head = struct.pack("<8sII qq", MAGIC, FORMAT_VERSION + 9, 0, 0, 0)
        path.write_bytes(head + b"\0" * (HEADER_BYTES - len(head)) + b"\0" * 8)
        with pytest.raises(ValueError, match="version"):
            ExternalCSRGraph(path)

    def test_truncated_file(self, graph, graph_bin, tmp_path):
        data = open(graph_bin, "rb").read()
        path = tmp_path / "trunc.bin"
        path.write_bytes(data[: len(data) // 2])
        with pytest.raises(ValueError, match="truncated or corrupt"):
            ExternalCSRGraph(path)

    def test_trailing_garbage(self, graph_bin, tmp_path):
        data = open(graph_bin, "rb").read()
        path = tmp_path / "fat.bin"
        path.write_bytes(data + b"\0" * 64)
        with pytest.raises(ValueError, match="truncated or corrupt"):
            ExternalCSRGraph(path)

    def test_corrupt_indptr(self, graph, tmp_path):
        path = tmp_path / "badptr.bin"
        bad = graph.indptr.copy()
        bad[-1] += 4  # declares more neighbours than the indices region holds
        bad[0] = 0
        # keep the file size consistent with the header so only the indptr
        # consistency check can catch it
        write_external_csr(path, bad, graph.indices)
        with pytest.raises(ValueError, match="corrupt indptr"):
            ExternalCSRGraph(path)


# ---------------------------------------------------------------- converter
class TestConverter:
    @pytest.mark.parametrize("via", ["npy", "txt", "csv"])
    def test_roundtrip_matches_from_edges(self, tmp_path, via):
        edges = _messy_edges()
        ref = CSRGraph.from_edges(edges, num_vertices=400)
        if via == "npy":
            src = tmp_path / "e.npy"
            np.save(src, edges)
        else:
            sep = "," if via == "csv" else " "
            src = tmp_path / f"e.{via}"
            with open(src, "w") as f:
                f.write("# snap-style header comment\n")
                for a, b in edges:
                    f.write(f"{a}{sep}{b}\n")
        out = tmp_path / "g.bin"
        stats = convert_edge_list(src, out, num_vertices=400)
        ext = ExternalCSRGraph(out)
        assert np.array_equal(np.asarray(ext.indptr), ref.indptr)
        assert np.array_equal(np.asarray(ext.indices), ref.indices)
        assert stats["num_edges"] == ref.num_edges
        assert stats["input_edges"] == edges.shape[0]

    def test_multi_run_external_merge(self, tmp_path):
        # tiny chunk/merge blocks force many spill runs + many merge blocks
        edges = _messy_edges(seed=1, n=300, m=6000)
        ref = CSRGraph.from_edges(edges, num_vertices=300)
        src = tmp_path / "e.npy"
        np.save(src, edges)
        out = tmp_path / "g.bin"
        stats = convert_edge_list(
            src, out, num_vertices=300, chunk_edges=257, merge_block=61
        )
        assert stats["runs"] > 10
        ext = ExternalCSRGraph(out)
        assert np.array_equal(np.asarray(ext.indptr), ref.indptr)
        assert np.array_equal(np.asarray(ext.indices), ref.indices)

    def test_infers_num_vertices(self, tmp_path):
        edges = np.array([[0, 5], [5, 2], [2, 0]])
        src = tmp_path / "e.npy"
        np.save(src, edges)
        out = tmp_path / "g.bin"
        stats = convert_edge_list(src, out)
        assert stats["num_vertices"] == 6  # max id + 1, like from_edges
        assert ExternalCSRGraph(out).num_vertices == 6

    def test_self_loops_and_dupes_dropped(self, tmp_path):
        edges = np.array([[1, 1], [0, 1], [1, 0], [0, 1], [2, 2]])
        src = tmp_path / "e.npy"
        np.save(src, edges)
        out = tmp_path / "g.bin"
        stats = convert_edge_list(src, out, num_vertices=3)
        assert stats["num_edges"] == 1
        ext = ExternalCSRGraph(out)
        assert np.array_equal(ext.neighbors(0), [1])
        assert np.array_equal(ext.neighbors(1), [0])
        assert ext.degree(2) == 0

    def test_extra_columns_ignored(self, tmp_path):
        src = tmp_path / "weighted.txt"
        src.write_text("0 1 0.5\n1 2 0.25\n")
        out = tmp_path / "g.bin"
        assert convert_edge_list(src, out)["num_edges"] == 2

    def test_rejects_negative_ids(self, tmp_path):
        src = tmp_path / "e.npy"
        np.save(src, np.array([[0, 1], [-2, 3]]))
        with pytest.raises(ValueError, match="negative vertex id"):
            convert_edge_list(src, tmp_path / "g.bin")

    def test_rejects_id_beyond_num_vertices(self, tmp_path):
        src = tmp_path / "e.npy"
        np.save(src, np.array([[0, 7]]))
        with pytest.raises(ValueError, match="num_vertices"):
            convert_edge_list(src, tmp_path / "g.bin", num_vertices=5)

    def test_rejects_bad_npy_shape(self, tmp_path):
        src = tmp_path / "e.npy"
        np.save(src, np.arange(10))
        with pytest.raises(ValueError, match="edge array"):
            convert_edge_list(src, tmp_path / "g.bin")


# ------------------------------------------------------------ stream parity
class TestPartitionParity:
    @pytest.mark.parametrize("order", ORDERS)
    @pytest.mark.parametrize("algo", ["fennel", "cuttana"])
    def test_file_backed_bit_identical(self, graph, graph_bin, algo, order):
        ext = ExternalCSRGraph(graph_bin)
        spec = PartitionSpec(
            algo=algo, k=4, balance_mode="edge", order=order, seed=0
        )
        mem = partition(graph, spec)
        mapped = partition(ext, spec)
        assert np.array_equal(mem.assignment, mapped.assignment)

    @pytest.mark.parametrize("num_shards", [1, 4])
    def test_parallel_file_backed_bit_identical(
        self, graph, graph_bin, num_shards
    ):
        ext = ExternalCSRGraph(graph_bin)
        spec = PartitionSpec(
            algo="cuttana-parallel", k=4, balance_mode="edge", order="random",
            seed=0, params={"num_shards": num_shards},
        )
        mem = partition(graph, spec)
        mapped = partition(ext, spec)
        assert np.array_equal(mem.assignment, mapped.assignment)
        assert mapped.telemetry["num_shards"] == num_shards

    @pytest.mark.parametrize("algo", ["hdrf", "ginger"])
    def test_vertex_cut_file_backed_bit_identical(self, graph, graph_bin, algo):
        # the vertex-cut edge partitioners consume edges_array(), which the
        # mapped graph builds with a chunked scan - same edges, same cut
        ext = ExternalCSRGraph(graph_bin)
        assert np.array_equal(ext.edges_array(), graph.edges_array())
        spec = PartitionSpec(algo=algo, k=4, seed=0)
        mem = partition(graph, spec)
        mapped = partition(ext, spec)
        assert np.array_equal(mem.assignment, mapped.assignment)

    def test_subgraph_edge_count_matches(self, graph, graph_bin):
        ext = ExternalCSRGraph(graph_bin)
        mask = np.zeros(graph.num_vertices, dtype=bool)
        mask[::3] = True
        assert ext.subgraph_edge_count(mask) == graph.subgraph_edge_count(mask)

    def test_telemetry_backing_fields(self, graph, graph_bin):
        ext = ExternalCSRGraph(graph_bin)
        spec = PartitionSpec(algo="ldg", k=4, balance_mode="vertex")
        mem = partition(graph, spec)
        mapped = partition(ext, spec)
        assert mem.telemetry["graph_backing"] == "resident"
        assert mem.telemetry["peak_graph_bytes"] == (
            graph.indptr.nbytes + graph.indices.nbytes
        )
        assert mem.telemetry["mapped_graph_bytes"] == 0
        assert mapped.telemetry["graph_backing"] == "mapped"
        assert mapped.telemetry["mapped_graph_bytes"] == os.path.getsize(graph_bin)
        # mapped runs only keep O(|V|) bookkeeping resident
        assert (
            mapped.telemetry["peak_graph_bytes"]
            < mem.telemetry["peak_graph_bytes"]
        )


# ------------------------------------------------------------- spec source
class TestSpecSource:
    def test_source_round_trips_json(self):
        spec = PartitionSpec(
            algo="cuttana", k=4, source="rmat:2000:8", order="random"
        )
        assert PartitionSpec.from_json(spec.to_json()) == spec
        assert json.loads(spec.to_json())["source"] == "rmat:2000:8"

    def test_source_absent_from_json_when_none(self):
        spec = PartitionSpec(algo="fennel", k=2)
        assert "source" not in json.loads(spec.to_json())

    @pytest.mark.parametrize(
        "bad",
        ["", "rmat:", "rmat:0", "rmat:x", "rmat:100:0", "rmat:1:2:3",
         "dataset:no-such-dataset"],
    )
    def test_bad_sources_fail_at_construction(self, bad):
        with pytest.raises(ValueError):
            PartitionSpec(algo="fennel", k=2, source=bad)

    def test_plain_paths_pass_syntax_check(self):
        validate_source("some/dir/graph.bin")
        validate_source("dump.npz")
        # colons are legal in POSIX paths: not a scheme error, fails (with a
        # clear message) only at load time if the file is absent
        validate_source("/data/run:3/graph.bin")
        with pytest.raises(ValueError, match="cannot open"):
            load_graph_source("/data/run:3/graph.bin")

    def test_partition_from_spec_source(self):
        spec = PartitionSpec(
            algo="fennel", k=4, balance_mode="edge", order="random",
            seed=1, source="rmat:1500:8",
        )
        direct = partition(rmat_graph(1500, avg_degree=8, seed=1), spec)
        from_source = partition(spec)  # spec-only convenience form
        assert np.array_equal(direct.assignment, from_source.assignment)

    def test_partition_without_graph_or_source_raises(self):
        with pytest.raises(ValueError, match="needs a graph"):
            partition(PartitionSpec(algo="fennel", k=2))

    def test_load_graph_source_path(self, graph, graph_bin):
        loaded = load_graph_source(graph_bin)
        assert isinstance(loaded, ExternalCSRGraph)
        assert loaded.num_edges == graph.num_edges
        assert isinstance(load_graph_file(graph_bin), ExternalCSRGraph)

    def test_load_graph_file_npz(self, graph, tmp_path):
        path = tmp_path / "dump.npz"
        graph.save(str(path))
        loaded = load_graph_file(str(path))
        assert isinstance(loaded, CSRGraph)
        assert np.array_equal(loaded.indices, graph.indices)

    def test_load_graph_source_dataset(self):
        g = load_graph_source("dataset:road-s")
        assert g.num_vertices == 25_000


# -------------------------------------------------------------------- CLI
class TestCLI:
    def test_partition_graph_flag(self, graph, graph_bin, tmp_path):
        from repro.api.cli import main

        spec_path = tmp_path / "spec.json"
        spec_path.write_text(
            PartitionSpec(
                algo="fennel", k=4, balance_mode="edge", order="random"
            ).to_json()
        )
        out = tmp_path / "report.json"
        assign = tmp_path / "assign.npy"
        rc = main([
            "partition", "--spec", str(spec_path), "--graph", graph_bin,
            "--out", str(out), "--assignment-out", str(assign),
        ])
        assert rc == 0
        report = json.loads(out.read_text())
        assert report["graph"]["name"] == graph_bin
        assert report["graph"]["num_edges"] == graph.num_edges
        assert report["telemetry"]["graph_backing"] == "mapped"
        mem = partition(
            graph, PartitionSpec(
                algo="fennel", k=4, balance_mode="edge", order="random"
            )
        )
        assert np.array_equal(np.load(assign), mem.assignment)

    def test_spec_source_seed_matches_api(self, tmp_path):
        # the same spec JSON must mean the same graph through the CLI and
        # through repro.api.partition(spec): both resolve source with spec.seed
        from repro.api.cli import main

        spec = PartitionSpec(
            algo="fennel", k=4, balance_mode="edge", order="random",
            seed=3, source="rmat:1500:8",
        )
        spec_path = tmp_path / "spec.json"
        spec_path.write_text(spec.to_json())
        assign = tmp_path / "a.npy"
        assert main([
            "partition", "--spec", str(spec_path), "--out", "/dev/null",
            "--assignment-out", str(assign),
        ]) == 0
        assert np.array_equal(np.load(assign), partition(spec).assignment)

    def test_graph_flag_is_file_only(self, tmp_path):
        from repro.api.cli import main

        spec_path = tmp_path / "spec.json"
        spec_path.write_text(PartitionSpec(algo="fennel", k=2,
                                           balance_mode="vertex").to_json())
        with pytest.raises(ValueError, match="cannot open"):
            main(["partition", "--spec", str(spec_path), "--graph",
                  "rmat:5000", "--out", "/dev/null"])

    def test_skip_quality_flag(self, graph_bin, tmp_path):
        from repro.api.cli import main

        spec_path = tmp_path / "spec.json"
        spec_path.write_text(PartitionSpec(algo="fennel", k=4,
                                           balance_mode="edge").to_json())
        out = tmp_path / "report.json"
        assert main(["partition", "--spec", str(spec_path), "--graph",
                     graph_bin, "--out", str(out), "--skip-quality"]) == 0
        report = json.loads(out.read_text())
        assert "quality" not in report
        assert report["telemetry"]["graph_backing"] == "mapped"

    def test_convert_script(self, tmp_path):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "convert_graph",
            os.path.join(os.path.dirname(__file__), "..", "scripts",
                         "convert_graph.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        edges = _messy_edges(seed=5, n=200, m=1500)
        src = tmp_path / "e.txt"
        with open(src, "w") as f:
            for a, b in edges:
                f.write(f"{a}\t{b}\n")
        out = tmp_path / "g.bin"
        assert mod.main([str(src), str(out), "--num-vertices", "200"]) == 0
        ref = CSRGraph.from_edges(edges, num_vertices=200)
        ext = ExternalCSRGraph(out)
        assert np.array_equal(np.asarray(ext.indices), ref.indices)


# ------------------------------------------------- bench-trajectory gate
def _report(stream_s=1.0, edge_cut=0.5, convert_s=0.2):
    return {
        "suites": {
            "outofcore": {"rows": [
                {"bench": "outofcore/rmat1000/convert",
                 "convert_seconds": convert_s},
                {"bench": "outofcore/rmat1000/cuttana/mapped",
                 "algo": "cuttana", "backing": "mapped",
                 "stream_seconds": stream_s, "edge_cut": edge_cut},
            ]},
            "scaling": {"rows": [
                {"algo": "cuttana", "num_shards": 0,
                 "stream_seconds": stream_s, "edge_cut": edge_cut},
            ]},
        },
    }


class TestTrajectoryGate:
    def test_identical_reports_pass(self):
        from benchmarks.trajectory import compare_reports

        regs, compared = compare_reports(_report(), _report(), tolerance=0.15)
        assert regs == []
        assert compared == 5  # 2x(stream+cut) + convert

    def test_within_tolerance_passes(self):
        from benchmarks.trajectory import compare_reports

        cur = _report(stream_s=1.10, edge_cut=0.55)
        regs, _ = compare_reports(cur, _report(), tolerance=0.15)
        assert regs == []

    def test_injected_2x_latency_regression_fails(self):
        from benchmarks.trajectory import compare_reports

        cur = _report(stream_s=2.0)  # the acceptance-criteria scenario
        regs, _ = compare_reports(cur, _report(), tolerance=0.15)
        assert len(regs) == 2  # both stream_seconds rows
        assert all("stream_seconds regressed 2.00x" in r for r in regs)

    def test_latency_tolerance_loosens_only_latency(self):
        from benchmarks.trajectory import compare_reports

        cur = _report(stream_s=1.5, edge_cut=0.65)
        regs, _ = compare_reports(
            cur, _report(), tolerance=0.15, latency_tolerance=0.75
        )
        # 1.5x latency allowed at +75%; 1.3x edge-cut still fails at +15%
        assert len(regs) == 2
        assert all("edge_cut" in r for r in regs)

    def test_edge_cut_regression_fails(self):
        from benchmarks.trajectory import compare_reports

        cur = _report(edge_cut=0.60)
        regs, _ = compare_reports(cur, _report(), tolerance=0.15)
        assert any("edge_cut" in r for r in regs)

    def test_missing_row_in_run_suite_is_regression(self):
        from benchmarks.trajectory import compare_reports

        cur = _report()
        del cur["suites"]["outofcore"]["rows"][1]
        regs, _ = compare_reports(cur, _report(), tolerance=0.15)
        assert any("missing from this run" in r for r in regs)

    def test_suites_not_run_are_out_of_scope(self):
        from benchmarks.trajectory import compare_reports

        cur = _report()
        del cur["suites"]["scaling"]  # e.g. --only outofcore
        regs, compared = compare_reports(cur, _report(), tolerance=0.15)
        assert regs == []
        assert compared == 3

    def test_zero_overlap_reports_zero_compared(self):
        from benchmarks.trajectory import compare_reports

        regs, compared = compare_reports(
            {"suites": {"other": {"rows": []}}}, _report()
        )
        assert compared == 0  # run.py fails the gate on this

    def test_seeded_baseline_gates_green_against_itself(self):
        # the committed repo-root baseline must be self-consistent: a run
        # identical to it passes, an injected 2x latency on every row fails
        from benchmarks.trajectory import compare_reports

        path = os.path.join(os.path.dirname(__file__), "..",
                            "BENCH_partition.json")
        baseline = json.load(open(path))
        regs, compared = compare_reports(baseline, baseline, tolerance=0.15)
        assert regs == [] and compared > 0
        doctored = json.loads(json.dumps(baseline))
        for payload in doctored["suites"].values():
            for row in payload.get("rows", []):
                if "stream_seconds" in row:
                    row["stream_seconds"] *= 2.0
        regs, _ = compare_reports(
            doctored, baseline, tolerance=0.15, latency_tolerance=0.75
        )
        assert any("stream_seconds" in r for r in regs)


def test_nightly_conversion_child_never_initialises_jax(tmp_path):
    """scripts/quality_nightly.py runs ``fetch_dataset.py --convert`` in a
    child process after the parent has partitioned, i.e. while the parent
    holds the accelerator. The child must not initialise a JAX backend: a
    second process that reaches for the chip fails or hangs. Here the child
    sees a platform that does not exist, so any backend start fails it."""
    import gzip
    import subprocess
    import sys

    g = rmat_graph(400, avg_degree=6, seed=2)
    edges_gz = tmp_path / "edges.txt.gz"
    with gzip.open(edges_gz, "wt") as fh:
        np.savetxt(fh, g.edges_array(), fmt="%d")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run(
        [
            sys.executable, "scripts/fetch_dataset.py",
            "--url", edges_gz.resolve().as_uri(), "--name", "child",
            "--cache-dir", str(tmp_path / "cache"),
            "--convert", str(tmp_path / "g.bin"),
        ],
        capture_output=True, text=True, cwd=repo, timeout=120,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "no_such_platform"},
    )
    assert res.returncode == 0, res.stderr
    assert ExternalCSRGraph(tmp_path / "g.bin").num_edges == g.num_edges
