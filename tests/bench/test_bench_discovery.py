"""The harness finds a cell, a traffic mix and a per-layer metric by name:
adding files and entries, and editing none, is enough."""
import json
import shutil

from bench_util import ROOT, bench_json, run_bench
from bench.run import Cell

PROBE_METRIC = '''"""Jobs in the window (a probe added by a test)."""


def read(run):
    return float(len(run.records))
'''


def _with_probe(tmp_path):
    """A checkout with one more traffic mix, cell and per-layer metric."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    (tmp_path / "src").symlink_to(ROOT / "src")
    traffic = json.loads((ROOT / "bench" / "traffic" / "fennel.json").read_text())
    traffic["params"] = {"chunk": 256}
    (tmp_path / "bench" / "traffic" / "fennel-c256.json").write_text(json.dumps(traffic))
    (tmp_path / "bench" / "metrics" / "probe_jobs.py").write_text(PROBE_METRIC)
    b = bench_json()
    b["workloads"].append({"name": "probe.cell", "config": "graph500-s18-k8",
                           "traffic": "fennel-c256", "chips": 1, "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if m["name"] in ("partition_rate", "edge_cut", "score_launches"):
            m["workloads"].append("probe.cell")
    b["per_layer"].append({"name": "probe_jobs", "unit": "jobs", "better": "higher",
                           "source": "program_counter", "layer": "harness",
                           "moves": "partition_rate", "workloads": ["probe.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    return tmp_path


def test_added_cell_and_metric_are_picked_up(tmp_path):
    root = _with_probe(tmp_path)
    for trace, want in ((0, {"partition_rate", "edge_cut", "setup_s"}),
                        (1, {"score_launches", "probe_jobs"})):
        line, err = run_bench(
            ["--workload", "probe.cell", "--seed", "99", "--seconds", "0.2",
             "--trace", str(trace), "--rehearse"], root=root,
        )
        assert line is not None, err[-3000:]
        assert line["correct"] is True
        assert set(line["metrics"]) == want
    # the chunk of 256 from the new traffic file reached the system
    assert line["metrics"]["score_launches"]["value"] == 4096 / 256


def test_metric_without_workloads_follows_the_metric_it_moves(tmp_path):
    b = bench_json()
    b["per_layer"].append({"name": "every_pagerank", "unit": "x", "better": "lower",
                           "source": "program_counter", "layer": "x",
                           "moves": "pagerank_iter_ms"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    (tmp_path / "bench").symlink_to(ROOT / "bench")
    names = {c: {m["name"] for m in Cell.load(c, root=tmp_path).per_layer}
             for c in [w["name"] for w in b["workloads"]]}
    assert "every_pagerank" in names["g500-s20-k4.pagerank-mesh"]
    assert "every_pagerank" not in names["g500-s19-k8.fennel"]
