"""The harness finds a cell, a traffic mix, a per-layer metric and a kind of
cell (a job with its reference, control and faults) by name: adding files
and entries, and editing none, is enough."""
import json
import os
import shutil
import subprocess
import sys

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


# A new kind of cell, as new files only: a job that subclasses the partition
# job and checks against a reference from a file of its own, the job's
# control and control scale, its faults, and a traffic mix.
NEW_KIND = {
    "bench/probe_reference.py": '''"""The probe kind's reference (added by a test): sequential FENNEL."""
from bench import reference


def fennel(indptr, indices, k, seed, epsilon):
    return reference.fennel(indptr, indices, k, seed, epsilon=epsilon)
''',
    "bench/jobs/probe_kind.py": '''"""A probe kind of cell (added by a test): partition jobs whose check, and
whose control, call a reference from a file of their own."""
import numpy as np

from bench import probe_reference
from bench.jobs import partition

CONTROL_SCALE = 12


class Job(partition.Job):
    def check(self, records):
        g, rec = self.graph, records[-1]
        want = probe_reference.fennel(g.indptr, g.indices, self.k, rec["seed"],
                                      self.config["epsilon"])
        return {"probe_mismatch": {"value": int(np.count_nonzero(want != rec["assignment"])),
                                   "limit": 0}}


def control(config, traffic, indptr, indices, seed):
    """The reference with every vertex moved on by one part."""
    k = int(config["k"])
    want = probe_reference.fennel(indptr, indices, k, partition.job_seed(seed, 1),
                                  config["epsilon"])
    got = (want + 1) % k
    return {"name": "probe_mismatch", "value": int(np.count_nonzero(got != want)), "limit": 0}
''',
    "tests/bench/faults/probe_kind.py": '''FAULTS = {
    "answer_altered": """
import repro.core.base as base, repro.core.fennel as fen
_fin = base.finalize
def finalize(state):
    part = _fin(state)
    part[0] = (part[0] + 1) % state.k
    return part
fen.finalize = finalize
""",
}
''',
    "bench/traffic/probe-kind.json": json.dumps(
        {"job": "probe_kind", "algo": "fennel", "warm_rows": [512, 512], "warm_widths": [8, 1024]}
    ),
}
COPIED = ("bench", "tests/bench")
CACHES = {"__pycache__", ".jax_cache"}


def _files(root):
    """The bytes of every file under the copied directories, caches aside."""
    return {
        p.relative_to(root).as_posix(): p.read_bytes()
        for d in COPIED for p in (root / d).rglob("*")
        if p.is_file() and not CACHES & set(p.relative_to(root).parts)
    }


def _with_new_kind(tmp_path):
    """A checkout of the benchmark with one more kind of cell; returns it and
    the bytes of every file it copied but ``BENCHMARK.json``, which gains
    entries."""
    for d in COPIED:
        shutil.copytree(ROOT / d, tmp_path / d, ignore=shutil.ignore_patterns(*CACHES))
    originals = _files(tmp_path)
    (tmp_path / "src").symlink_to(ROOT / "src")
    for rel, text in NEW_KIND.items():
        (tmp_path / rel).write_text(text)
    b = bench_json()
    b["workloads"].append({"name": "probe.kind", "config": "graph500-s18-k8",
                           "traffic": "probe-kind", "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] in ("partition_rate", "edge_cut"):
            m["workloads"].append("probe.kind")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    return tmp_path, originals


def test_a_new_kind_of_cell_needs_only_new_files(tmp_path):
    root, originals = _with_new_kind(tmp_path)
    # run and checked, against the new reference
    line, err = run_bench(["--workload", "probe.kind", "--seed", str(2**33 + 3),
                           "--seconds", "0.2", "--trace", "0", "--rehearse"], root=root)
    assert line is not None, err[-3000:]
    assert line["correct"] is True and line["attempted"] >= 1
    assert line["checks"] == {"probe_mismatch": {"value": 0, "limit": 0}}
    # controlled and fault-tested: the control and fault tests of the new
    # checkout collect its cases, and they pass
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_") and k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(root / "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rA", "-p", "no:cacheprovider",
         "--rootdir", str(root), "-k", "probe",
         "tests/bench/test_bench_controls.py", "tests/bench/test_bench_faults.py"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout[-3000:]
    for case in ("test_bench_controls.py::test_control_fails_the_limit[probe.kind]",
                 "test_bench_faults.py::test_broken_timed_path_is_not_correct"
                 "[probe.kind-answer_altered]"):
        assert f"PASSED tests/bench/{case}" in proc.stdout, proc.stdout[-3000:]
    # and no file the checkout had was changed
    after = _files(root)
    assert {rel: after.get(rel) for rel in originals} == originals
    assert set(after) - set(originals) == set(NEW_KIND)
