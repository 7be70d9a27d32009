"""Helpers shared by the benchmark's tests: run ``bench.run`` in a child
process and read its result line, and find each cell's fault cases."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
# the benchmark's own modules (``bench.*``) are imported from the repo root
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def run_bench(args: list[str], root: Path = ROOT, prelude: str = "",
              timeout: float = 300) -> tuple[dict | None, str]:
    """Run ``bench.run`` with ``args`` from ``root`` in a child process (the
    platform and the virtual device count are set before JAX starts).
    ``prelude`` is Python run first, to break the system under test. Returns
    the parsed last stdout line (None when there is none) and stderr."""
    code = (
        "import sys\n"
        f"sys.path[:0] = [{str(root)!r}, {str(root / 'src')!r}]\n"
        f"{prelude}\n"
        "from bench.run import main\n"
        f"raise SystemExit(main({args!r}))\n"
    )
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS",)}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=root, env=env, capture_output=True,
        text=True, timeout=timeout,
    )
    lines = proc.stdout.strip().splitlines()
    line = json.loads(lines[-1]) if proc.returncode == 0 and lines else None
    return line, proc.stderr


def bench_json(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


# The window of a fault case's run holds exactly two jobs, however long a
# job takes on a loaded runner: the window's clock stands still until the
# second window job has ended, then jumps past the window's end.
TWO_JOB_WINDOW = """
import types
import bench.run as run
done = [0]
_load = run.load_module
def load_module(path, name):
    mod = _load(path, name)
    if name.startswith("bench_job_"):
        one = mod.Job.run_one
        def run_one(self, index):
            out = one(self, index)
            done[0] += 1
            return out
        mod.Job.run_one = run_one
    return mod
run.load_module = load_module
run.time = types.SimpleNamespace(perf_counter=lambda: 0.0 if done[0] < 2 else 1e6)
"""


def run_fault(cell: str, prelude: str, root: Path = ROOT) -> tuple[dict | None, str]:
    """Rehearse ``cell`` with a two-job window after ``prelude`` broke the
    system under test."""
    return run_bench(
        ["--workload", cell, "--seed", "424242", "--seconds", "1", "--trace", "0",
         "--rehearse"], root=root, prelude=prelude + TWO_JOB_WINDOW,
    )


def fault_cases(root: Path = ROOT) -> list[tuple[str, str, str]]:
    """``(cell, fault, prelude)`` for every cell of ``BENCHMARK.json`` and
    every fault of its job, from ``tests/bench/faults/<job>.py``."""
    from bench.run import Cell, load_module

    cases = []
    for w in bench_json(root)["workloads"]:
        job = Cell.load(w["name"], root).traffic["job"]
        faults = load_module(root / "tests" / "bench" / "faults" / f"{job}.py",
                             f"bench_faults_{job}").FAULTS
        cases += [(w["name"], fault, prelude) for fault, prelude in faults.items()]
    return cases
