"""Helpers shared by the benchmark's tests: run ``bench.run`` in a child
process and read its result line."""
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
