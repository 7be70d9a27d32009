"""Run one benchmark cell once and print its result line.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell, its configuration and its
metrics come from ``BENCHMARK.json``; everything else is found by name:

* ``bench/configs/<config>.json`` (the file ``BENCHMARK.json`` names) - the
  deployment: graph, ``k``, balance, guarantees;
* ``bench/traffic/<traffic>.json`` - the job mix; its ``job`` key names
* ``bench/jobs/<job>.py`` - a ``Job`` class that sets the cell up, runs one
  job, reports the end-to-end metrics and checks the answers, beside the
  job's control (``control`` and ``CONTROL_SCALE``, see ``bench.control``);
* ``bench/metrics/<name>.py`` - a ``read(run)`` function per per-layer
  metric, which returns the metric or None where it finds nothing to read.

A run generates the graph from ``--seed`` (or the fixed instance its
configuration names), sets up and warms the cell's shapes (all of it
``setup_s``), then runs whole jobs back to back, starting
a new one only while ``--seconds`` have not yet passed. With ``--trace 1``
the window runs under the JAX profiler and the per-layer metrics are
printed instead of the end-to-end ones. After the window the answers are
checked against the plain references in ``bench/reference.py``; each number
compared is printed beside its limit, last on standard error and last in
the result line.

Without a TPU, or with fewer chips than the cell asks for, the run fails
and prints no result. ``--rehearse`` runs on the CPU instead, at a tiny
scale with the Pallas kernels in interpret mode; it exists for the tests.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

T_IMPORT = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
REHEARSE_SCALE = 12


def _process_age_s() -> float:
    """Seconds since this process started (Linux), else since this module
    was imported."""
    try:
        ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return uptime - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_IMPORT


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny scale, kernels in interpret mode (tests only)")
    return ap.parse_args(argv)


def load_module(path: Path, name: str):
    """Import a file of the benchmark by path (its name may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def job_module(traffic: dict):
    """The job module a traffic mix names by its ``job``."""
    job = traffic["job"]
    return load_module(BENCH / "jobs" / f"{job}.py", f"bench_job_{job}")


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, resolved."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @classmethod
    def load(cls, name: str, root: Path = ROOT) -> "Cell":
        bench = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        w = cells[name]
        conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
        config = json.loads((root / conf["file"]).read_text())
        traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())

        def applies(m):
            return name in m["workloads"] if "workloads" in m else None

        e2e = [m for m in bench["end_to_end"] if applies(m) is not False]
        reported = {m["name"] for m in e2e}
        layer = [
            m for m in bench["per_layer"]
            if applies(m) or ("workloads" not in m and m["moves"] in reported)
        ]
        return cls(name, int(w["chips"]), config, traffic, e2e, layer)


class CompileMeter:
    """Running totals of XLA compiles and persistent-cache hits, read
    through ``jax.monitoring``."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits}


@dataclasses.dataclass
class Run:
    """What a per-layer reader may read: the job (graph sizes, program
    counters), the window's job records, and the reduced trace."""

    job: object
    records: list
    trace: object
    device_kind: str


def _setup_jax(args, chips: int):
    """Place the compile cache, pick the platform, and check the chips."""
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}"
            )
    import jax

    # one fixed directory inside the checkout unless the caller names one:
    # the path is part of the cache key
    jax.config.update(
        "jax_compilation_cache_dir",
        os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache"),
    )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    backend = jax.default_backend()
    if backend != "tpu" and not args.rehearse:
        raise SystemExit(f"error: JAX finds no TPU (backend {backend!r})")
    if len(jax.devices()) < chips:
        raise SystemExit(f"error: the cell needs {chips} chips, JAX sees {len(jax.devices())}")
    return jax


def _memory_peak(jax, devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


def main(argv=None) -> int:
    args = parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"error: no system under test at {ROOT / 'src' / 'repro'}", file=sys.stderr)
        return 2
    cell = Cell.load(args.workload)
    jax = _setup_jax(args, cell.chips)
    sys.path.insert(0, str(ROOT / "src"))
    from jax import profiler

    from bench.trace import load as load_trace

    meter = CompileMeter(jax)
    job_mod = job_module(cell.traffic)
    config = dict(cell.config)
    if args.rehearse:
        config["scale"] = min(config["scale"], REHEARSE_SCALE)
    job = job_mod.Job(config, cell.traffic, args.seed, rehearse=args.rehearse)
    with profiler.TraceAnnotation("bench.setup"):
        job.setup()
    setup_s = _process_age_s()
    before = meter.snapshot()

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = profiler.ProfileOptions()
        opts.python_tracer_level = 0
        profiler.start_trace(trace_dir, profiler_options=opts)
    records, job_s = [], []
    with profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while not records or time.perf_counter() - t0 < args.seconds:
            with profiler.TraceAnnotation("bench.job"):
                records.append(job.run_one(len(records)))
            job_s.append(time.perf_counter() - t0 - sum(job_s))
        window_s = time.perf_counter() - t0
    if args.trace:
        profiler.stop_trace()
    after = meter.snapshot()
    in_window = {k: after[k] - before[k] for k in after}

    devices = job.devices()
    memory_peak = _memory_peak(jax, devices)
    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}

    metrics: dict = {}
    breakdown = None
    if args.trace:
        trace = load_trace(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = trace.mean_busy_s()
        device["window_s"] = trace.window_s()
        breakdown = {"device_ops": trace.top_ops(), "idle_gaps": trace.idle_gaps()}
        run = Run(job=job, records=records, trace=trace, device_kind=dev0.device_kind)
        for m in cell.per_layer:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py", f"bench_metric_{m['name']}")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = job.end_to_end(records, window_s)
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    with profiler.TraceAnnotation("bench.check"):
        failed = job.failed(records)
        checks = job.check(records)
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in checks.values())

    info = {"setup_s": setup_s, "window_s": window_s, "jobs": len(records),
            "job_s": [round(t, 4) for t in job_s],
            "window_compile": in_window, "setup_compile": before, **job.info(records)}
    print(json.dumps({"info": info}, default=float), file=sys.stderr)
    for name, c in checks.items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} {verdict}", file=sys.stderr)
    line = {"correct": bool(correct), "attempted": len(records), "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(line, default=float), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
