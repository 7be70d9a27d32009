"""Benchmark entry: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV lines; with ``--json`` also collects
every suite's structured rows (built from ``PartitionResult``s in the
api-driven suites) into one machine-readable report - the perf-trajectory
artifact CI uploads.

With ``--baseline`` the run is additionally *gated* against a committed
report (repo-root ``BENCH_partition.json``): stream-phase latency or
edge-cut regressing past ``--tolerance`` (latency optionally loosened via
``--latency-tolerance`` - CI wall clocks are noisy) exits non-zero, so the
perf trajectory is enforced, not just recorded.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only quality,db,...]
        [--json out.json] [--baseline BENCH_partition.json]
        [--tolerance 0.15] [--latency-tolerance 0.75]
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time


def _suite(module: str, kwargs=None):
    """Lazy suite thunk: the module is imported only when the suite actually
    runs, so one broken suite (an import-time failure included) can never
    mask the others - ``--only scaling`` must work even if e.g. the kernels
    suite's imports are broken. ``kwargs`` may be a dict or a zero-arg
    callable returning one (for --full-dependent arguments)."""

    def run_it():
        mod = importlib.import_module(f"benchmarks.{module}")
        kw = kwargs() if callable(kwargs) else (kwargs or {})
        return mod.run(**kw)

    return run_it


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="medium-size datasets (minutes instead of seconds)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="write all suites' structured rows to this file")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="gate this run against a committed report; exits "
                         "non-zero on latency/edge-cut regressions")
    ap.add_argument("--tolerance", type=float, default=0.15,
                    help="allowed relative regression vs the baseline")
    ap.add_argument("--latency-tolerance", type=float, default=None,
                    help="looser bound for wall-clock metrics only "
                         "(default: same as --tolerance)")
    ap.add_argument("--tuning", default=None, metavar="OUT",
                    help="have the scaling suite write its auto-tuning "
                         "artifact (TUNING_partition.json) here")
    args = ap.parse_args()

    from repro.api.result import jsonify
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    suites = {
        "quality": _suite("quality", lambda: dict(
            datasets=["social-s", "web-s", "road-s", "ldbc-s"]
            if not args.full
            else ["social-m", "web-m", "road-m", "ldbc-s"]
        )),
        "quality_vs_k": _suite("quality_vs_k", lambda: dict(
            ks=(2, 4, 8, 16) if not args.full else (2, 4, 8, 16, 32)
        )),
        "imbalance": _suite("imbalance"),
        "ablation": _suite("ablation"),
        "analytics": _suite("analytics"),
        "db": _suite("db"),
        "latency": _suite("latency", lambda: dict(
            dataset="social-s" if not args.full else "social-m"
        )),
        "engine": _suite("engine_compare", lambda: dict(
            n=30_000 if not args.full else 100_000
        )),
        "scaling": _suite("scaling", lambda: dict(
            n=20_000 if not args.full else 100_000,
            tuning_out=args.tuning,
        )),
        "outofcore": _suite("outofcore", lambda: dict(
            n=40_000 if not args.full else 125_000
        )),
        "churn": _suite("churn", lambda: dict(
            n=25_000 if not args.full else 100_000
        )),
        "serving": _suite("serving", lambda: dict(
            n=8_000 if not args.full else 30_000,
            queries=2_000 if not args.full else 6_000,
        )),
        "kernels": _suite("kernels"),
        "substrate": _suite("substrate"),
        "roofline": _suite("roofline"),
    }
    # --only runs suites in the order GIVEN: peak_rss_mb rows report the
    # process-lifetime high-water mark (ru_maxrss cannot be reset), so a
    # memory-measuring suite (outofcore) must be able to run before the
    # allocation-heavy ones (quality loads every dataset) - CI's
    # "scaling,outofcore,serving,quality" relies on this
    if args.only:
        names = args.only.split(",")
        unknown = [n for n in names if n not in suites]
        if unknown:
            ap.error(f"unknown suite(s) {unknown}; known: {sorted(suites)}")
    else:
        names = list(suites)
    report: dict = {"full": args.full, "suites": {}}
    t0 = time.time()
    for name in names:
        fn = suites[name]
        print(f"# === {name} ===", flush=True)
        try:
            rows = fn()
        except Exception as e:  # run the other suites, then exit non-zero
            print(f"{name},0.0,ERROR={type(e).__name__}:{e}", flush=True)
            report["suites"][name] = {"error": f"{type(e).__name__}: {e}"}
        else:
            report["suites"][name] = {"rows": jsonify(rows)}
    report["seconds"] = time.time() - t0
    errored = [n for n, r in report["suites"].items() if "error" in r]
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"# wrote {args.json}", file=sys.stderr)
    print(f"# total {report['seconds']:.1f}s", file=sys.stderr)
    if args.baseline:
        from benchmarks.trajectory import compare_reports

        with open(args.baseline) as f:
            baseline = json.load(f)
        regressions, compared = compare_reports(
            report, baseline, args.tolerance, args.latency_tolerance
        )
        if compared == 0:
            print(
                f"# BENCH GATE FAILED: no comparable rows between this run "
                f"and {args.baseline} - the gate checked nothing",
                file=sys.stderr,
            )
            sys.exit(2)
        for line in regressions:
            print(f"# REGRESSION {line}", file=sys.stderr)
        if regressions:
            print(
                f"# BENCH GATE FAILED: {len(regressions)} regression(s) vs "
                f"{args.baseline} ({compared} metrics compared)",
                file=sys.stderr,
            )
            sys.exit(2)
        print(
            f"# bench gate OK vs {args.baseline} ({compared} metrics compared)",
            file=sys.stderr,
        )
    if errored:
        print(f"# FAILED: suite(s) errored: {','.join(errored)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
