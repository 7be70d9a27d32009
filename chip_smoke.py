"""On-chip smoke check: the streaming partitioner and the vertex-program
engine, driven once on a TPU through the entry points a user calls.

    python chip_smoke.py              # one chip: phases a, b, c
    python chip_smoke.py --chips 4    # four chips: sharded PageRank only
    python chip_smoke.py --rehearse   # CPU, tiny graphs, Pallas interpret mode

The graph is Graph500's Kronecker generator (R-MAT A=0.57, B=0.19, C=0.19,
edge factor 16, seed 0), generated on the host.

  a. scale 20: ``repro.api.partition`` with ``cuttana`` and
     ``cuttana-parallel`` (S=4), phase-2 refinement off (its host-only
     trade loop takes hours at this scale), and ``fennel``; k=8, edge
     balance, random order. The Pallas histogram path must be the one that
     ran: ``cuttana-parallel`` launches the sharded kernel, ``fennel`` the
     single-frontier one (sequential ``cuttana`` scores on the host).
  b. scale 16, full algorithms: ``cuttana``, ``cuttana-parallel`` and
     ``fennel`` through the kernel must assign bit-identically to the same
     callables with ``use_pallas=False`` (the host bincount path).
  c. 30 PageRank iterations of the simulated engine on phase a's cuttana
     partition, against ``reference_pagerank``.
  --chips 4: phase a's graph partitioned by ``fennel`` with k=4, PageRank under
     ``shard_map`` on a 4-device mesh (halo all-to-all over ICI), against
     the simulated engine and the reference.

Each phase prints one JSON line with its wall time, its XLA compile time
(``compile_s``: backend compiles and persistent-cache reads) and its
counters. The last line is ``{"ok": true, "device": {...}}``. Without a TPU
(and without ``--rehearse``), or when any phase fails, the script exits
non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# Graph500 edge factor 16: avg_degree counts both endpoints of each edge
AVG_DEGREE = 32
PAGERANK_ITERS = 30
# tolerance of the sharded-engine test against the float64 reference
RTOL, ATOL = 3e-4, 1e-9


def _parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded analytics phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at a tiny size, kernels in "
                         "Pallas interpret mode")
    return ap.parse_args(argv)


class CompileMeter:
    """Running totals of XLA compiles, read through ``jax.monitoring``."""

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

    def snapshot(self):
        return self.seconds, self.compiles, self.cache_hits


class Phase:
    """Times one phase and prints its line; an exception fails the run."""

    def __init__(self, meter, name, **fields):
        self.meter, self.fields = meter, {"phase": name, **fields}

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.meter.snapshot()
        return self.fields

    def __exit__(self, exc_type, exc, tb):
        s, n, h = self.meter.snapshot()
        self.fields.update(
            ok=exc_type is None,
            wall_s=time.perf_counter() - self.t0,
            compile_s=s - self.c0[0],
            compiles=n - self.c0[1],
            cache_hits=h - self.c0[2],
        )
        if exc_type is not None:
            self.fields["error"] = f"{exc_type.__name__}: {exc}"
        print(json.dumps(self.fields, default=float), flush=True)
        return False


def _partition(graph, spec, **engine_kw):
    """``repro.api.partition``, or, with engine overrides (``use_pallas``,
    ``interpret``), the registry's own callable with the same keyword
    arguments, wrapped in the same result type."""
    import numpy as np

    from repro.api import PartitionResult, partition
    from repro.api.registry import build_spec_kwargs, get_info

    if not engine_kw:
        return partition(graph, spec)
    info = get_info(spec.algo)
    telemetry: dict = {}
    t0 = time.perf_counter()
    out = info.resolve()(
        graph, spec.k, **build_spec_kwargs(info, spec),
        telemetry=telemetry, **engine_kw,
    )
    return PartitionResult(
        spec=spec, graph=graph, assignment=np.asarray(out),
        timings={"total_s": time.perf_counter() - t0}, telemetry=telemetry,
    )


def _graph(meter, scale):
    from repro.graph.generators import rmat_graph

    with Phase(meter, "graph", scale=scale) as f:
        g = rmat_graph(1 << scale, avg_degree=AVG_DEGREE, seed=0)
        f.update(num_vertices=g.num_vertices, num_edges=g.num_edges,
                 max_degree=int(g.degrees.max()))
    return g


def _check_kernel_ran(result, algo):
    tel = result.telemetry
    if tel.get("kernel_active") is not True:
        raise AssertionError(f"{algo}: the Pallas histogram path was not active")
    # the sequential buffered policy (cuttana) scores each placement on the
    # host; only chunked and sharded policies launch the kernel
    if algo != "cuttana" and tel.get("kernel_calls", 0) <= 0:
        raise AssertionError(f"{algo}: no kernel launch was counted")


def phase_a(meter, g, kernel_kw):
    from repro.api import PartitionSpec

    results = {}
    for algo, params in (
        ("cuttana", {"use_refinement": False}),
        ("cuttana-parallel", {"num_shards": 4, "use_refinement": False}),
        ("fennel", None),
    ):
        spec = PartitionSpec(
            algo=algo, k=8, balance_mode="edge", order="random", seed=0,
            params=params,
        )
        with Phase(meter, "a", algo=algo, k=8) as f:
            result = _partition(g, spec, **kernel_kw)
            q = result.quality()
            tel = result.telemetry
            f.update(
                edge_cut=q["edge_cut"], comm_volume=q["comm_volume"],
                timings=result.timings,
                kernel_active=tel.get("kernel_active"),
                kernel_calls=tel.get("kernel_calls"),
                single_place_calls=tel.get("single_place_calls"),
                supersteps=tel.get("supersteps"),
            )
            _check_kernel_ran(result, algo)
        results[algo] = result
    return results


def phase_b(meter, g, kernel_kw):
    import numpy as np

    from repro.api import PartitionSpec

    for algo, params in (
        ("cuttana", None),
        ("cuttana-parallel", {"num_shards": 4}),
        ("fennel", None),
    ):
        spec = PartitionSpec(
            algo=algo, k=8, balance_mode="edge", order="random", seed=0,
            params=params,
        )
        with Phase(meter, "b", algo=algo, k=8) as f:
            kern = _partition(g, spec, **kernel_kw)
            host = _partition(g, spec, use_pallas=False)
            same = bool(np.array_equal(kern.assignment, host.assignment))
            f.update(
                bit_identical=same,
                kernel_calls=kern.telemetry.get("kernel_calls"),
                kernel_s=kern.timings["total_s"],
                host_s=host.timings["total_s"],
            )
            _check_kernel_ran(kern, algo)
            if host.telemetry.get("kernel_active") is not False:
                raise AssertionError(f"{algo}: use_pallas=False ran the kernel")
            if not same:
                diff = int((kern.assignment != host.assignment).sum())
                raise AssertionError(f"{algo}: {diff} vertices differ")


def _pagerank_error(got, want):
    import numpy as np

    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), ATOL)))


def phase_c(meter, g, result):
    from repro.analytics.programs import reference_pagerank

    with Phase(meter, "c", program="pagerank", iters=PAGERANK_ITERS,
               k=result.k) as f:
        out = result.analytics(
            program="pagerank", iters=PAGERANK_ITERS, mode="simulated"
        )
        f.update(
            engine_s=out["seconds"],
            halo_messages_per_iter=out["halo_messages_per_iter"],
            padded_halo_elements_per_iter=out["padded_halo_elements_per_iter"],
            max_local_edges=out["max_local_edges"],
        )
        t0 = time.perf_counter()
        want = reference_pagerank(g, PAGERANK_ITERS)
        f["reference_s"] = time.perf_counter() - t0
        f["max_rel_err"] = _pagerank_error(out["values"], want)


def phase_mesh(meter, jax, g, kernel_kw):
    """Four chips: sharded PageRank over the halo all-to-all."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.analytics import GraphEngine, localize, pagerank_program
    from repro.analytics.programs import reference_pagerank
    from repro.api import PartitionSpec

    # fennel: the cheapest host pass that still launches the kernel, so the
    # four chips wait the least on host-side partitioning
    spec = PartitionSpec(
        algo="fennel", k=4, balance_mode="edge", order="random", seed=0
    )
    with Phase(meter, "mesh-partition", algo=spec.algo, k=4) as f:
        result = _partition(g, spec, **kernel_kw)
        q = result.quality()
        f.update(edge_cut=q["edge_cut"], comm_volume=q["comm_volume"],
                 timings=result.timings,
                 kernel_calls=result.telemetry.get("kernel_calls"))
        _check_kernel_ran(result, spec.algo)
    with Phase(meter, "mesh-pagerank", program="pagerank",
               iters=PAGERANK_ITERS, k=4) as f:
        lg = localize(g, result.assignment, 4)
        eng = GraphEngine(lg, pagerank_program())
        mesh = Mesh(np.array(jax.devices()[:4]), ("w",))
        fn, sharding = eng.build_sharded(mesh, iters=PAGERANK_ITERS)
        state = jax.device_put(eng.program.init_state(lg, eng.ctx), sharding)
        arrays = [jax.device_put(a, sharding) for a in eng.graph_arrays()]
        compiled = fn.lower(state, *arrays).compile()
        if "all-to-all" not in compiled.as_text():
            raise AssertionError("halo exchange did not lower to all-to-all")
        t0 = time.perf_counter()
        out = compiled(state, *arrays).block_until_ready()
        f["sharded_s"] = time.perf_counter() - t0
        devices = {shard.device for shard in out.addressable_shards}
        f["devices"] = len(devices)
        if len(devices) != 4:
            raise AssertionError(f"output spans {len(devices)} devices, not 4")
        got = eng.gather_global(np.asarray(out))
        t0 = time.perf_counter()
        sim = eng.run_simulated(PAGERANK_ITERS)
        f["simulated_s"] = time.perf_counter() - t0
        np.testing.assert_allclose(got, sim, rtol=1e-6, atol=1e-12)
        f["max_abs_diff_vs_simulated"] = float(np.max(np.abs(got - sim)))
        f["max_rel_err"] = _pagerank_error(
            got, reference_pagerank(g, PAGERANK_ITERS)
        )
        st = eng.stats(PAGERANK_ITERS)
        f.update(halo_messages_per_iter=st.true_halo_messages_per_iter,
                 padded_halo_elements_per_iter=st.padded_halo_elements_per_iter)


def main(argv=None) -> int:
    args = _parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"error: {SRC / 'repro'} not found: run from a checkout of the "
              f"repository", file=sys.stderr)
        return 2
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}"
            )
    sys.path.insert(0, str(SRC))
    import jax

    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    backend = jax.default_backend()
    if backend != "tpu" and not args.rehearse:
        print(f"error: JAX finds no TPU (default backend {backend!r}); "
              f"use --rehearse for a CPU run", file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"error: {args.chips} devices needed, JAX sees "
              f"{len(jax.devices())}", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({"device": device, "compile_cache": cache_dir,
                      "rehearse": args.rehearse}), flush=True)
    meter = CompileMeter(jax)
    # interpret mode is how a CPU rehearsal reaches the kernel path; on
    # the chip the user's own entry point resolves to the kernel
    kernel_kw = {"interpret": True} if args.rehearse else {}
    big, small = (12, 11) if args.rehearse else (20, 16)
    try:
        g = _graph(meter, big)
        if args.chips == 4:
            phase_mesh(meter, jax, g, kernel_kw)
        else:
            results = phase_a(meter, g, kernel_kw)
            phase_b(meter, _graph(meter, small), kernel_kw)
            phase_c(meter, g, results["cuttana"])
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
