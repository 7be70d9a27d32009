"""Coverage for the remaining substrate: gradient compression, stream
orders, workload generators, localize edge cases, serve package."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.graph import CSRGraph, rmat_graph
from repro.graph.stream import stream_order


def test_stream_orders_are_permutations():
    g = rmat_graph(500, avg_degree=6, seed=0)
    for order in ("natural", "random", "bfs", "dfs"):
        ids = stream_order(g, order, seed=1)
        assert sorted(ids.tolist()) == list(range(g.num_vertices)), order


def test_compression_single_pod_noop():
    from repro.train.compression import compressed_psum_pod, init_residuals

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("pod", "data", "model"))
    g = jnp.asarray(np.random.default_rng(0).standard_normal((8, 8)), jnp.float32)
    r = jnp.zeros((8, 8), jnp.float32)
    out, new_r = compressed_psum_pod(g, r, mesh, "pod")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(g))


@pytest.mark.slow
def test_compression_error_feedback_subprocess():
    """int8 cross-pod psum: mean of pods within quantization error, residual
    carries the rounding error forward."""
    import json
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.compat import use_mesh
        from repro.train.compression import compressed_psum_pod

        mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 2),
                    ("pod", "data", "model"))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.standard_normal((16, 16)), jnp.float32)
        r = jnp.zeros((16, 16), jnp.float32)
        with use_mesh(mesh):
            gd = jax.device_put(g, NamedSharding(mesh, P()))
            rd = jax.device_put(r, NamedSharding(mesh, P()))
            out, new_r = jax.jit(
                lambda a, b: compressed_psum_pod(a, b, mesh, "pod")
            )(gd, rd)
        # both pods held the same g -> mean == g up to int8 quantization
        err = float(np.abs(np.asarray(out) - np.asarray(g)).max())
        scale = float(np.abs(np.asarray(g)).max()) / 127.0
        ok = err <= scale + 1e-6
        # residual equals the quantization error of this round
        res_ok = float(np.abs(np.asarray(new_r)).max()) <= scale + 1e-6
        print(json.dumps({"ok": bool(ok and res_ok), "err": err}))
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    res = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert res.returncode == 0, res.stderr
    assert json.loads(res.stdout.strip().splitlines()[-1])["ok"]


def test_localize_with_isolated_vertices():
    from repro.analytics import GraphEngine, localize, pagerank_program
    from repro.analytics.programs import reference_pagerank

    edges = np.array([[0, 1], [1, 2], [5, 6]])
    g = CSRGraph.from_edges(edges, num_vertices=8)  # 3,4,7 isolated
    part = np.array([0, 0, 1, 1, 0, 1, 0, 1], dtype=np.int32)
    lg = localize(g, part, 2)
    got = GraphEngine(lg, pagerank_program()).run_simulated(5)
    want = reference_pagerank(g, 5)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_workload_degree_bias():
    from repro.db import ldbc_query_mix

    g = rmat_graph(2000, avg_degree=10, seed=0)
    biased = ldbc_query_mix(g, 2000, seed=0, degree_biased=True)
    uniform = ldbc_query_mix(g, 2000, seed=0, degree_biased=False)
    assert g.degrees[biased].mean() > g.degrees[uniform].mean()


def test_serve_package_exports():
    import repro.serve as s

    assert callable(s.make_prefill_step) and callable(s.make_decode_step)


def test_csr_permute_preserves_structure():
    g = rmat_graph(300, avg_degree=8, seed=0)
    rng = np.random.default_rng(1)
    perm = rng.permutation(g.num_vertices)
    g2 = g.permute(perm)
    assert g2.num_edges == g.num_edges
    np.testing.assert_array_equal(
        np.sort(g2.degrees[perm]), np.sort(g.degrees[perm])
    )
    # degree of relabeled vertex matches original
    for v in rng.integers(0, g.num_vertices, 10):
        assert g2.degree(int(perm[v])) == g.degree(int(v))


def test_checkpoint_save_restore_with_sharded_arrays(tmp_path):
    from repro.train.checkpoint import restore_checkpoint, save_checkpoint

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    from jax.sharding import NamedSharding, PartitionSpec as P

    x = jax.device_put(jnp.arange(16.0).reshape(4, 4),
                       NamedSharding(mesh, P("data", None)))
    save_checkpoint(str(tmp_path), 1, {"x": x})
    restored, step = restore_checkpoint(str(tmp_path), {"x": x})
    np.testing.assert_array_equal(np.asarray(restored["x"]), np.asarray(x))


def test_benchmark_suite_imports_are_lazy():
    """--only must not import the other suites: a broken suite (import-time
    failure included) can then never mask the one being run."""
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent(
        """
        import sys
        import benchmarks.run as r
        eager = [m for m in sys.modules
                 if m.startswith("benchmarks.") and m != "benchmarks.run"]
        assert not eager, f"benchmarks.run eagerly imported {eager}"
        # a missing/broken suite fails only when its thunk actually runs
        bad = r._suite("definitely_not_a_suite")
        try:
            bad()
        except ModuleNotFoundError:
            print("lazy-ok")
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    res = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=120,
    )
    assert res.returncode == 0, res.stderr
    assert "lazy-ok" in res.stdout


def test_benchmark_run_exits_nonzero_when_a_suite_errors():
    """A failing suite still prints its ERROR= line and the other suites
    still run, but the runner's exit code reports the failure."""
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent(
        """
        import sys
        import benchmarks.run as r
        import repro.compile_cache

        def suite(module, kwargs=None):
            def run_it():
                if module == "kernels":
                    raise RuntimeError("boom")
                return [{"bench": module}]
            return run_it

        r._suite = suite
        repro.compile_cache.enable_compile_cache = lambda: None
        sys.argv = ["run", "--only", "kernels,imbalance"]
        try:
            r.main()
        except SystemExit as e:
            print("exit", e.code)
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    res = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=120,
    )
    assert res.returncode == 0, res.stderr
    assert "kernels,0.0,ERROR=RuntimeError:boom" in res.stdout
    assert "# === imbalance ===" in res.stdout
    assert res.stdout.strip().endswith("exit 1")
