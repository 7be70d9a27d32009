"""Validate the real shard_map engine path on 8 forced host devices.

Runs in a subprocess so the XLA device-count flag never leaks into the main
test process (smoke tests elsewhere must see exactly 1 device).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import re
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.analytics import GraphEngine, localize, pagerank_program, cc_program
    from repro.analytics.programs import reference_pagerank, reference_cc
    from repro.core import get_partitioner
    from repro.graph import rmat_graph

    k = 8
    g = rmat_graph(1200, avg_degree=8, seed=5)
    part = get_partitioner("cuttana")(g, k, balance_mode="edge", seed=0)
    lg = localize(g, part, k)
    mesh = Mesh(np.array(jax.devices()[:k]), ("w",))

    eng = GraphEngine(lg, pagerank_program())
    got = eng.run_sharded(mesh, iters=10)
    want = reference_pagerank(g, iters=10)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=1e-9)

    # simulated and sharded paths must agree bit-for-bit-ish
    sim = eng.run_simulated(iters=10)
    np.testing.assert_allclose(got, sim, rtol=1e-6, atol=1e-12)

    eng2 = GraphEngine(lg, cc_program())
    got2 = eng2.run_sharded(mesh, iters=25)
    want2 = reference_cc(g, iters=25)
    np.testing.assert_allclose(got2, want2)

    # the compiled HLO must contain a real all-to-all collective
    txt = eng.lower_sharded(mesh, iters=3).compile().as_text()
    assert "all-to-all" in txt, "halo exchange did not lower to all-to-all"

    # one gather by edge a device (the per-slot message), beside the
    # ghost-table gather of the states to send
    sizes = [
        int(np.prod([int(d) for d in dims.split(",") if d]))
        for dims in re.findall(r"= \\w+\\[([\\d,]*)\\][^ ]* gather\\(", txt)
    ]
    edge_len, send_len = lg.rows.shape[1], lg.send_gather.shape[1] * lg.send_gather.shape[2]
    assert edge_len != send_len
    assert sizes.count(edge_len) == 1, sizes
    assert sizes.count(send_len) == 1, sizes
    print(json.dumps({"ok": True, "devices": len(jax.devices())}))
    """
)


@pytest.mark.slow
def test_shard_map_engine_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["devices"] == 8


MOE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.compat import use_mesh
    from repro.configs import get_reduced_config
    from repro.models import Axes, Model

    # capacity large enough that no token drops: capacity-drop patterns are
    # per-source-shard and legitimately differ across mesh shapes; with no
    # drops the EP all-to-all path must match the single-device math exactly.
    cfg = dataclasses.replace(
        get_reduced_config("jamba-v0.1-52b"), capacity_factor=8.0
    )

    def run(mesh_shape):
        devs = np.array(jax.devices()[: mesh_shape[0] * mesh_shape[1]])
        mesh = Mesh(devs.reshape(mesh_shape), ("data", "model"))
        model = Model(cfg, Axes(dp=("data",), tp="model"), mesh)
        with use_mesh(mesh):
            params = model.init(jax.random.key(0))
            rng = np.random.default_rng(0)
            tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)
            logits, aux = model.forward(params, {"tokens": tokens})
        return np.asarray(logits, np.float32)

    a = run((1, 1))
    b = run((2, 4))   # expert-parallel over a real 4-way model axis
    # 2e-2 is this repo's bf16 rtol (see test_kernels): TP splits every
    # projection's contraction across the model axis, so partial-sum rounding
    # legitimately differs from the 1-device mesh by a few bf16 ulps. The
    # atol is one bf16 ulp at the logit dynamic range (near-zero logits see
    # the full accumulated rounding of the large terms that cancelled).
    atol = float(np.spacing(np.abs(a).max(), dtype=np.float32) * 2**16)  # ~1 bf16 ulp
    np.testing.assert_allclose(a, b, rtol=2e-2, atol=max(atol, 2e-2))
    print(json.dumps({"ok": True, "maxdiff": float(np.abs(a - b).max())}))
    """
)


@pytest.mark.slow
def test_moe_expert_parallel_parity_subprocess():
    """MoE outputs must agree between a 1-device mesh and a real 2x4 mesh
    (expert-parallel all-to-all path) within bf16 tolerance."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    res = subprocess.run(
        [sys.executable, "-c", MOE_SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["ok"]
