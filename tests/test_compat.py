"""Guards for the repro.compat version-shim surface: the running jax must be
inside the declared support range, and the shims must actually provide a
working ambient-mesh context and shard_map on it."""
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro import compat


def test_jax_version_tuple():
    assert isinstance(compat.JAX_VERSION, tuple)
    assert len(compat.JAX_VERSION) == 3
    assert all(isinstance(p, int) for p in compat.JAX_VERSION)
    assert compat.JAX_VERSION == compat._parse_version(jax.__version__)


def test_running_jax_inside_declared_range():
    assert compat.JAX_VERSION >= compat.MIN_JAX_VERSION, (
        f"jax {jax.__version__} is older than the supported minimum "
        f"{'.'.join(map(str, compat.MIN_JAX_VERSION))}"
    )
    assert compat.JAX_VERSION[:2] == compat.MIN_JAX_VERSION[:2], (
        f"jax {jax.__version__} is outside the supported 0.9 line"
    )


def test_jax_at_least():
    assert compat.jax_at_least(0, 9)
    assert compat.jax_at_least(*compat.MIN_JAX_VERSION)
    assert not compat.jax_at_least(0, 10)
    assert not compat.jax_at_least(99, 0)


def test_pyproject_declares_the_same_floor():
    """pyproject's jax pin and compat.MIN_JAX_VERSION must not drift apart."""
    text = Path(__file__).resolve().parent.parent.joinpath("pyproject.toml").read_text()
    m = re.search(r'"jax>=(\d+)\.(\d+)\.(\d+)', text)
    assert m, "pyproject.toml must declare a jax>=X.Y.Z lower bound"
    assert tuple(int(g) for g in m.groups()) == compat.MIN_JAX_VERSION
    major, minor, _ = compat.MIN_JAX_VERSION
    upper = f"<{major}.{minor + 1}"
    for pkg in ("jax", "jaxlib"):
        assert re.search(rf'"{pkg}>=\d+\.\d+\.\d+,{upper}"', text), (
            f"{pkg} must be pinned to the {major}.{minor} line"
        )


def test_use_mesh_enables_ambient_sharding():
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with compat.use_mesh(mesh):
        x = jnp.ones((4, 4))
        y = jax.lax.with_sharding_constraint(x, P("data", None))
    np.testing.assert_array_equal(np.asarray(y), np.ones((4, 4)))


def test_compat_shard_map_runs():
    mesh = Mesh(np.array(jax.devices()[:1]), ("w",))
    f = compat.shard_map(
        lambda x: x * 2.0,
        mesh=mesh,
        in_specs=(P("w"),),
        out_specs=P("w"),
        check_vma=False,
    )
    np.testing.assert_array_equal(np.asarray(f(jnp.ones(4))), np.full(4, 2.0))


def test_parse_version_handles_dev_suffixes():
    assert compat._parse_version("0.9.0") == (0, 9, 0)
    assert compat._parse_version("0.9.1.dev20260101") == (0, 9, 1)
    assert compat._parse_version("0.9") == (0, 9, 0)
    assert compat._parse_version("0.9.0rc1") == (0, 9, 0)

@pytest.mark.parametrize("env_dir", [True, False])
def test_enable_compile_cache_placement(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, is where the cache lands;
    otherwise the fixed .jax_cache/ at the repo root. Run in a child so
    the global jax config of this process stays untouched."""
    import os
    import subprocess
    import sys

    repo = Path(__file__).resolve().parent.parent
    env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n"
        + ("jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()\n"
           if env_dir else "")
    )
    res = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, cwd=repo, timeout=120,
    )
    assert res.returncode == 0, res.stderr
    cache_dir, min_secs = res.stdout.split()[:2]
    assert float(min_secs) == 0.0
    if env_dir:
        assert cache_dir == str(tmp_path)
        assert any(tmp_path.iterdir()), "no cache entry was written"
    else:
        assert cache_dir == str(repo / ".jax_cache")
