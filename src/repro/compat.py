"""The jax surfaces the repo pins, in one place.

The repo is written for the jax 0.9 line (see ``pyproject.toml``). Call sites
import :func:`use_mesh` / :func:`shard_map` from here rather than from
``jax`` directly, so a future rename lands in this file and nowhere else.
``JAX_VERSION`` / ``MIN_JAX_VERSION`` make the supported floor
introspectable (and testable) at runtime.
"""
from __future__ import annotations

import re

import jax

__all__ = [
    "JAX_VERSION",
    "MIN_JAX_VERSION",
    "jax_at_least",
    "use_mesh",
    "shard_map",
]


def _parse_version(v: str) -> tuple[int, int, int]:
    parts = []
    for p in v.split(".")[:3]:
        m = re.match(r"\d+", p)  # leading digits only ("37rc1" -> 37)
        parts.append(int(m.group(0)) if m else 0)
    while len(parts) < 3:
        parts.append(0)
    return tuple(parts)  # type: ignore[return-value]


#: the running jax version as an (major, minor, patch) int tuple
JAX_VERSION: tuple[int, int, int] = _parse_version(jax.__version__)

#: oldest jax this repo supports (see pyproject.toml)
MIN_JAX_VERSION: tuple[int, int, int] = (0, 9, 0)


def jax_at_least(*version: int) -> bool:
    """True when the running jax is >= ``version`` (e.g. ``(0, 9)``)."""
    return JAX_VERSION >= tuple(version)


def use_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with the repo's keyword spelling."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )
