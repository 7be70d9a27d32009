"""Graph500 Kronecker (R-MAT) graphs, drawn on the device from a seed.

The generator follows the Graph500 specification's Kronecker generator:
``edge_factor * 2**scale`` edges, each placed by ``scale`` independent
quadrant draws with probabilities A, B, C and D = 1 - A - B - C. The CSR
that the benchmark hands to the system is built on the device too: self
loops dropped, duplicate undirected edges removed, every edge stored in
both rows, each row sorted. That is the layout ``CSRGraph.from_edges``
produces, without its host sorts.

A configuration fixes one Graph500 instance by its ``graph_seed``; a run's
``--seed`` relabels its vertices by a random permutation, as the Graph500
specification does, so every seed gets the same graph (the same degrees,
hubs and sizes) under other vertex ids. Random bits come from JAX's threefry
generator, which gives the same bits on every backend, so the seeds name the
same graph on the chip and on the CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int = 0):
    """A threefry key from a seed of any size (more than 32 bits too)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


@functools.partial(jax.jit, static_argnames=("scale", "edge_factor", "a", "b", "c"))
def rmat_edges(key, *, scale: int, edge_factor: int, a: float, b: float, c: float):
    """``(src, dst)`` int32[edge_factor * 2**scale]: the raw Kronecker edges."""
    m = edge_factor << scale

    def level(i, carry):
        src, dst = carry
        r = jax.random.uniform(jax.random.fold_in(key, i), (m,), jnp.float32)
        down = r >= a + b  # quadrants C and D set the source bit
        right = ((r >= a) & (r < a + b)) | (r >= a + b + c)  # B and D
        bit = jnp.left_shift(jnp.int32(1), i)
        return src | jnp.where(down, bit, 0), dst | jnp.where(right, bit, 0)

    zero = jnp.zeros(m, jnp.int32)
    return jax.lax.fori_loop(0, scale, level, (zero, zero))


@functools.partial(jax.jit, static_argnames=("num_vertices",))
def csr_from_edges(src, dst, *, num_vertices: int):
    """Symmetric, deduplicated, row-sorted CSR of an undirected edge list.

    Returns ``(indptr int32[n+1], indices int32[2m])``: the first
    ``indptr[n]`` entries of ``indices`` are the neighbour lists, row after
    row; the rest is padding.
    """
    n = num_vertices
    lo = jnp.minimum(src, dst)
    hi = jnp.maximum(src, dst)
    sentinel = jnp.int32(n)
    keep = lo != hi
    lo = jnp.where(keep, lo, sentinel)
    hi = jnp.where(keep, hi, sentinel)
    lo, hi = jax.lax.sort((lo, hi), num_keys=2)
    dup = jnp.concatenate(
        [jnp.zeros(1, bool), (lo[1:] == lo[:-1]) & (hi[1:] == hi[:-1])]
    )
    keep = (lo < sentinel) & ~dup
    lo = jnp.where(keep, lo, sentinel)
    hi = jnp.where(keep, hi, sentinel)
    rows, cols = jax.lax.sort(
        (jnp.concatenate([lo, hi]), jnp.concatenate([hi, lo])), num_keys=2
    )
    # rows are sorted, and padding rows (the sentinel n) sort last
    indptr = jnp.searchsorted(rows, jnp.arange(n + 1, dtype=jnp.int32))
    return indptr.astype(jnp.int32), cols


@functools.partial(jax.jit, static_argnames=("num_vertices",))
def relabel(src, dst, key, *, num_vertices: int):
    """Both endpoints of every edge through one random permutation."""
    perm = jax.random.permutation(key, num_vertices).astype(jnp.int32)
    return perm[src], perm[dst]


def graph500_csr(
    graph_seed: int, label_seed: int, scale: int, edge_factor: int = 16,
    a: float = 0.57, b: float = 0.19, c: float = 0.19,
) -> tuple[np.ndarray, np.ndarray]:
    """Host ``(indptr int64[n+1], indices int32[2|E|])`` of the Graph500
    graph ``graph_seed``, its vertices relabelled by ``label_seed``."""
    n = 1 << scale
    src, dst = rmat_edges(
        seed_key(graph_seed), scale=scale, edge_factor=edge_factor, a=a, b=b, c=c
    )
    src, dst = relabel(src, dst, seed_key(label_seed, 1), num_vertices=n)
    indptr, cols = csr_from_edges(src, dst, num_vertices=n)
    indptr = np.asarray(indptr).astype(np.int64)
    # slice on the host: a device slice would compile once per edge count
    return indptr, np.asarray(cols)[: indptr[-1]].copy()
