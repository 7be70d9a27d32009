"""Pallas TPU kernel: fused neighbour-partition histogram + FENNEL penalty.

The paper's streaming phase evaluates Eq. 7 for every vertex: count assigned
neighbours per partition, subtract the balance penalty, argmax. On CPU this is
the O(K|V| + |E|) inner loop; CUTTANA parallelises it with threads. The TPU
adaptation tiles a *batch* of vertices' padded neighbour-partition ids into
VMEM and builds the histogram with VPU compares against the lane-resident
partition ids - no scatter, MXU-free, fully vectorised.

Tiling:
  grid over (shard, vertex block of BB rows); the neighbour axis D is read
  from the ref in chunks of DC columns so the [BB, DC, K] compare cube stays
  within VMEM. Chunks start at static multiples of DC (``pl.multiple_of``):
  Mosaic cannot slice a loaded value at a dynamic offset, and a block
  narrower than 128 lanes (D = DC < 128) is read in one static load.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _score_kernel(nbr_ref, size_ref, out_ref, *, alpha, gamma, d_chunk):
    """One (shard, vertex-block) grid cell. The size row is the *shard's*
    size view, so the fused penalty may differ per shard (the parallel
    engine's bulk-synchronous local state)."""
    _, bb, d = nbr_ref.shape  # leading shard dim is blocked to 1
    sizes = size_ref[0]  # [1, K] float32
    k = sizes.shape[-1]
    part_ids = jax.lax.broadcasted_iota(jnp.int32, (1, 1, k), 2)

    def count(tile):  # [BB, DC] int32 -> [BB, K] float32 (exact up to 2^24)
        return (tile[:, :, None] == part_ids).astype(jnp.float32).sum(axis=1)

    if d == d_chunk:
        hist = count(nbr_ref[0])
    else:
        def body(c, hist):
            start = pl.multiple_of(c * d_chunk, d_chunk)
            return hist + count(nbr_ref[0, :, pl.ds(start, d_chunk)])

        hist = jax.lax.fori_loop(
            0, d // d_chunk, body, jnp.zeros((bb, k), jnp.float32)
        )
    penalty = alpha * gamma * jnp.power(jnp.maximum(sizes, 0.0), gamma - 1.0)
    out_ref[0] = hist - penalty


@functools.partial(
    jax.jit, static_argnames=("alpha", "gamma", "block_b", "d_chunk", "interpret")
)
def fennel_scores_sharded_pallas(
    nbr_parts: jnp.ndarray,  # int32[S, C, D] (-1 pad; C % block_b == 0, D % d_chunk == 0)
    sizes: jnp.ndarray,  # float32[S, K]
    alpha: float,
    gamma: float,
    block_b: int = 128,
    d_chunk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """scores[S, C, K]: ONE kernel launch for all S shard frontiers.

    The grid is (shard, vertex-block); every cell loads its shard's candidate
    tile plus that shard's K-wide size row, so a whole superstep of the
    parallel engine is a single fused call instead of S sequential ones.
    """
    s, c, d = nbr_parts.shape
    k = sizes.shape[-1]
    assert c % block_b == 0 and d % d_chunk == 0
    kernel = functools.partial(
        _score_kernel, alpha=alpha, gamma=gamma, d_chunk=d_chunk
    )
    return pl.pallas_call(
        kernel,
        grid=(s, c // block_b),
        in_specs=[
            pl.BlockSpec((1, block_b, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, k), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_b, k), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((s, c, k), jnp.float32),
        interpret=interpret,
    )(nbr_parts, sizes[:, None, :])

