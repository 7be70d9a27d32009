"""Public wrapper: pads to kernel-friendly shapes, dispatches kernel vs ref."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.partition_score.partition_score import fennel_scores_sharded_pallas
from repro.kernels.partition_score.ref import (
    fennel_scores_ref,
    fennel_scores_sharded_ref,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_active(use_pallas: bool | None, interpret: bool = False) -> bool:
    """Resolve the tri-state ``use_pallas`` flag the same way
    :func:`fennel_scores` does (None => kernel only on TPU)."""
    if interpret:
        return True
    return _on_tpu() if use_pallas is None else bool(use_pallas)


def neighbor_histograms_host(
    rows: np.ndarray,  # int[NNZ] batch-row index of each neighbour slot
    parts: np.ndarray,  # int[NNZ] neighbour partition ids, -1 = unassigned
    num_rows: int,
    k: int,
    out: np.ndarray | None = None,  # float64[num_rows, K] to fill in place
) -> np.ndarray:
    """hist[B, K] of assigned-neighbour counts from flat (row, part) pairs.

    The CPU companion of the Pallas histogram: one ``bincount`` over the
    chunk's edges instead of a per-vertex loop (and instead of the jnp
    reference's [B, D, K] one-hot cube, which is far too slow for the
    streaming hot path). ``out`` lets a shard worker fill its disjoint rows
    of a preallocated superstep histogram without a second allocation."""
    mask = parts >= 0
    idx = rows[mask] * np.int64(k) + parts[mask]
    hist = np.bincount(idx, minlength=num_rows * k).reshape(num_rows, k)
    if out is None:
        return hist.astype(np.float64)
    out[:] = hist
    return out


def fennel_scores(
    nbr_parts,
    sizes,
    alpha: float,
    gamma: float = 1.5,
    use_pallas: bool | None = None,
    interpret: bool = False,
):
    """scores[B, K] for a batch of vertices (Eq. 7 affinity + penalty).

    ``nbr_parts`` int[B, D] (-1 padding), ``sizes`` float[K]. The kernel
    path is the ``S = 1`` launch of :func:`fennel_scores_sharded` and
    returns a host array.
    """
    if not kernel_active(use_pallas, interpret):
        return fennel_scores_ref(
            jnp.asarray(nbr_parts, jnp.int32), jnp.asarray(sizes, jnp.float32),
            alpha, gamma,
        )
    nbr_parts = np.asarray(nbr_parts, np.int32)
    return _launch(nbr_parts[None], np.asarray(sizes)[None], alpha, gamma, interpret)[0]


def fennel_scores_sharded(
    nbr_parts,
    sizes,
    alpha: float,
    gamma: float = 1.5,
    use_pallas: bool | None = None,
    interpret: bool = False,
):
    """scores[S, C, K] for S shard frontiers in one fused call.

    ``nbr_parts`` int[S, C, D] (-1 padding, both on the neighbour axis and
    for rows beyond a shard's candidate count), ``sizes`` float[S, K] - one
    size row per shard, so a caller *can* fuse shard-local penalties into
    the launch. The stream engine applies penalties incrementally on the
    host (they change per placement) and calls this with ``alpha=0`` / zero
    sizes - there the leading batch dimension packs all shards' padded
    frontiers into one (shard, block) grid launch per superstep.
    """
    if not kernel_active(use_pallas, interpret):
        return fennel_scores_sharded_ref(
            jnp.asarray(nbr_parts, jnp.int32), jnp.asarray(sizes, jnp.float32),
            alpha, gamma,
        )
    return _launch(
        np.asarray(nbr_parts, np.int32), np.asarray(sizes), alpha, gamma, interpret
    )


def kernel_tiling(c: int, d: int) -> tuple[int, int, int, int]:
    """``(block_b, d_chunk, padded_c, padded_d)`` of the launch for ``c``
    candidate rows of neighbour width ``d``."""
    block_b = 128 if c >= 128 else 8
    d_chunk = 128 if d >= 128 else max(8, d)
    cp = -(-max(c, 1) // block_b) * block_b
    dp = -(-max(d, 1) // d_chunk) * d_chunk
    return block_b, d_chunk, cp, dp


def _launch(nbr_parts: np.ndarray, sizes: np.ndarray, alpha, gamma, interpret):
    """Pad ``int32[S, C, D]`` on the host to the kernel's tiling, run one
    launch, and return the unpadded ``float32[S, C, K]`` on the host.

    Padding and slicing stay in numpy so that each chunk costs one transfer
    each way and one compiled program per padded shape, not one per
    ``(C, D)`` pair."""
    s, c, d = nbr_parts.shape
    block_b, d_chunk, cp, dp = kernel_tiling(c, d)
    padded = np.full((s, cp, dp), -1, dtype=np.int32)
    padded[:, :c, :d] = nbr_parts
    out = fennel_scores_sharded_pallas(
        padded, np.asarray(sizes, np.float32), alpha, gamma,
        block_b=block_b, d_chunk=d_chunk, interpret=interpret,
    )
    return np.asarray(out)[:, :c]
