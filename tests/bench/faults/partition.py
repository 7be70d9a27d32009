"""Faults a partition cell can have, each as Python run before the harness
in the child process (``tests/bench/test_bench_faults.py``)."""

FAULTS = {
    # one vertex's part changed as the partition is finalised
    "answer_altered": """
import numpy as np
import repro.core.base as base, repro.core.fennel as fen, repro.core.parallel as par
_fin = base.finalize
def finalize(state):
    part = _fin(state)
    part[0] = (part[0] + 1) % state.k
    return part
fen.finalize = par.finalize = finalize
""",
    # answers altered from the second window job on, as state carried from
    # one job to the next would: only the check of the last job sees it
    "later_job_altered": """
import repro.core.base as base, repro.core.fennel as fen, repro.core.parallel as par
_fin = base.finalize
calls = [0]
def finalize(state):
    part = _fin(state)
    calls[0] += 1
    if calls[0] > 2:  # the warm-up job and the first window job are sound
        part[0] = (part[0] + 1) % state.k
    return part
fen.finalize = par.finalize = finalize
""",
    # the score kernel hands back its (zero) input state: no counts
    "state_unchanged": """
import jax.numpy as jnp
import repro.kernels.partition_score.ops as ops
ops.fennel_scores_sharded_pallas = lambda nbr, sizes, a, g, **kw: jnp.zeros(
    nbr.shape[:2] + (sizes.shape[-1],), jnp.float32)
""",
    # the kernel counts only the first half of each launch's rows
    "half_batch": """
import repro.kernels.partition_score.ops as ops
_k = ops.fennel_scores_sharded_pallas
def half(nbr, sizes, a, g, **kw):
    out = _k(nbr, sizes, a, g, **kw)
    return out.at[:, out.shape[1] // 2:].set(0.0)
ops.fennel_scores_sharded_pallas = half
""",
}
