"""A run whose timed path is broken underneath must come out not correct.

Each case breaks the system under test in the child process before the
harness runs (CPU rehearsal, so the look for a chip is skipped) and checks
that ``correct`` is false: an answer altered where it is produced (in every
job, or from the second window job on), a step that leaves its state
unchanged, half of a batch left out, and, on the mesh, the exchange between
chips left out."""
import pytest

from bench_util import run_bench

PARTITION_FAULTS = {
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

MESH_FAULTS = {
    # one vertex's new value altered where the program computes it
    "answer_altered": """
import repro.analytics.programs as progs
_pr = progs.pagerank_program
def pagerank_program(damping=0.85):
    p = _pr(damping)
    apply = p.apply
    def bad(old, agg, ctx):
        return apply(old, agg, ctx).at[0].multiply(1.5)
    import dataclasses
    return dataclasses.replace(p, apply=bad)
progs.pagerank_program = pagerank_program
import repro.analytics as an
an.pagerank_program = pagerank_program
""",
    # the compiled program returns the state it was given
    "state_unchanged": """
import jax
from repro.analytics.engine import GraphEngine
_b = GraphEngine.build_sharded
def build(self, mesh, axis="w", iters=1):
    fn, sharding = _b(self, mesh, axis=axis, iters=iters)
    return jax.jit(lambda state, *arrays: state), sharding
GraphEngine.build_sharded = build
""",
    # half of every device's edge slots left out
    "half_batch": """
from repro.analytics.engine import GraphEngine
_g = GraphEngine.graph_arrays
def arrays(self):
    rows, cols, deg, send = _g(self)
    rows = rows.copy()
    rows[:, rows.shape[1] // 2:] = self.lg.v_max
    return rows, cols, deg, send
GraphEngine.graph_arrays = arrays
""",
    # the halo all-to-all replaced by each device keeping what it would send
    "no_exchange": """
import jax
jax.lax.all_to_all = lambda x, *a, **k: x
""",
}

CASES = [("g500-s19-k8.fennel", f, PARTITION_FAULTS[f]) for f in PARTITION_FAULTS]
CASES += [("g500-s18-k8.cuttana-par4", f, PARTITION_FAULTS[f]) for f in PARTITION_FAULTS]
CASES += [("g500-s20-k4.pagerank-mesh", f, MESH_FAULTS[f]) for f in MESH_FAULTS]


@pytest.mark.parametrize("cell,fault,prelude", CASES, ids=[f"{c}-{f}" for c, f, _ in CASES])
def test_broken_timed_path_is_not_correct(cell, fault, prelude):
    line, err = run_bench(
        ["--workload", cell, "--seed", "424242", "--seconds", "1", "--trace", "0",
         "--rehearse"], prelude=prelude,
    )
    assert line is not None, err[-3000:]
    assert line["attempted"] >= 2
    assert line["correct"] is False, line["checks"]
