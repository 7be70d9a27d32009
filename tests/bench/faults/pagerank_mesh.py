"""Faults the sharded PageRank cell can have, each as Python run before the
harness in the child process (``tests/bench/test_bench_faults.py``)."""

FAULTS = {
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
