"""Faults a full CUTTANA cell can have, each as Python run before the
harness in the child process (``tests/bench/test_bench_faults.py``)."""

FAULTS = {
    # phase 2 makes no trade
    "refinement_skipped": """
import repro.core.refinement as ref
ref.Refiner.refine = lambda self, *a, **kw: ref.RefineStats(stopped_reason="skipped")
""",
    # phase 2 stops one trade short of maximal (max_moves one short)
    "last_move_dropped": """
import copy
import repro.core.refinement as ref
_refine = ref.Refiner.refine
def refine(self, thresh=0.0, max_moves=None, spans=None):
    moves = _refine(copy.deepcopy(self), thresh, max_moves).moves
    return _refine(self, thresh, max(moves - 1, 0), spans)
ref.Refiner.refine = refine
""",
    # equal decreases go to the highest sub-partition id
    "tie_other_way": """
import numpy as np
import repro.core.refinement as ref
def best_move(self, thresh=0.0):
    ids = np.arange(self.kp)
    dec = self.m - self.m[ids, self.sub_part][:, None]
    ok = self.size[:, None] <= self.cap - self.part_load[None, :] + 1e-9
    ok[ids, self.sub_part] = False
    dec = np.where(ok, dec, -np.inf)
    r, dst = divmod(int(np.argmax(dec[::-1])), self.k)
    i = self.kp - 1 - r
    return (i, dst, float(dec[i, dst])) if dec[i, dst] > thresh else None
ref.Refiner.best_move = best_move
""",
    # phase 1's score kernel counts only the first half of each launch's
    # rows, carried through phase 2 to the final answer
    "half_batch": """
import repro.kernels.partition_score.ops as ops
_k = ops.fennel_scores_sharded_pallas
def half(nbr, sizes, a, g, **kw):
    out = _k(nbr, sizes, a, g, **kw)
    return out.at[:, out.shape[1] // 2:].set(0.0)
ops.fennel_scores_sharded_pallas = half
""",
}
