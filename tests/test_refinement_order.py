"""Phase-2 refinement's stated tie order (``repro.core.refinement``): the
next trade is ``np.argmax`` of the masked [K', K] decrease matrix read row
by row (largest decrease, then lowest sub-partition id, then lowest
destination), and depends on (W, P', sizes) alone, never on the history of
moves. Also ``W``'s counts on a Graph500 graph."""
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.refinement import Refiner, build_subpartition_graph
from repro.graph.csr import CSRGraph

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))  # the benchmark's Graph500 generator

from bench.graph500 import graph500_csr  # noqa: E402


def argmax_move(r: Refiner, thresh: float = 0.0):
    """The stated order, written out: feasible trades masked, then argmax."""
    ids = np.arange(r.kp)
    dec = r.m - r.m[ids, r.sub_part][:, None]
    feasible = r.size[:, None] <= r.cap - r.part_load[None, :] + 1e-9
    feasible[ids, r.sub_part] = False
    dec = np.where(feasible, dec, -np.inf)
    i, dst = divmod(int(np.argmax(dec)), r.k)
    return (i, dst, float(dec[i, dst])) if dec[i, dst] > thresh else None


def tied_refiner(seed: int, kp: int, k: int, epsilon: float) -> Refiner:
    """Integer weights in {0, 1, 2} and integer sizes: many equal decreases."""
    rng = np.random.default_rng(seed)
    w = rng.integers(0, 3, (kp, kp)) * (rng.random((kp, kp)) < 0.2)
    w = np.triu(w, 1)
    w = (w + w.T).astype(np.float64)
    sub_part = rng.integers(0, k, kp)
    size = rng.integers(1, 4, kp).astype(np.float64)
    return Refiner(w, sub_part, size, k, epsilon=epsilon)


@pytest.mark.parametrize(
    "seed,kp,k,epsilon",
    [(0, 40, 2, 0.3), (1, 96, 4, 0.05), (2, 150, 8, 1.0), (3, 200, 5, 0.2)],
)
def test_every_pick_of_a_refine_is_the_argmax_order(seed, kp, k, epsilon):
    r = tied_refiner(seed, kp, k, epsilon)
    pick = r.best_move
    picks, tied = [], 0

    def checked(thresh=0.0):
        got = pick(thresh)
        assert got == argmax_move(r, thresh)
        if got is not None:
            picks.append(got)
            ids = np.arange(r.kp)
            dec = r.m - r.m[ids, r.sub_part][:, None]
            dec[ids, r.sub_part] = -np.inf
            nonlocal tied
            tied += int(np.count_nonzero(dec == got[2]) > 1)
        return got

    r.best_move = checked
    stats = r.refine()
    assert stats.stopped_reason == "maximal" and stats.moves == len(picks) > 0
    assert tied > 0  # the case exercises ties
    assert argmax_move(r) is None
    r.check_invariants()


def test_the_next_move_does_not_depend_on_the_history():
    r0 = tied_refiner(5, 120, 4, 0.5)
    start = r0.sub_part.copy()
    rng = np.random.default_rng(9)
    movers = rng.choice(r0.kp, size=12, replace=False)
    dsts = [(int(start[i]) + 1 + int(rng.integers(r0.k - 1))) % r0.k for i in movers]

    a = Refiner(r0.w, start, r0.size, r0.k, epsilon=0.5)
    for i, d in zip(movers, dsts):
        a.apply_move(int(i), d)
    b = Refiner(r0.w, start, r0.size, r0.k, epsilon=0.5)
    for i, d in reversed(list(zip(movers, dsts))):
        # a detour through every other part first
        for q in range(b.k):
            if q not in (d, int(b.sub_part[i])):
                b.apply_move(int(i), q)
        b.apply_move(int(i), d)
    fresh = Refiner(r0.w, a.sub_part, r0.size, r0.k, epsilon=0.5)
    np.testing.assert_array_equal(a.sub_part, b.sub_part)
    for r in (a, b, fresh):
        r.check_invariants()
    # the same state picks the same move, all the way to maximal
    while True:
        mv = a.best_move()
        assert mv == b.best_move() == fresh.best_move() == argmax_move(a)
        if mv is None:
            break
        for r in (a, b, fresh):
            r.apply_move(mv[0], mv[1])


def test_w_counts_edges_between_subpartitions_on_graph500():
    indptr, indices = graph500_csr(20240601, 2**33 + 1, 11)
    g = CSRGraph(indptr=indptr, indices=indices)
    kp = 64
    sub_of = np.random.default_rng(4).integers(0, kp, g.num_vertices)
    w = build_subpartition_graph(g, sub_of, kp)
    np.testing.assert_array_equal(w, np.round(w))
    np.testing.assert_array_equal(w, w.T)
    assert np.all(np.diag(w) == 0)
    src = np.repeat(np.arange(g.num_vertices), np.diff(indptr))
    for i, j in ((0, 1), (5, 9), (63, 2)):
        between = ((sub_of[src] == i) & (sub_of[indices] == j)) & (src < indices)
        between |= ((sub_of[src] == j) & (sub_of[indices] == i)) & (src < indices)
        assert w[i, j] == np.count_nonzero(between)
    cut = np.count_nonzero(sub_of[src] != sub_of[indices]) // 2
    assert w.sum() / 2 == cut
