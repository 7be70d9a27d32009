"""CUTTANA Phase 2: coarsened refinement (paper §III-B).

The sub-partition graph (Def. 3) is coarse enough to hold in memory for any
input graph, so refinement cost is independent of |V|, |E| (paper's headline
theoretical property). We maintain, exactly as the paper:

  * ``W``    - K'xK' weighted sub-partition adjacency (diag zeroed),
  * ``M``    - K'xK matrix, M[i,p] = sum_j W[i,j] * [P'(j) = p]
               (so ECP[i,p] = total_w[i] - M[i,p], Eq. 8),
  * ``DEC``  - DEC[i, dst] = ECP[i, src] - ECP[i, dst] = M[i,dst] - M[i,src]
               (Eq. 9),
  * ``MS``   - for every (src, dst) partition pair, a max-segment-tree over
               the DEC values of sub-partitions currently in ``src``
               (find-best O(1) at the root, update O(log(K'/K)), Lemma 1).

After a trade we update exactly the O(K') entries of Theorem 2. Feasibility
(the balance condition) is enforced at query time with a pruned descent of the
segment tree, so capacity-blocked trades are skipped without being lost.

Tie order. A trade <S_i, dst> is feasible when dst != P'(i) and
size[i] <= cap - load[dst] + 1e-9. The next trade is the feasible one of
largest DEC greater than ``thresh``; equal decreases go to the lowest
sub-partition id i, then to the lowest dst. That is ``np.argmax``'s order
over the masked [K', K] DEC matrix read row by row, and it depends on
(W, P', sizes) alone, never on the moves that led there: sub-partition i
has the leaf i in every partition's trees.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.profile import SpanRecorder
from repro.graph.csr import CSRGraph

NEG_INF = -np.inf


def build_subpartition_graph(
    graph: CSRGraph, sub_of: np.ndarray, kp: int
) -> np.ndarray:
    """Dense K'xK' weighted sub-partition adjacency; W[i,j] = #edges between
    members of S_i and S_j, an integer count (diagonal zeroed). The CSR
    stores each undirected edge in both rows, so the count matrix is
    already symmetric: S_i -> S_j slots number the edges between them."""
    src = np.repeat(np.arange(graph.num_vertices, dtype=np.int64), graph.degrees)
    si = sub_of[src].astype(np.int64)
    sj = sub_of[graph.indices].astype(np.int64)
    key = si * kp + sj
    counts = np.bincount(key, minlength=kp * kp).astype(np.float64)
    w = counts.reshape(kp, kp)
    w = 0.5 * (w + w.T)  # symmetrises only: the counts are symmetric already
    np.fill_diagonal(w, 0.0)
    return w


@dataclasses.dataclass
class RefineStats:
    moves: int = 0
    cut_improvement: float = 0.0
    stopped_reason: str = ""


class Refiner:
    def __init__(
        self,
        w: np.ndarray,
        sub_part: np.ndarray,  # int[K'] -> current partition of each sub-part
        size: np.ndarray,  # float[K'] balance mass of each sub-part
        k: int,
        epsilon: float,
        total_mass: float | None = None,
    ):
        self.kp = w.shape[0]
        self.k = k
        self.w = w
        self.sub_part = sub_part.astype(np.int64).copy()
        self.size = size.astype(np.float64)
        total = float(self.size.sum()) if total_mass is None else total_mass
        self.cap = (1.0 + epsilon) * total / k
        self.part_load = np.bincount(
            self.sub_part, weights=self.size, minlength=k
        ).astype(np.float64)
        self.total_w = w.sum(axis=1)
        onehot = np.zeros((self.kp, k), dtype=np.float64)
        onehot[np.arange(self.kp), self.sub_part] = 1.0
        self.m = w @ onehot  # M[i, p]
        # ------------------------------------------------------ segment trees
        # Sub-partition i is leaf i of every (src, dst) tree; the leaf holds
        # DEC[i, dst] while P'(i) = src, else -inf. (Balance is by mass, so
        # any number of sub-partitions may share a partition: a tree needs
        # room for all K' anyway.)
        self.cap2 = 1 << int(np.ceil(np.log2(max(self.kp, 2))))
        self._leaf_level = self.cap2.bit_length() - 1
        self.tree = np.full((k, k, 2 * self.cap2), NEG_INF, dtype=np.float64)
        for q in range(k):
            members = np.flatnonzero(self.sub_part == q)
            if members.size:
                self._write_entries_group(members, q)

    # ------------------------------------------------------------- leaf ops
    def _clear_leaf(self, i: int, p: int) -> None:
        """Empty sub-partition ``i``'s leaf across every (p, dst) tree (it
        leaves ``p``), one repair pass."""
        self.tree[p, :, self.cap2 + i] = NEG_INF
        self._repair_levels(p, slice(None), np.asarray([i]))

    # ------------------------------------------------------------- tree ops
    def _repair_levels(self, src: int, dst_idx, leaves: np.ndarray) -> None:
        """Recompute the internal max nodes above ``leaves`` in the
        ``(src, dst)`` trees selected by ``dst_idx`` (a slice for "all
        destinations" or an index array) - ONE level-by-level pass repairs
        any number of dirty leaves, each level a single K-wide ``maximum``
        instead of per-(dst, leaf) scalar climbs."""
        t = self.tree[src]
        nodes = np.unique((np.asarray(leaves, dtype=np.int64) + self.cap2) >> 1)
        while True:
            if isinstance(dst_idx, slice):
                t[dst_idx, nodes] = np.maximum(
                    t[dst_idx, 2 * nodes], t[dst_idx, 2 * nodes + 1]
                )
            else:
                t[np.ix_(dst_idx, nodes)] = np.maximum(
                    t[np.ix_(dst_idx, 2 * nodes)], t[np.ix_(dst_idx, 2 * nodes + 1)]
                )
            if nodes[0] == 1:  # perfect tree: every leaf reaches the root together
                return
            nodes = np.unique(nodes >> 1)

    def _write_entries(self, i: int) -> None:
        """(Re)write DEC entries of sub-partition ``i`` for all destinations."""
        p = int(self.sub_part[i])
        col = self.m[i] - self.m[i, p]
        col[p] = NEG_INF  # own partition is never a trade destination
        self.tree[p, :, self.cap2 + i] = col
        self._repair_levels(p, slice(None), np.asarray([i]))

    def _write_entries_group(self, members: np.ndarray, q: int) -> None:
        """Batched :meth:`_write_entries` for sub-partitions all living in
        ``q``: one [K, n] leaf write + one repair pass (the Theorem 2 path
        for neighbours in the move's src/dst partitions, whose DEC base
        changed for every destination)."""
        vals = self.m[members] - self.m[members, q][:, None]  # [n, K]
        vals[:, q] = NEG_INF
        self.tree[q][:, self.cap2 + members] = vals.T
        self._repair_levels(q, slice(None), members)

    def _write_pair_group(self, members: np.ndarray, q: int, src: int, dst: int) -> None:
        """Batched Theorem 2 update for neighbours whose home partition ``q``
        is uninvolved in the move: only their (q, src) and (q, dst) entries
        changed, so two leaf-row writes + one two-row repair pass."""
        base = self.m[members, q]
        t = self.tree[q]
        t[src, self.cap2 + members] = self.m[members, src] - base
        t[dst, self.cap2 + members] = self.m[members, dst] - base
        self._repair_levels(q, np.asarray([src, dst]), members)

    def _first_leaf(self, node: int) -> int:
        """The lowest sub-partition id under tree ``node``."""
        return (node << (self._leaf_level + 1 - node.bit_length())) - self.cap2

    def _best_feasible(
        self, src: int, dst: int, best_val: float, best_i: int
    ) -> tuple[int, float] | None:
        """The feasible trade src -> dst that comes first in the tie order,
        if it beats ``(best_val, best_i)``: a larger DEC, or an equal DEC at
        a lower sub-partition id. Pruned descent; ``(i, dec)`` or None."""
        t = self.tree[src, dst]
        if t[1] < best_val:
            return None
        room = self.cap - self.part_load[dst]
        found = None
        stack = [1]
        while stack:
            node = stack.pop()
            val = t[node]
            if val < best_val or (val == best_val and self._first_leaf(node) >= best_i):
                continue
            if node >= self.cap2:  # leaf
                i = node - self.cap2
                if self.size[i] <= room + 1e-9:
                    best_val, best_i = val, i
                    found = (i, float(val))
            else:
                # the larger child first for tighter pruning, the left one
                # (lower ids) on a tie
                l, r = 2 * node, 2 * node + 1
                if t[l] >= t[r]:
                    stack.extend((r, l))
                else:
                    stack.extend((l, r))
        return found

    # ------------------------------------------------------------- main API
    def best_move(self, thresh: float = 0.0) -> tuple[int, int, float] | None:
        """The next trade in the tie order (module docstring):
        ``(sub_part_id, dst, dec)``, or None when no feasible trade lowers
        the cut by more than ``thresh``."""
        best: tuple[int, int, float] | None = None
        best_val, best_i = thresh, -1  # an equal DEC never beats thresh
        for src in range(self.k):
            for dst in range(self.k):
                if src == dst:
                    continue
                got = self._best_feasible(src, dst, best_val, best_i)
                if got is not None:
                    best_i, best_val = got
                    best = (best_i, dst, best_val)
        return best

    def apply_move(self, i: int, dst: int) -> float:
        """Apply trade <S_i, dst>; returns the edge-cut decrease."""
        src = int(self.sub_part[i])
        assert src != dst
        dec = float(self.m[i, dst] - self.m[i, src])
        nbrs = np.flatnonzero(self.w[i])
        wvals = self.w[i, nbrs]
        # --- M updates for neighbours (Eq. 10 in M-form)
        self.m[nbrs, src] -= wvals
        self.m[nbrs, dst] += wvals
        # --- move i itself
        self._clear_leaf(i, src)
        self.sub_part[i] = dst
        self.part_load[src] -= self.size[i]
        self.part_load[dst] += self.size[i]
        self._write_entries(i)
        # --- Theorem 2 updates for neighbours, batched per home partition
        if nbrs.size:
            qs = self.sub_part[nbrs]
            for q in np.unique(qs).tolist():
                members = nbrs[qs == q]
                if q == src or q == dst:
                    # base m[j, q] changed: every destination entry is dirty
                    self._write_entries_group(members, int(q))
                else:
                    self._write_pair_group(members, int(q), src, dst)
        return dec

    def refine(
        self,
        thresh: float = 0.0,
        max_moves: int | None = None,
        spans: SpanRecorder | None = None,
    ) -> RefineStats:
        """Apply trades in the tie order until none lowers the cut by more
        than ``thresh`` (or ``max_moves``); each pick is the span
        ``refine.best`` and each trade ``refine.apply`` of ``spans``."""
        span = (spans or SpanRecorder()).span
        stats = RefineStats()
        while True:
            if max_moves is not None and stats.moves >= max_moves:
                stats.stopped_reason = "max_moves"
                return stats
            with span("refine.best"):
                mv = self.best_move(thresh)
            if mv is None:
                stats.stopped_reason = "maximal" if thresh <= 0 else "thresh"
                return stats
            i, dst, dec = mv
            with span("refine.apply"):
                got = self.apply_move(i, dst)
            assert abs(got - dec) < 1e-6
            stats.moves += 1
            stats.cut_improvement += got

    # ------------------------------------------------------------- debugging
    def current_cut(self) -> float:
        """Edge-cut of the coarsened graph (Prop. 1)."""
        same = self.sub_part[:, None] == self.sub_part[None, :]
        return float(self.w[~same].sum() / 2.0)

    def check_invariants(self) -> None:
        onehot = np.zeros((self.kp, self.k))
        onehot[np.arange(self.kp), self.sub_part] = 1.0
        np.testing.assert_allclose(self.m, self.w @ onehot, atol=1e-6)
        loads = np.bincount(self.sub_part, weights=self.size, minlength=self.k)
        np.testing.assert_allclose(self.part_load, loads, atol=1e-6)
        ids = np.arange(self.kp)
        for src in range(self.k):
            for dst in range(self.k):
                if src == dst:
                    continue
                t = self.tree[src, dst]
                leaves = t[self.cap2 :]
                expect = np.full(self.cap2, NEG_INF)
                own = ids[self.sub_part == src]
                expect[own] = self.m[own, dst] - self.m[own, src]
                np.testing.assert_allclose(leaves, expect, atol=1e-6)
                inner = np.arange(1, self.cap2)
                np.testing.assert_array_equal(
                    t[inner], np.maximum(t[2 * inner], t[2 * inner + 1])
                )


# --------------------------------------------------------------------- swaps
def best_swap(r: "Refiner") -> tuple[int, int, float] | None:
    """Paper §VI future work: when single trades are balance-blocked, a
    *pairwise swap* <S_i in V_a, S_j in V_b> can still improve quality while
    keeping both partitions within capacity. Returns the best (i, j, gain)
    with gain = DEC_i(a->b) + DEC_j(b->a) - 2*W(S_i,S_j), or None.

    O(K'^2) scan over cross-partition neighbour pairs - run only when
    ``refine`` stalls (the greedy single-trade loop is the common path)."""
    best: tuple[int, int, float] | None = None
    kp = r.kp
    for i in range(kp):
        a = int(r.sub_part[i])
        nbrs = np.flatnonzero(r.w[i])
        for j in nbrs:
            j = int(j)
            if j <= i:
                continue
            b = int(r.sub_part[j])
            if a == b:
                continue
            gain = (
                (r.m[i, b] - r.m[i, a])
                + (r.m[j, a] - r.m[j, b])
                - 2.0 * r.w[i, j]  # they stop being cut towards each other... twice-counted
            )
            if gain <= 1e-9:
                continue
            # balance: dest gains size[x] - size[y]
            if r.part_load[b] + r.size[i] - r.size[j] > r.cap + 1e-9:
                continue
            if r.part_load[a] + r.size[j] - r.size[i] > r.cap + 1e-9:
                continue
            if best is None or gain > best[2]:
                best = (i, j, float(gain))
    return best


def refine_with_swaps(r: "Refiner", thresh: float = 0.0,
                      max_rounds: int = 50) -> dict:
    """Alternate greedy single trades with pairwise swaps until neither
    improves (a strictly larger move class than the paper's maximality)."""
    moves = swaps = 0
    improvement = 0.0
    for _ in range(max_rounds):
        stats = r.refine(thresh=thresh)
        moves += stats.moves
        improvement += stats.cut_improvement
        sw = best_swap(r)
        if sw is None:
            break
        i, j, gain = sw
        a, b = int(r.sub_part[i]), int(r.sub_part[j])
        got = r.apply_move(i, b) + r.apply_move(j, a)
        improvement += got
        swaps += 1
    return {"moves": moves, "swaps": swaps, "improvement": improvement}
