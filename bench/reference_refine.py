"""Plain reference of full parallel CUTTANA: phase 1, sub-partitions and
phase-2 refinement (CUTTANA paper, arXiv 2312.08356, §III-B), importing
nothing of the system under test.

* Phase 1 is :func:`bench.reference.cuttana_parallel`, run as it is. The
  placements of each superstep, shard after shard in placement order, are
  read from its calls to ``reference._expand`` (every shard placed in one
  superstep is handed the same ``room`` array).
* Def. 2, sub-partitions, with the program's settings: ``subparts`` per
  part, ``max(8, min(4096, n // (8k)))``; sub-epsilon ``max(epsilon,
  0.10)``; each placed vertex scores the sub-partitions of its part by
  ``hist - 0.125 * size / cap``, ``hist`` its neighbours in each,
  ``size = (|S| + mu * E_S) / 2``, ``cap`` the same of the hard caps, and
  ``mu = |V| / 2|E|``; a sub-partition whose degree mass would pass
  ``(1 + sub-epsilon) * 2|E| / K'`` is closed to it, and a vertex every one
  is closed to takes the one of least degree mass. A superstep's vertices
  are taken ``wave`` at a time: a wave counts its neighbours and sizes as
  they stood before it, ties go to the lowest sub-partition, and a wave
  whose picks together would pass a sub-partition's cap is placed again one
  vertex at a time against live sizes.
* Def. 3: ``W[i, j]`` is the number of edges between ``S_i`` and ``S_j``.
* Phase 2, greedy trades until none lowers the cut by more than ``thresh``
  (0, refine until maximal, as the configuration states):
  ``M[i, p]`` is the weight from ``S_i`` into part ``p``, kept by Eq. 10;
  every move recomputes the masked decrease matrix ``DEC[i, dst] =
  M[i, dst] - M[i, P'(i)]`` (feasible: ``dst != P'(i)`` and ``size[i] <=
  cap - load[dst] + 1e-9``, ``size`` the degree mass, ``cap = (1 +
  epsilon) * 2|E| / k``) and takes its ``np.argmax``: the largest decrease,
  then the lowest sub-partition id, then the lowest destination.
  ``tie="high"`` breaks ties to the highest sub-partition id instead: it
  serves the control that has to fail (``bench/jobs/cuttana_refine.py``).

Edge balance, as every partition cell states. Counts are integers, so every
comparison is exact.
"""
from __future__ import annotations

import sys

import numpy as np

from bench import reference


def phase1_supersteps(indptr, indices, k, seed, num_shards, **kwargs):
    """``(part, steps)``: the phase-1 reference's answer, and for each
    superstep the vertices it placed, shard after shard in placement
    order."""
    steps: list[tuple[np.ndarray, list[np.ndarray]]] = []
    expand = reference._expand

    def spy(indptr_, indices_, batch):
        caller = sys._getframe(1)
        if caller.f_code.co_name == "place":
            room = caller.f_locals["room"]
            if not steps or steps[-1][0] is not room:
                steps.append((room, []))
            steps[-1][1].append(batch)
        return expand(indptr_, indices_, batch)

    reference._expand = spy
    try:
        part = reference.cuttana_parallel(
            indptr, indices, k, seed, num_shards=num_shards, **kwargs
        )
    finally:
        reference._expand = expand
    return part, [np.concatenate(batches) for _, batches in steps]


def subpartitions(indptr, indices, k, part, steps, subparts, sub_epsilon, wave=128):
    """Def. 2 over phase 1's supersteps; returns ``(sub_of, mass)``, the
    sub-partition of every vertex and each sub-partition's degree mass."""
    n = indptr.shape[0] - 1
    nnz = indices.shape[0]
    s = int(subparts)
    kp = k * s
    deg = np.diff(indptr).astype(np.float64)
    mu = max(n, 1) / max(nnz, 1)
    v_cap = (1.0 + sub_epsilon) * max(n, 1) / kp
    e_cap = (1.0 + sub_epsilon) * nnz / kp
    cap = max(0.5 * (v_cap + mu * e_cap), 1e-9)
    sub_of = np.full(n, -1, dtype=np.int64)
    count = np.zeros((k, s))
    mass = np.zeros((k, s))
    for vs in steps:
        for g0 in range(0, vs.shape[0], wave):
            batch = vs[g0 : g0 + wave]
            g = batch.shape[0]
            ps = part[batch]
            dw = deg[batch]
            rows, cols = reference._expand(indptr, indices, batch)
            nb = sub_of[cols]
            inside = nb // s == ps[rows]  # an unplaced neighbour's -1 is in no part
            hist = np.zeros((g, s))
            np.add.at(hist, (rows[inside], nb[inside] - ps[rows[inside]] * s), 1)
            size = 0.5 * (count[ps] + mu * mass[ps])
            closed = mass[ps] + dw[:, None] > e_cap
            score = np.where(closed, -np.inf, hist - 0.125 * (size / cap))
            local = score.argmax(axis=1)
            stuck = score[np.arange(g), local] == -np.inf
            local[stuck] = mass[ps[stuck]].argmin(axis=1)
            add = np.zeros((k, s))
            np.add.at(add, (ps, local), dw)
            over = mass + add > e_cap
            if over[ps[~stuck], local[~stuck]].any():
                for r in range(g):
                    p = ps[r]
                    size_r = 0.5 * (count[p] + mu * mass[p])
                    sc = np.where(mass[p] + dw[r] > e_cap, -np.inf,
                                  hist[r] - 0.125 * (size_r / cap))
                    local[r] = sc.argmax() if sc.max() > -np.inf else mass[p].argmin()
                    count[p, local[r]] += 1
                    mass[p, local[r]] += dw[r]
            else:
                np.add.at(count, (ps, local), 1)
                mass += add
            sub_of[batch] = ps * s + local
    return sub_of, mass.ravel()


def subpartition_graph(indptr, indices, sub_of, kp):
    """Def. 3: ``W[i, j]``, the number of edges between ``S_i`` and ``S_j``
    (each undirected edge once, both ways; none on the diagonal)."""
    src = np.repeat(np.arange(indptr.shape[0] - 1), np.diff(indptr))
    one = src < indices
    a, b = sub_of[src[one]], sub_of[indices[one]]
    cross = a != b
    a, b = a[cross], b[cross]
    both = np.concatenate([a * kp + b, b * kp + a])
    return np.bincount(both, minlength=kp * kp).reshape(kp, kp)


def greedy_trades(w, home, size, k, cap, thresh=0.0, tie="low"):
    """Phase 2 from the sub-partitions' parts ``home``; returns their final
    parts and the number of moves."""
    kp = w.shape[0]
    home = home.copy()
    ids = np.arange(kp)
    m = np.zeros((kp, k), dtype=np.int64)
    for p in range(k):
        m[:, p] = w[:, home == p].sum(axis=1)
    load = np.bincount(home, weights=size, minlength=k)
    moves = 0
    while True:
        dec = (m - m[ids, home][:, None]).astype(np.float64)
        feasible = size[:, None] <= cap - load[None, :] + 1e-9
        feasible[ids, home] = False
        dec[~feasible] = -np.inf
        if tie == "low":
            i, dst = divmod(int(np.argmax(dec)), k)
        else:
            r, dst = divmod(int(np.argmax(dec[::-1])), k)
            i = kp - 1 - r
        if not dec[i, dst] > thresh:
            break
        src = home[i]
        m[:, src] -= w[i]  # Eq. 10; W is symmetric
        m[:, dst] += w[i]
        load[src] -= size[i]
        load[dst] += size[i]
        home[i] = dst
        moves += 1
    return home, moves


def cuttana_refine(
    indptr: np.ndarray,
    indices: np.ndarray,
    k: int,
    seed: int,
    num_shards: int,
    epsilon: float = 0.05,
    tie: str = "low",
) -> np.ndarray:
    """Full parallel CUTTANA, edge balance: every vertex's final part."""
    n = indptr.shape[0] - 1
    subparts = int(max(8, min(4096, n // (8 * k))))
    part, steps = phase1_supersteps(indptr, indices, k, seed, num_shards, epsilon=epsilon)
    sub_of, mass = subpartitions(
        indptr, indices, k, part, steps, subparts, max(epsilon, 0.10)
    )
    kp = k * subparts
    w = subpartition_graph(indptr, indices, sub_of, kp)
    cap = (1.0 + epsilon) * indices.shape[0] / k
    home = np.repeat(np.arange(k), subparts)
    final, _ = greedy_trades(w, home, mass, k, cap, tie=tie)
    return final[sub_of]
