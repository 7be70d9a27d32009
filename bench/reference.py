"""Plain references the benchmark judges the system against.

Each is a straightforward implementation of the semantics the configuration
states, written from the published algorithms and the repository's
documented contracts, importing nothing of the system under test:

* :func:`edge_cut` - the normalised edge-cut (CUTTANA paper, Eq. 3);
* :func:`pagerank` - float64 power iteration, ``np.bincount`` reduces;
* :func:`fennel` - sequential FENNEL (Tsourakakis et al., WSDM'14) with
  the PowerLyra edge-balance penalty of the CUTTANA paper's Eq. 7, one
  vertex at a time in stream order, ties broken by a seeded draw;
* :func:`cuttana_parallel` - CUTTANA's Algorithm 1 (the Eq. 6 priority
  buffer with the D_max bypass) over ``S`` interleaved shard cursors in
  bulk-synchronous supersteps, the paper's parallel design, with phase-2
  refinement off.

Graphs are plain ``(indptr, indices)`` CSR arrays, symmetric, each
undirected edge stored in both rows. Scores are float64, as the system
computes them. ``nbr_cap`` makes the neighbour histograms count at most the
first ``nbr_cap`` neighbours of a vertex: it only serves the control that
has to fail (``bench/control.py``).
"""
from __future__ import annotations

import heapq

import numpy as np


def edge_cut(indptr: np.ndarray, indices: np.ndarray, part: np.ndarray) -> float:
    """Share of undirected edges whose endpoints lie in different parts."""
    src = np.repeat(np.arange(indptr.shape[0] - 1), np.diff(indptr))
    cut = int(np.count_nonzero(part[src] != part[indices]))
    return (cut // 2) / max(indices.shape[0] // 2, 1)


def pagerank(
    indptr: np.ndarray, indices: np.ndarray, iters: int, damping: float = 0.85
) -> np.ndarray:
    """float64 PageRank by power iteration from the uniform vector; each
    vertex sends ``x / degree`` along every edge."""
    n = indptr.shape[0] - 1
    deg = np.diff(indptr)
    src = np.repeat(np.arange(n), deg)
    inv_deg = 1.0 / np.maximum(deg, 1).astype(np.float64)
    x = np.full(n, 1.0 / n)
    for _ in range(iters):
        agg = np.bincount(src, weights=(x * inv_deg)[indices], minlength=n)
        x = (1.0 - damping) / n + damping * agg
    return x


# --------------------------------------------------------------- scoring
class _Eq7:
    """FENNEL Eq. 7 with the edge-balance (PowerLyra hybrid) size:
    ``score_p = hist_p - alpha * gamma * size_p ** (gamma - 1)``,
    ``size_p = (|V_p| + mu * E_p) / 2``, ``mu = |V| / 2|E|``,
    ``alpha = sqrt(k) * |E| / |V| ** 1.5``."""

    def __init__(self, indptr, indices, k, gamma):
        n = max(indptr.shape[0] - 1, 1)
        m = max(indices.shape[0] // 2, 1)
        alpha = 1.0 * np.sqrt(k) * m / (n**1.5)
        self.ag = float(alpha * gamma)
        self.gm1 = gamma - 1.0
        self.mu = n / max(indices.shape[0], 1)

    def penalty(self, v_counts, e_counts):
        """``alpha * gamma * size ** (gamma - 1)`` over arrays of counts."""
        size = 0.5 * (v_counts + self.mu * e_counts)
        return self.ag * np.power(np.maximum(size, 0.0), self.gm1)

    def penalty_of(self, v: float, e: float) -> float:
        """The penalty of one part, in scalar arithmetic (libm ``pow``)."""
        size = max(0.5 * (float(v) + self.mu * float(e)), 0.0)
        return self.ag * size**self.gm1


# ---------------------------------------------------------------- FENNEL
def fennel(
    indptr: np.ndarray,
    indices: np.ndarray,
    k: int,
    seed: int,
    epsilon: float = 0.05,
    gamma: float = 1.5,
    chunk: int = 512,
    nbr_cap: int | None = None,
) -> np.ndarray:
    """Sequential edge-balanced FENNEL over the seed's random stream order.

    Each vertex, in order, counts its assigned neighbours per part, scores
    the parts by Eq. 7, and goes to the best part whose edge load stays
    within ``(1 + epsilon) * 2|E| / k`` after it. Parts within 1e-12 of the
    best are tied and one is drawn from ``default_rng(seed)``; when no part
    has room, the least loaded one takes the vertex.
    """
    n = indptr.shape[0] - 1
    order = np.random.default_rng(seed).permutation(n)
    tie_rng = np.random.default_rng(seed)
    eq7 = _Eq7(indptr, indices, k, gamma)
    cap = (1.0 + epsilon) * indices.shape[0] / k
    deg = np.diff(indptr)
    part = np.full(n, -1, dtype=np.int64)
    v_counts = np.zeros(k)
    e_counts = np.zeros(k)
    cap_n = n if nbr_cap is None else nbr_cap
    for c0 in range(0, n, chunk):
        # the penalties are taken afresh over all parts every ``chunk``
        # vertices, and kept up to date one part at a time in between
        pen = eq7.penalty(v_counts, e_counts)
        for v in order[c0 : c0 + chunk].tolist():
            d = int(deg[v])
            nbr_parts = part[indices[indptr[v] : indptr[v] + min(d, cap_n)]]
            hist = np.bincount(nbr_parts[nbr_parts >= 0], minlength=k).astype(np.float64)
            scores = np.where(e_counts + d <= cap, hist - pen, -np.inf)
            best = scores.max()
            if best == -np.inf:
                p = int(e_counts.argmin())
            else:
                ties = np.flatnonzero(scores >= best - 1e-12)
                p = int(ties[0] if ties.size == 1 else ties[tie_rng.integers(ties.size)])
            part[v] = p
            v_counts[p] += 1
            e_counts[p] += d
            pen[p] = eq7.penalty_of(v_counts[p], e_counts[p])
    return part


# ------------------------------------------------------ parallel CUTTANA
class _Buffer:
    """CUTTANA's bounded priority buffer (Eq. 6): the best-scored vertex
    leaves first, ``score = deg / d_max + theta * assigned / deg``, ties to
    the lower vertex id."""

    def __init__(self, capacity, d_max, theta, deg):
        self.capacity, self.d_max, self.theta, self.deg = capacity, d_max, theta, deg
        self.assigned: dict[int, int] = {}
        self.heap: list[tuple[float, int]] = []

    def __len__(self):
        return len(self.assigned)

    def _score(self, v: int) -> float:
        d = int(self.deg[v])
        return d / self.d_max + self.theta * self.assigned[v] / max(d, 1)

    def push(self, v: int, assigned: int) -> None:
        self.assigned[v] = assigned
        heapq.heappush(self.heap, (-self._score(v), v))

    def pop_best(self) -> int:
        while True:
            neg, v = heapq.heappop(self.heap)
            if v in self.assigned and -self._score(v) == neg:
                del self.assigned[v]
                return v

    def placed(self, nbr_ids: np.ndarray) -> list[int]:
        """Neighbours in ``nbr_ids`` (one entry per placed neighbour) were
        assigned: bump the buffered ones, and take out and return those
        whose every neighbour is now assigned, in order of first mention."""
        done = []
        counts: dict[int, int] = {}
        for w in nbr_ids.tolist():
            if w in self.assigned:
                counts[w] = counts.get(w, 0) + 1
        for w, c in counts.items():
            self.assigned[w] += c
            if self.assigned[w] >= self.deg[w]:
                del self.assigned[w]
                done.append(w)
            else:
                heapq.heappush(self.heap, (-self._score(w), w))
        return done


def _expand(indptr, indices, batch):
    """``(rows, cols)``: row index in ``batch`` and neighbour id of every
    neighbour slot of the batch, row after row."""
    degs = indptr[batch + 1] - indptr[batch]
    rows = np.repeat(np.arange(batch.shape[0]), degs)
    first = np.repeat(indptr[batch] - np.cumsum(degs) + degs, degs)
    return rows, indices[first + np.arange(rows.shape[0])]


def cuttana_parallel(
    indptr: np.ndarray,
    indices: np.ndarray,
    k: int,
    seed: int,
    num_shards: int,
    epsilon: float = 0.05,
    gamma: float = 1.5,
    chunk: int = 512,
    d_max: int = 1000,
    max_qsize: int | None = None,
    theta: float = 1.0,
    wave: int = 128,
    nbr_cap: int | None = None,
) -> np.ndarray:
    """Phase 1 of parallel CUTTANA, edge balance, over the seed's random
    stream order dealt round-robin to ``num_shards`` shard cursors.

    A superstep: every shard takes its next ``chunk`` stream vertices.
    Vertices of degree >= ``d_max``, and those whose neighbours are all
    assigned, become candidates at once; the rest enter the shard's buffer,
    and a full buffer gives up its best vertices as candidates. Vertices that
    a placement completed come first in their shard's next candidate list;
    once a cursor is spent, its buffer drains ``chunk`` vertices a superstep.

    Every shard scores its candidates against the assignment as it stood
    when the superstep began, and places them ``wave`` at a time: the
    shard's own loads and its in-shard neighbours are brought up to date
    between waves, not within one. A shard may fill each part by its share
    of the part's remaining room. Where a wave's picks together overrun a
    part's room, the wave is placed again one vertex at a time against live
    loads with the same scores. Ties go to the lowest part; a vertex that
    fits nowhere goes to the least loaded part. At the end of the superstep
    all placements are applied, and every buffer hears of every placed
    vertex's neighbours.
    """
    n = indptr.shape[0] - 1
    s_count = int(num_shards)
    if max_qsize is None:
        max_qsize = max(1024, n // 10)
    order = np.random.default_rng(seed).permutation(n)
    shards = [order[s::s_count] for s in range(s_count)]
    deg = np.diff(indptr)
    eq7 = _Eq7(indptr, indices, k, gamma)
    cap = (1.0 + epsilon) * indices.shape[0] / k
    part = np.full(n, -1, dtype=np.int64)
    v_counts = np.zeros(k)
    e_counts = np.zeros(k)
    bufs = [_Buffer(max_qsize, max(int(d_max), 1), theta, deg) for _ in shards]
    pending: list[list[int]] = [[] for _ in shards]
    cursor = [0] * s_count
    pos = np.full(n, -1, dtype=np.int64)

    def admit(s: int) -> np.ndarray:
        cand, pending[s] = pending[s], []
        buf = bufs[s]
        take = shards[s][cursor[s] : cursor[s] + chunk]
        cursor[s] += take.shape[0]
        if take.shape[0]:
            rows, cols = _expand(indptr, indices, take)
            known = np.bincount(rows[part[cols] >= 0], minlength=take.shape[0])
            for v, a in zip(take.tolist(), known.tolist()):
                d = int(deg[v])
                if d >= d_max or (a == d and d > 0):
                    cand.append(v)
                else:
                    buf.push(v, a)
            while len(buf) >= buf.capacity:
                cand.append(buf.pop_best())
        else:
            for _ in range(max(chunk - len(cand), 0)):
                if not len(buf):
                    break
                cand.append(buf.pop_best())
        return np.asarray(cand, dtype=np.int64)

    def place(batch, room):
        """One shard's superstep placement (see the docstring above)."""
        c = batch.shape[0]
        rows, cols = _expand(indptr, indices, batch)
        known = part[cols] >= 0
        if nbr_cap is not None:
            known &= (np.arange(rows.shape[0]) - np.searchsorted(rows, rows)) < nbr_cap
        hist = np.zeros((c, k))
        np.add.at(hist, (rows[known], part[cols[known]]), 1)
        # (earlier, later) candidate pairs that are neighbours
        pos[batch] = np.arange(c)
        earlier = pos[cols]
        pos[batch] = -1
        pair = (earlier >= 0) & (earlier < rows)
        src, dst = earlier[pair], rows[pair]
        inc = deg[batch].astype(np.float64)
        v_loc, e_loc = v_counts.copy(), e_counts.copy()
        used = np.zeros(k)
        out = np.empty(c, dtype=np.int64)
        for g0 in range(0, c, wave):
            g1 = min(g0 + wave, c)
            sc = hist[g0:g1] - eq7.penalty(v_loc, e_loc)
            w_inc = inc[g0:g1]
            fits = used + w_inc[:, None] <= room
            masked = np.where(fits, sc, -np.inf)
            choice = masked.argmax(axis=1)
            stuck = masked[np.arange(g1 - g0), choice] == -np.inf
            choice[stuck] = e_loc.argmin()
            add = np.bincount(choice, weights=w_inc, minlength=k)
            over = used + add > room
            if over[choice[~stuck]].any():
                for i in range(g1 - g0):
                    m = np.where(used + w_inc[i] <= room, sc[i], -np.inf)
                    p = int(m.argmax()) if m.max() > -np.inf else int(e_loc.argmin())
                    choice[i] = p
                    used[p] += w_inc[i]
                    v_loc[p] += 1
                    e_loc[p] += w_inc[i]
            else:
                used += add
                v_loc += np.bincount(choice, minlength=k)
                e_loc += add
            out[g0:g1] = choice
            later = (src >= g0) & (src < g1) & (dst >= g1)
            np.add.at(hist, (dst[later], choice[src[later] - g0]), 1)
        return out, cols

    while True:
        cands = [admit(s) for s in range(s_count)]
        if not any(c.shape[0] for c in cands):
            spent = all(cursor[s] >= shards[s].shape[0] for s in range(s_count))
            if spent and not any(len(b) for b in bufs):
                break
            continue
        active = sum(1 for c in cands if c.shape[0])
        room = np.maximum(cap - e_counts, 0.0) / active
        placed = [place(c, room) if c.shape[0] else None for c in cands]
        for c, res in zip(cands, placed):
            if res is not None:
                part[c] = res[0]
                v_counts += np.bincount(res[0], minlength=k)
                e_counts += np.bincount(res[0], weights=deg[c].astype(np.float64), minlength=k)
        nbrs = np.concatenate([res[1] for res in placed if res is not None])
        for s in range(s_count):
            if len(bufs[s]):
                pending[s].extend(bufs[s].placed(nbrs))
    return part
