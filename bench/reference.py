"""The plain reference: exact range-filtered k nearest neighbours.

Independent of the program: it takes the collection from ``data.py``,
sorts it by attribute itself (stable, as the paper's rank order), and
answers each query over the objects whose rank lies in ``[L, R]``.
Candidates are the ``k + MARGIN`` nearest by an f32 matrix product over the
range; their exact squared L2 distances are then taken in float64 from the
f32 vectors and sorted. The f32 pass errs by about 1e-4 on distances of a
few hundred, far less than the gap between the k-th and the
(k + MARGIN)-th neighbour, so the k chosen are the exact ones.
"""
from __future__ import annotations

import numpy as np

MARGIN = 22
BLOCK = 256


class Collection:
    """The collection in rank order, with what the reference needs."""

    def __init__(self, vectors: np.ndarray, attr: np.ndarray):
        self.perm = np.argsort(attr, kind="stable")     # rank -> original id
        self.rank_of = np.empty_like(self.perm)
        self.rank_of[self.perm] = np.arange(len(self.perm))
        self.attr = attr                                # by original id
        self.sorted_attr = attr[self.perm]
        self.x = np.ascontiguousarray(vectors[self.perm])   # rank order
        self.sq = np.einsum("ij,ij->i", self.x, self.x)
        self.n = len(attr)

    def exact_dist(self, q: np.ndarray, orig_ids: np.ndarray) -> np.ndarray:
        """float64 squared L2 distances of one query to original ids."""
        x = self.x[self.rank_of[orig_ids]].astype(np.float64)
        return ((x - q.astype(np.float64)) ** 2).sum(-1)

    def topk(self, q: np.ndarray, L: np.ndarray, R: np.ndarray, k: int):
        """Exact in-range top-k: (original ids int64[B, k], float64 squared
        distances [B, k]), nearest first; -1 / inf past a range's size."""
        B = len(q)
        ids = np.full((B, k), -1, np.int64)
        dist = np.full((B, k), np.inf)
        for s in range(0, B, BLOCK):
            qb = q[s:s + BLOCK]
            lo, hi = int(L[s:s + BLOCK].min()), int(R[s:s + BLOCK].max())
            d32 = (self.sq[lo:hi + 1][None, :]
                   - 2.0 * (qb @ self.x[lo:hi + 1].T))
            for i in range(len(qb)):
                a, b = int(L[s + i]) - lo, int(R[s + i]) - lo
                if b < a:
                    continue
                row = d32[i, a:b + 1]
                c = min(k + MARGIN, len(row))
                cand = np.argpartition(row, c - 1)[:c] + a + lo   # ranks
                exact = ((self.x[cand].astype(np.float64)
                          - qb[i].astype(np.float64)) ** 2).sum(-1)
                o = np.argsort(exact, kind="stable")[:k]
                ids[s + i, :len(o)] = self.perm[cand[o]]
                dist[s + i, :len(o)] = exact[o]
        return ids, dist
