"""Plain reference: the path-query fixpoint of one edge set, in numpy on the host.

The configurations state exact answers: every answer equals the unique
fixpoint ``val[v] = min(val[v], min over edges (u, v, w) of val[u] (x) w)``
from ``val[source] = 0``, evaluated in float32 (SSSP: ``(x)`` is float32
addition of the edge weight; BFS: ``+ 1``). Relaxing in any order reaches
that fixpoint: each value is the left-to-right float32 sum along its best
path, so the comparison is exact, with limit 0.

The control (``rounding="bfloat16"``) is the same reference with every
value rounded to bfloat16, the next precision below the one stated.
This module imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

ALGS = ("sssp", "bfs")


def _round_bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes
    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


class EdgeSet:
    """One edge set (a snapshot or a window's common graph), sorted by dst."""

    def __init__(self, keys: np.ndarray, num_nodes: int, weights):
        src = (keys // num_nodes).astype(np.int32)
        dst = (keys % num_nodes).astype(np.int32)
        order = np.argsort(dst, kind="stable")
        self.num_nodes = num_nodes
        self.src, self.dst = src[order], dst[order]
        self.w = np.asarray(weights(keys), np.float32)[order]

    def fixpoint(self, alg: str, source: int,
                 rounding: str = "float32") -> np.ndarray:
        """Frontier Bellman-Ford to the fixpoint; float32 values."""
        if alg not in ALGS:
            raise ValueError(f"no reference for {alg!r}")
        rnd = _round_bf16 if rounding == "bfloat16" else (lambda x: x)
        w = rnd(self.w) if alg == "sssp" else np.ones_like(self.w)
        vals = np.full(self.num_nodes, np.inf, np.float32)
        vals[source] = 0.0
        frontier = np.zeros(self.num_nodes, bool)
        frontier[source] = True
        while True:
            act = frontier[self.src]
            d = self.dst[act]            # still sorted by dst
            if d.size == 0:
                return vals
            cand = rnd(vals[self.src[act]] + w[act])
            starts = np.flatnonzero(np.r_[True, d[1:] != d[:-1]])
            best, seg = np.minimum.reduceat(cand, starts), d[starts]
            better = best < vals[seg]
            vals[seg[better]] = best[better]
            frontier[:] = False
            frontier[seg[better]] = True


class Reference:
    """Fixpoints over the snapshots and windows of one generated history.

    ``weights`` maps an array of edge keys to their float32 weights.
    """

    def __init__(self, snapshot_keys, num_nodes: int, weights):
        self.snapshot_keys = snapshot_keys
        self.num_nodes = num_nodes
        self.weights = weights
        self._sets: dict = {}

    def edge_set(self, window: "tuple[int, int]") -> EdgeSet:
        """The common graph of snapshots ``lo..hi`` (a snapshot when equal)."""
        if window not in self._sets:
            lo, hi = window
            keys = self.snapshot_keys[lo]
            for k in range(lo + 1, hi + 1):
                keys = np.intersect1d(keys, self.snapshot_keys[k],
                                      assume_unique=True)
            self._sets[window] = EdgeSet(keys, self.num_nodes, self.weights)
        return self._sets[window]

    def answer(self, window, alg: str, source: int,
               rounding: str = "float32") -> np.ndarray:
        return self.edge_set(tuple(window)).fixpoint(alg, int(source), rounding)


def mismatched(values, expected: np.ndarray) -> int:
    """Vertices whose value differs from the reference's (exact comparison)."""
    got = np.asarray(values, dtype=np.float32).reshape(-1)
    if got.shape != expected.shape:
        return int(expected.size)
    return int(np.count_nonzero(got != expected))
