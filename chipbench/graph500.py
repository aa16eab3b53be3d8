"""The benchmark's own data: Graph 500 Kronecker graphs and their change stream.

A copy of the generator semantics the program ships (R-MAT edges, the
CommonGraph paper's half-add/half-delete change protocol, weights hashed
from the edge key) and of Graph 500's search-key sampling and vertex
relabelling, kept here so that the yardstick cannot move when the
program does. Pure numpy: this module imports nothing of the program.
"""

from __future__ import annotations

import numpy as np


def edge_keys(src: np.ndarray, dst: np.ndarray, num_nodes: int) -> np.ndarray:
    """Injective int64 key ``src * num_nodes + dst`` (sorted keys are src-major)."""
    return src.astype(np.int64) * np.int64(num_nodes) + dst.astype(np.int64)


def rmat_keys(scale: int, num_edges: int, seed: int, a: float, b: float,
              c: float) -> np.ndarray:
    """Graph 500 Kronecker (R-MAT) edges as unsorted unique keys.

    ``scale`` bits per endpoint, quadrant probabilities a/b/c (d = the
    rest), duplicates and self-loops dropped, then ``num_edges`` kept.
    """
    rng = np.random.default_rng(seed)
    num_nodes = 1 << scale
    n_draw = int(num_edges * 1.3) + 16
    src = np.zeros(n_draw, dtype=np.int64)
    dst = np.zeros(n_draw, dtype=np.int64)
    for _ in range(scale):
        r = rng.random(n_draw)
        src = (src << 1) | (r >= a + b)
        dst = (dst << 1) | (((r >= a) & (r < a + b)) | (r >= a + b + c))
    keep = src != dst
    keys = np.unique(edge_keys(src[keep], dst[keep], num_nodes))
    rng.shuffle(keys)
    return keys[:num_edges]


def evolving_keys(scale: int, edge_factor: int, snapshots: int, changes: int,
                  seed: int, a: float, b: float, c: float):
    """Snapshot key sets under the change protocol of arXiv 2308.14834 §5.

    Each transition deletes ``changes // 2`` present edges and adds as many
    absent ones. Returns ``(snapshot_keys, additions, deletions)``, each a
    list of sorted int64 key arrays.
    """
    num_nodes = 1 << scale
    rng = np.random.default_rng(seed + 1)
    current = np.sort(rmat_keys(scale, edge_factor * num_nodes, seed, a, b, c))
    half = changes // 2
    snaps, adds, dels = [current], [], []
    for _ in range(snapshots - 1):
        del_keys = np.sort(current[rng.choice(current.shape[0], size=half,
                                              replace=False)])
        add_keys = np.empty(0, dtype=np.int64)
        while add_keys.shape[0] < half:
            s = rng.integers(0, num_nodes, size=2 * half)
            d = rng.integers(0, num_nodes, size=2 * half)
            ok = s != d
            cand = np.unique(edge_keys(s[ok], d[ok], num_nodes))
            cand = cand[~np.isin(cand, current)]
            add_keys = np.unique(np.concatenate([add_keys, cand]))
        add_keys = np.sort(rng.permutation(add_keys)[:half])
        current = np.union1d(np.setdiff1d(current, del_keys, assume_unique=True),
                             add_keys)
        snaps.append(current)
        adds.append(add_keys)
        dels.append(del_keys)
    return snaps, adds, dels


def edge_weights(keys: np.ndarray, weight_seed: int = 0) -> np.ndarray:
    """Per-edge float32 weight in (0, 1], a hash of the key (stable over snapshots)."""
    h = (keys.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
         + np.uint64(weight_seed)) >> np.uint64(1)
    u = (h % np.uint64(1 << 24)).astype(np.float64) / float(1 << 24)
    return (u * (1.0 - 1e-3) + 1e-3).astype(np.float32)


class History:
    """A deployment's history under one run's vertex labels.

    The deployment (its Kronecker edges, change stream, weights and search
    keys) is drawn once from the configuration's ``graph_seed``. A run's
    seed permutes the vertex labels, as Graph 500 does, and the order in
    which the search keys are asked. Every seed so runs an isomorphic
    history with the same work, under other labels and in another order.
    """

    def __init__(self, config: dict, seed: int):
        self.graph_seed = config["graph_seed"]
        self.num_nodes = n = 1 << config["scale"]
        base = evolving_keys(config["scale"], config["edge_factor"],
                             config["snapshots"],
                             config["changes_per_transition"], self.graph_seed,
                             config["a"], config["b"], config["c"])
        self.label = np.random.default_rng([seed, 0]).permutation(n)
        self.unlabel = np.argsort(self.label)
        self._base_sources = np.unique(base[0][0] // n)
        self.snapshots, self.additions, self.deletions = (
            [np.sort(edge_keys(self.label[k // n], self.label[k % n], n))
             for k in keys] for keys in base)

    def weights(self, keys: np.ndarray) -> np.ndarray:
        """Float32 weights of edges given by their keys in this run's labels."""
        n = self.num_nodes
        base = edge_keys(self.unlabel[keys // n], self.unlabel[keys % n], n)
        return edge_weights(base, self.graph_seed)

    def search_keys(self, count: int, order: "np.random.Generator | None" = None,
                    block: int = 8) -> np.ndarray:
        """Graph 500 search keys: ``count`` distinct vertices with out-degree >= 1.

        The deployment's own keys in this run's labels; with ``order``,
        shuffled within consecutive blocks of ``block``.
        """
        rng = np.random.default_rng([self.graph_seed, 1])
        keys = self.label[rng.choice(self._base_sources, size=count,
                                     replace=False)]
        if order is not None:
            for i in range(0, count, block):
                keys[i:i + block] = order.permutation(keys[i:i + block])
        return keys.astype(np.int64)


def isolated_vertices(snapshot_keys, num_nodes: int, count: int,
                      rng: np.random.Generator) -> "list[int]":
    """Up to ``count`` vertices with no out-edge in any snapshot."""
    has_out = np.zeros(num_nodes, bool)
    for keys in snapshot_keys:
        has_out[keys // num_nodes] = True
    free = np.flatnonzero(~has_out)
    return [int(v) for v in rng.choice(free, size=min(count, free.size),
                                       replace=False)]
