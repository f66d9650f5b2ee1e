"""The benchmark's own data and plain reference: the graph generator and a
host CSR BFS over the benchmark's own record of every edge it declared.

Nothing here imports the program. ``HostGraph`` is copied from
``chip_smoke.py`` (PR 21), ``power_law_dag`` from
``stl_fusion_tpu/graph/synthetic.py``: the same edges in the same order for
the same seed, de-duplicated without the 30 M-key ``np.unique`` argsort.
"""
from __future__ import annotations

import numpy as np


def power_law_dag(n_nodes: int, avg_degree: float = 3.0, seed: int = 0,
                  alpha: float = 0.8):
    """Preferential-attachment DAG: node d depends on ~avg_degree earlier
    nodes, biased toward low ids by ``rand**(1/alpha)``. Returns (src, dst)
    int32, sorted by (src, dst), duplicates dropped: exactly what the
    program's generator returns for this seed."""
    rng = np.random.default_rng(seed)
    k = max(int(round(avg_degree)), 1)
    dst = np.repeat(np.arange(1, n_nodes, dtype=np.int64), k)
    u = rng.random(dst.shape[0])
    src = np.floor((u ** (1.0 / alpha)) * dst).astype(np.int64)
    np.minimum(src, dst - 1, out=src)
    key = src * n_nodes + dst
    del src, dst, u
    key.sort()
    keep = np.ones(key.shape[0], dtype=bool)
    np.not_equal(key[1:], key[:-1], out=keep[1:])
    key = key[keep]
    return (key // n_nodes).astype(np.int32), (key % n_nodes).astype(np.int32)


class HostGraph:
    """Record of the dependency topology (the generated DAG plus every churn
    edge the benchmark declared, in the order declared), with a vectorised
    CSR BFS for large closures and a set BFS for small ones. The generated
    DAG's CSR is built once; the few declared edges ride beside it, so a
    closure over the topology of any earlier moment costs no rebuild."""

    def __init__(self, src: np.ndarray, dst: np.ndarray, n: int):
        self.n = n
        self._src = [np.asarray(src, dtype=np.int64)]
        self._dst = [np.asarray(dst, dtype=np.int64)]
        self._csr = None
        self._extra = (1, None)  # (chunks, (has[n], src sorted, dst))

    def add_edges(self, src: np.ndarray, dst: np.ndarray) -> None:
        self._src.append(np.asarray(src, dtype=np.int64))
        self._dst.append(np.asarray(dst, dtype=np.int64))

    @property
    def chunks(self) -> int:
        """How many edge batches are on record (1 = the generated DAG)."""
        return len(self._src)

    def edges_declared(self) -> int:
        return int(sum(len(s) for s in self._src[1:]))

    def _base(self):
        """CSR (starts, out-neighbors) over the generated DAG."""
        if self._csr is None:
            src, dst = self._src[0], self._dst[0]
            if not (src[1:] >= src[:-1]).all():  # the generator sorts by src
                order = np.argsort(src, kind="stable")
                dst = dst[order]
            starts = np.zeros(self.n + 1, dtype=np.int64)
            np.cumsum(np.bincount(src, minlength=self.n), out=starts[1:])
            self._csr = (starts, dst)
        return self._csr

    def _declared(self, chunks=None):
        """The declared edges of batches 1..chunks (default: all), sorted by
        source, and bool[n] of the nodes that have any; None where none."""
        chunks = len(self._src) if chunks is None else chunks
        if self._extra[0] != chunks:
            table = None
            if chunks > 1:
                src = np.concatenate(self._src[1:chunks])
                dst = np.concatenate(self._dst[1:chunks])
                order = np.argsort(src, kind="stable")
                has = np.zeros(self.n, dtype=bool)
                has[src] = True
                table = (has, src[order], dst[order])
            self._extra = (chunks, table)
        return self._extra[1]

    def in_degree(self) -> np.ndarray:
        return np.bincount(np.concatenate(self._dst), minlength=self.n)

    def levels(self) -> np.ndarray:
        """int32[n]: each node's longest-path level over the generated DAG
        (a node with no in-edge is level 0; otherwise one more than its
        deepest in-neighbor), by peeling in-degree-0 frontiers. An edge from
        a lower level to a higher one keeps every node's level."""
        starts, nbr = self._base()
        indeg = np.bincount(self._dst[0], minlength=self.n)
        level = np.zeros(self.n, dtype=np.int32)
        frontier = np.flatnonzero(indeg == 0)
        depth = 0
        while frontier.size:
            level[frontier] = depth
            cand, hits = np.unique(_gather(starts, nbr, frontier), return_counts=True)
            indeg[cand] -= hits
            frontier = cand[indeg[cand] == 0]
            depth += 1
        return level

    def out_neighbors(self, nodes, chunks=None) -> np.ndarray:
        """Every out-neighbor of the nodes (with repeats), over the first
        ``chunks`` edge batches."""
        nodes = np.asarray(nodes, dtype=np.int64)
        out = _gather(*self._base(), nodes)
        declared = self._declared(chunks)
        if declared is not None:
            has, xsrc, xdst = declared
            nodes = nodes[has[nodes]]
            if nodes.size:
                lo = np.searchsorted(xsrc, nodes, side="left")
                hi = np.searchsorted(xsrc, nodes, side="right")
                out = np.concatenate([out, xdst[_ranges(lo, hi - lo)]])
        return out

    def closure(self, seeds, chunks=None) -> np.ndarray:
        """bool[n]: the seeds and everything that transitively depends on
        them, over the first ``chunks`` edge batches."""
        seen = np.zeros(self.n, dtype=bool)
        frontier = np.unique(np.asarray(seeds, dtype=np.int64))
        seen[frontier] = True
        while frontier.size:
            cand = self.out_neighbors(frontier, chunks)
            cand = np.unique(cand[~seen[cand]])
            seen[cand] = True
            frontier = cand
        return seen

    def closure_ids(self, seeds, max_depth=None, chunks=None) -> set:
        """The same closure as a set of ids, for closures of tens of nodes
        (no O(n) array per call). ``max_depth`` cuts the cascade after that
        many hops: the controls use it, the reference never does."""
        seen = {int(s) for s in seeds}
        frontier = list(seen)
        depth = 0
        while frontier and (max_depth is None or depth < max_depth):
            nxt = []
            for d in self.out_neighbors(frontier, chunks).tolist():
                if d not in seen:
                    seen.add(d)
                    nxt.append(d)
            frontier = nxt
            depth += 1
        return seen


def _ranges(first: np.ndarray, count: np.ndarray) -> np.ndarray:
    """Indices ``first[i] .. first[i] + count[i] - 1`` for every i, in order."""
    total = int(count.sum())
    return np.repeat(first - np.cumsum(count) + count, count) + np.arange(total)


def _gather(starts: np.ndarray, nbr: np.ndarray, nodes: np.ndarray) -> np.ndarray:
    """The CSR rows of ``nodes``, concatenated."""
    s0 = starts[nodes]
    return nbr[_ranges(s0, starts[nodes + 1] - s0)]
