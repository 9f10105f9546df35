"""Input generators of the benchmark, kept here so that a change to the
program cannot move the yardstick.

``sparse_rows``/``regression`` and ``index_stream`` are copies of
``repro.data.synthetic.make_regression`` and ``repro.core.dsba.draw_indices``;
``erdos_renyi_edges``/``ring_edges``/``laplacian_mixing`` follow
``repro.core.mixing``. Everything is NumPy and deterministic in its seed.
"""
from __future__ import annotations

import numpy as np


def sparse_rows(rng, n: int, d: int, k: int, dtype):
    """n rows with exactly k distinct nonzeros each, normalized to ||a|| = 1."""
    idx = np.empty((n, k), dtype=np.int32)
    for i in range(n):
        idx[i] = rng.choice(d, size=k, replace=False)
    val = rng.standard_normal((n, k)).astype(dtype)
    val /= np.linalg.norm(val, axis=1, keepdims=True)
    return idx, val


def regression(n_nodes: int, q: int, d: int, k: int, noise: float,
               seed: int, dtype=np.float32):
    """Sparse ridge data y = a^T w* + noise, split over nodes.

    Returns (idx (N, q, k) int32, val (N, q, k), y (N, q)).
    """
    rng = np.random.default_rng(seed)
    n = n_nodes * q
    idx, val = sparse_rows(rng, n, d, k, dtype)
    w_star = rng.standard_normal(d).astype(dtype)
    u = np.einsum("nk,nk->n", val, w_star[idx])
    y = u + noise * rng.standard_normal(n).astype(dtype)
    perm = rng.permutation(n)[: q * n_nodes]
    return (idx[perm].reshape(n_nodes, q, k), val[perm].reshape(n_nodes, q, k),
            y[perm].reshape(n_nodes, q))


def index_stream(steps: int, n_nodes: int, q: int, seed: int) -> np.ndarray:
    """(steps, N) uniform sample indices, one row per iteration."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, q, size=(steps, n_nodes)).astype(np.int32)


def _connected(n: int, edges) -> bool:
    adj = {i: set() for i in range(n)}
    for i, j in edges:
        adj[i].add(j)
        adj[j].add(i)
    seen, todo = {0}, [0]
    while todo:
        for m in adj[todo.pop()] - seen:
            seen.add(m)
            todo.append(m)
    return len(seen) == n


def erdos_renyi_edges(n: int, p: float, seed: int) -> tuple:
    """Random G(n, p) edges (i < j), resampled until connected."""
    rng = np.random.default_rng(seed)
    for _ in range(1000):
        edges = tuple((i, j) for i in range(n) for j in range(i + 1, n)
                      if rng.random() < p)
        if _connected(n, edges):
            return edges
    raise RuntimeError("failed to sample a connected graph")


def ring_edges(n: int) -> tuple:
    """Cycle over n >= 3 nodes."""
    return tuple(sorted((min(i, (i + 1) % n), max(i, (i + 1) % n))
                        for i in range(n)))


def graph_edges(spec: dict, n: int) -> tuple:
    """Edges of the topology a configuration names."""
    if spec["kind"] == "erdos_renyi":
        return erdos_renyi_edges(n, spec["p"], spec["seed"])
    if spec["kind"] == "ring":
        return ring_edges(n)
    raise ValueError(f"unknown topology {spec['kind']!r}")


def laplacian_mixing(n: int, edges) -> np.ndarray:
    """The paper's Section 7 mixing W = I - L / lambda_max(L)."""
    a = np.zeros((n, n))
    for i, j in edges:
        a[i, j] = a[j, i] = 1.0
    lap = np.diag(a.sum(1)) - a
    return np.eye(n) - lap / float(np.linalg.eigvalsh(lap).max())


def stratified(rng, n: int, ppf) -> np.ndarray:
    """n draws of a distribution as its quantiles at (i + 1/2) / n, shuffled.

    Every seed gets the same multiset of values in another order, so the
    seed changes the order of the work and not its amount.
    """
    vals = ppf((np.arange(n) + 0.5) / n)
    return vals[rng.permutation(n)]
