"""Plain float64 references for the ridge deployments.

``root``: the centralized ridge root z* of
    (1/Q) sum_i a_i (a_i^T z - y_i) + lam z = 0,
solved through the Q x Q dual system z = A^T (A A^T + lam Q I)^{-1} y.

``dsba_trajectory``: Algorithm 1 of Shen et al. (ICML 2018), eqs. 27-31 with
the exact l2 carry-over (B^lam = B + lam I, rho = 1 / (1 + alpha lam)), for
ridge, on every node at once, from z^0 = 0 over a given index stream. It is
written from the paper's equations in NumPy and imports nothing of the
program; it is what the program's iterates are compared with.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def design(idx: np.ndarray, val: np.ndarray, d: int) -> sp.csr_matrix:
    """The (Q, d) float64 design matrix of padded-CSR rows."""
    n, q, k = idx.shape
    rows = np.repeat(np.arange(n * q), k)
    return sp.csr_matrix(
        (val.reshape(-1).astype(np.float64), (rows, idx.reshape(-1))),
        shape=(n * q, d),
    )


def root(idx: np.ndarray, val: np.ndarray, y: np.ndarray, d: int,
         lam: float) -> np.ndarray:
    """The ridge root z* (d,), in float64."""
    a = design(idx, val, d)
    q_tot = a.shape[0]
    gram = (a @ a.T).toarray()
    gram[np.diag_indices(q_tot)] += lam * q_tot
    dual = np.linalg.solve(gram, y.reshape(-1).astype(np.float64))
    return np.asarray(a.T @ dual).reshape(-1)


def rel_dist2(z: np.ndarray, z_star: np.ndarray) -> float:
    """mean_n ||z_n - z*||^2 / ||z*||^2 of iterates z (N, d), in float64."""
    z = np.asarray(z, np.float64)
    return float(np.mean(np.sum((z - z_star) ** 2, -1)) / np.dot(z_star, z_star))


def dsba_trajectory(idx, val, y, d: int, w: np.ndarray, lam: float,
                    alpha: float, indices: np.ndarray,
                    dtype=np.float64) -> np.ndarray:
    """Final iterates (N, d) of len(indices) DSBA iterations from z^0 = 0.

    Ridge: B_i(z) = (a_i^T z - y_i) a_i, so the SAGA table holds the scalar
    g_i = a_i^T z - y_i of each row at its last visit, and the resolvent is
    closed-form. ``dtype`` is the arithmetic of every array.
    """
    n, q, k = idx.shape
    f = np.dtype(dtype).type
    val = np.asarray(val, dtype)
    y = np.asarray(y, dtype)
    w = np.asarray(w, dtype)
    wt = (w + np.eye(n, dtype=dtype)) / f(2)
    alpha, lam = f(alpha), f(lam)
    rho = f(1) / (f(1) + alpha * lam)
    a_eff = rho * alpha
    scale = f(q - 1) / f(q)
    nodes = np.arange(n)[:, None]

    z = np.zeros((n, d), dtype)
    z_prev = z.copy()
    table = -y.copy()  # g at z^0 = 0
    phibar = np.zeros((n, d), dtype)
    np.add.at(phibar, (np.repeat(np.arange(n), q * k), idx.reshape(-1)),
              (table[:, :, None] * val).reshape(-1) / f(q))
    dg_prev = np.zeros(n, dtype)
    didx_prev = np.zeros((n, k), np.int64)
    dval_prev = np.zeros((n, k), dtype)
    rows = np.arange(n)
    for t, i_t in enumerate(np.asarray(indices)):
        ix, vx, ys = idx[rows, i_t], val[rows, i_t], y[rows, i_t]
        cs = table[rows, i_t]
        if t == 0:  # eq. 31
            psi = w @ z - alpha * phibar
        else:  # eq. 29 with the l2 carry-over
            psi = wt @ (f(2) * z - z_prev) + alpha * lam * z
            psi[nodes, didx_prev] += (alpha * scale * dg_prev)[:, None] * dval_prev
        psi[nodes, ix] += (alpha * cs)[:, None] * vx
        s = np.sum(vx * psi[nodes, ix], axis=1)
        xsq = np.sum(vx * vx, axis=1)
        u = (rho * s + a_eff * ys * xsq) / (f(1) + a_eff * xsq)  # eq. 30
        g = u - ys
        z_prev = z
        z = rho * psi
        z[nodes, ix] -= (a_eff * g)[:, None] * vx
        dg = g - cs
        table[rows, i_t] = g
        phibar[nodes, ix] += (dg / f(q))[:, None] * vx
        dg_prev, didx_prev, dval_prev = dg, ix, vx
    return z
