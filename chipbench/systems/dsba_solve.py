"""DSBA solves through the solver API, ``repro.core.solvers.solve``.

The window runs back-to-back solves of one deployment (a configuration
file) over one comm backend (the traffic file's ``comm``), each from
z = 0 for the configuration's ``steps`` iterations on an index stream of
its own drawn from the run's seed. The dataset, topology and mixing are
the deployment's and do not change with the seed.

End to end: ``solve_s`` = (window seconds / iterations run in the window)
x (mean over the window's solves of the iterations each needed to first
reach the deployment's relative distance ``target_rel_dist2``, read from
``SolveResult.dist2`` at its record points against the benchmark's own
root).

Correctness, after the window: every solve's final iterates must lie
within the target distance of the float64 root (the deployment states
that limit), and one solve drawn from the seed is replayed by the float64
reference (``references/ridge.py``) over its index stream; the relative
Frobenius gap of the final iterates is held to the cell's limit.
"""
from __future__ import annotations

import time

import ml_dtypes  # numpy's bfloat16
import numpy as np

from chipbench import inputs
from chipbench.references import ridge

WARM, PICK = 2**32 - 1, 2**32 - 2  # stream numbers of the warm-up and check


def stream_seed(seed: int, j: int) -> np.random.SeedSequence:
    """Seed of the j-th solve's index stream in a run of ``seed``."""
    return np.random.SeedSequence([seed % 2**64, j])


class System:
    """One deployment under back-to-back solves on one comm backend."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices, spans,
                 *, dtype: str | None = None):
        import jax

        # the solver entry points run with 64-bit mode on; the data keeps
        # the deployment's dtype (float32: what the compiled kernel takes)
        jax.config.update("jax_enable_x64", True)
        from repro.core.mixing import Graph
        from repro.core.solvers import make_problem
        from repro.data.synthetic import SparseDataset

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.spans = spans
        n, q, d, k = cfg["n_nodes"], cfg["q"], cfg["d"], cfg["k"]
        self.dtype_name = dtype or cfg["dtype"]
        np_dtype = np.dtype(getattr(ml_dtypes, self.dtype_name, self.dtype_name))
        idx, val, y = inputs.regression(n, q, d, k, cfg["noise"],
                                        cfg["data_seed"], np.float32)
        self.idx, self.val, self.y = idx, val, y
        self.edges = inputs.graph_edges(cfg["graph"], n)
        self.w = inputs.laplacian_mixing(n, self.edges)
        self.itemsize = np_dtype.itemsize
        data = SparseDataset(idx, val.astype(np_dtype), y.astype(np_dtype), d)
        self.problem = make_problem(cfg["task"], data, Graph(n, self.edges),
                                    w=self.w, lam=cfg["lam"])
        t0 = time.perf_counter()
        self.z_star = ridge.root(idx, val, y, d, cfg["lam"])
        self.reference_setup_s = time.perf_counter() - t0
        self.problem.z_star = self.z_star
        self.zz = float(self.z_star @ self.z_star)
        self.solves: list[dict] = []
        self.window_s = 0.0

    def _stream(self, j: int) -> np.ndarray:
        c = self.cfg
        return inputs.index_stream(c["steps"], c["n_nodes"], c["q"],
                                   stream_seed(self.seed, j))

    def _solve(self, indices: np.ndarray):
        from repro.core import solvers

        c = self.cfg
        return solvers.solve(
            self.problem, "dsba", comm=self.traffic["comm"], steps=c["steps"],
            record_every=c["record_every"], indices=indices, alpha=c["alpha"],
        )

    def warm(self) -> None:
        """One whole solve: compiles the runner and every read-out."""
        self._solve(self._stream(WARM))

    def run_window(self, seconds: float) -> None:
        """Back-to-back solves until ``seconds`` have passed."""
        t0 = time.perf_counter()
        j = 0
        while True:
            indices = self._stream(j)
            with self.spans("solve"):
                r = self._solve(indices)
            rel = np.asarray(r.dist2) / self.zz
            hit = np.nonzero(rel <= self.cfg["target_rel_dist2"])[0]
            self.solves.append({
                "j": j,
                "iters": int(r.iters[-1]),
                "to_target": int(r.iters[hit[0]]) if len(hit) else None,
                "z": np.asarray(r.z),
            })
            del r
            j += 1
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_s = time.perf_counter() - t0

    def finish(self) -> None:
        """Nothing is in flight once the window's last solve returns."""

    def counters(self) -> dict:
        reached = [s["to_target"] for s in self.solves if s["to_target"]]
        c = self.cfg
        return {
            "window_s": self.window_s,
            "solves": len(self.solves),
            "iterations": sum(s["iters"] for s in self.solves),
            "iters_to_target": float(np.mean(reached)) if reached else None,
            "n_nodes": c["n_nodes"], "d": c["d"], "k": c["k"],
            "itemsize": self.itemsize,
        }

    def end_to_end(self) -> dict:
        c = self.counters()
        to_target = c["iters_to_target"] or float(self.cfg["steps"])
        return {"solve_s": c["window_s"] / c["iterations"] * to_target}

    def release(self) -> None:
        """Drop the program's state (its runners and device arrays)."""
        from repro.core import runner_cache

        for cache in (runner_cache.DENSE, runner_cache.SPARSE):
            cache.clear()
        self.problem = None

    def reference_control(self, limits: dict, dtype_name: str) -> dict:
        """The control: the reference in ``dtype_name`` in the program's
        place, on the stream of a window's first solve, held to the same
        checks as the program's answers."""
        c = self.cfg
        stream = self._stream(0)
        args = (self.idx, self.val, self.y, c["d"], self.w, c["lam"],
                c["alpha"], stream)
        z = ridge.dsba_trajectory(*args, dtype=getattr(ml_dtypes, dtype_name))
        z = np.asarray(z, np.float64)
        z_ref = ridge.dsba_trajectory(*args)
        gap = float(np.linalg.norm(z - z_ref) / np.linalg.norm(z_ref))
        return {
            "rel_dist2_worst": {"value": ridge.rel_dist2(z, self.z_star),
                                "limit": float(c["target_rel_dist2"])},
            "replay_rel_gap": {"value": gap,
                               "limit": float(limits["replay_rel_gap"])},
        }

    def check(self, limits: dict) -> tuple[dict, int, int]:
        """Every solve's distance to the root, and one replayed solve."""
        c = self.cfg
        worst = max(ridge.rel_dist2(s["z"], self.z_star) for s in self.solves)
        rng = np.random.default_rng(stream_seed(self.seed, PICK))
        pick = self.solves[int(rng.integers(len(self.solves)))]
        z_ref = ridge.dsba_trajectory(
            self.idx, self.val, self.y, c["d"], self.w, c["lam"], c["alpha"],
            self._stream(pick["j"]),
        )
        gap = float(np.linalg.norm(pick["z"] - z_ref) / np.linalg.norm(z_ref))
        failed = sum(1 for s in self.solves if s["to_target"] is None)
        checks = {
            "rel_dist2_worst": {"value": worst,
                                "limit": float(c["target_rel_dist2"])},
            "replay_rel_gap": {"value": gap,
                               "limit": float(limits["replay_rel_gap"])},
        }
        return checks, len(self.solves), failed
