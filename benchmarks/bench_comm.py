"""Paper Table 1 (communication column) + Section 5.1 cost model validation.

Runs the ACTUAL DSBA-s relay via ``solve(..., comm="sparse")`` and checks
the ``SolveResult.doubles_received`` accounting against the closed-form
O(N rho d) model and against the dense O(Delta(G) d) baselines; prints the
crossover ratios the paper claims.

Also sweeps ring topologies at N in {8, 16, 32} — the regime where DSA's
O(N) relay delays and Lan et al.'s communication-complexity analysis bite,
and where the pre-vectorization per-observer Python loop was intractable.

``sharded_scaling_sweep`` is the ``comm="sharded"`` half (bench-group
``comm-sharded``): for N in {8, 16, 32, 64} simulated nodes it times the
single-device dense matmul backend against the node-per-device shard_map
backend and reports the HLO-measured collective bytes — the matmul-vs-
ppermute crossover table. It is a CPU simulation: each N runs in a CHILD
process with N forced host devices, because
``--xla_force_host_platform_device_count`` must be set before jax
initializes (``--sharded-child`` below). It refuses to start unless
``JAX_PLATFORMS=cpu``: on a host with a chip, the parent would hold the
chip and every child that reached for it would fail or hang. Its times are
CPU times, never device times.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np

from repro.core import mixing
from repro.core.dsba import draw_indices
from repro.core.solvers import make_problem, solve
from repro.core.sparse_comm import (
    dense_doubles_per_iter, sparse_doubles_per_iter,
)
from repro.data.synthetic import DATASET_PRESETS, make_regression


def measure(n=8, q=10, d=800, k=12, steps=25, seed=0):
    data = make_regression(n, q, d, k=k, seed=seed)
    graph = mixing.erdos_renyi_graph(n, 0.4, seed=2)
    problem = make_problem("ridge", data, graph, lam=1e-3)
    idx = draw_indices(steps, n, q, seed=3)
    res = solve(problem, "dsba", comm="sparse", steps=steps, record_every=1,
                indices=idx, alpha=0.3, comm_options={"verify": True})
    steady = np.diff(res.doubles_received, axis=0)[-8:]
    return data, graph, steady, res


def warm_sweep_demo(alphas=(0.3, 0.45, 0.6), n=8, q=10, d=800, k=12,
                    steps=25, seed=0):
    """Per-call relay latency across a step-size sweep on one problem.

    The first call compiles the jitted relay scan; later alphas are traced
    arguments into the cached executable (core.runner_cache), so the sweep
    runs at solver speed. Returns the per-call wall times in sweep order.
    """
    data = make_regression(n, q, d, k=k, seed=seed)
    graph = mixing.erdos_renyi_graph(n, 0.4, seed=2)
    problem = make_problem("ridge", data, graph, lam=1e-3)
    idx = draw_indices(steps, n, q, seed=3)
    times = []
    for a in alphas:
        t0 = time.perf_counter()
        solve(problem, "dsba", comm="sparse", steps=steps,
              record_every=steps, indices=idx, alpha=a)
        times.append(time.perf_counter() - t0)
    return times


def topology_sweep(sizes=(8, 16, 32), q=10, d=256, k=8, seed=0):
    """Ring-graph sweep: steady-state doubles must match the closed form.

    Rings maximize the diameter (N/2 relay hops), so this exercises the
    deepest reconstruction recursion the protocol supports. Runs long enough
    past warm-up (2*diam + 40 iterations) that steady state is unambiguous.
    """
    print(f"\nring-topology sweep (q={q}, d={d}, k={k}):")
    print(f"{'N':>4} {'diam':>5} {'steps':>6} {'doubles/node/iter':>18} "
          f"{'model':>6} {'dense':>8} {'wall':>7} {'ms/iter':>8}")
    for n in sizes:
        graph = mixing.ring_graph(n)
        data = make_regression(n, q, d, k=k, seed=seed)
        problem = make_problem("ridge", data, graph, lam=1e-3)
        steps = 2 * graph.diameter + 40
        extra = 600
        idx = draw_indices(steps + extra, n, q, seed=3)
        t0 = time.perf_counter()
        res = solve(problem, "dsba", comm="sparse", steps=steps,
                    record_every=1, indices=idx, alpha=0.3)
        wall = time.perf_counter() - t0
        # wall above is compile-dominated (one jitted scan per call); the
        # marginal cost of `extra` more iterations isolates the engine speed
        t0 = time.perf_counter()
        solve(problem, "dsba", comm="sparse", steps=steps + extra,
              record_every=steps + extra, indices=idx, alpha=0.3)
        ms_iter = 1e3 * (time.perf_counter() - t0 - wall) / extra
        steady = np.diff(res.doubles_received, axis=0)[graph.diameter + 2 :]
        measured = sorted(set(steady.reshape(-1).tolist()))
        model = sparse_doubles_per_iter(n, k, 0)
        assert measured == [model], (n, measured, model)
        dense = int(dense_doubles_per_iter(graph, d).max())
        print(f"{n:>4} {graph.diameter:>5} {steps:>6} {str(measured):>18} "
              f"{model:>6} {dense:>8} {wall:>6.2f}s "
              f"{'<noise' if ms_iter <= 0 else f'{ms_iter:.2f}':>8}")
    print("(wall includes the one-time XLA compile of the jitted scan; "
          "ms/iter is the marginal cost of 600 extra iterations, '<noise' "
          "when it is below compile-time variance)")


def _sharded_child(n: int, q=10, d=64, k=8, steps=60, seed=0) -> None:
    """Measure one N inside a forced-device process; print a JSON line.

    Warm per-iteration wall time for both backends (second solve() call —
    the compiled runner is cached), plus the sharded run's HLO-measured
    collective traffic and the modeled dense exchange for the same graph.
    """
    graph = mixing.ring_graph(n)
    data = make_regression(n, q, d, k=k, seed=seed)
    problem = make_problem("ridge", data, graph, lam=1e-3)
    idx = draw_indices(steps, n, q, seed=3)

    def one(comm, alpha):
        return solve(problem, "dsba", comm=comm, steps=steps,
                     record_every=steps, indices=idx, alpha=alpha)

    out = {"n": n, "d": d, "steps": steps}
    for comm in ("dense", "sharded"):
        one(comm, 0.3)  # compile
        t0 = time.perf_counter()
        res = one(comm, 0.31)
        out[f"{comm}_us_iter"] = (time.perf_counter() - t0) / steps * 1e6
    cc = res.extras["collectives"]
    out["bytes_per_iter"] = cc["bytes_per_iter"]
    out["permutes_per_iter"] = cc["count_per_iter"]
    out["measured_bytes_total"] = float(
        np.asarray(res.measured_collective_bytes)[-1]
    )
    out["modeled_dense_doubles_iter"] = int(
        dense_doubles_per_iter(graph, d).max()
    )
    print("SHARDED_CHILD " + json.dumps(out))


def sharded_scaling_sweep(sizes=(8, 16, 32, 64)) -> list[dict]:
    """Spawn one forced-device child per N; return the measured records.

    Decided from the environment alone, before any child starts: the sweep
    simulates devices on the CPU and runs only with ``JAX_PLATFORMS=cpu``.
    """
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            "the sharded scaling sweep simulates N devices on the CPU in "
            "child processes; run it with JAX_PLATFORMS=cpu (on a chip "
            "host the parent holds the chip and the children cannot reach "
            "it)"
        )
    records = []
    for n in sizes:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}"
        ).strip()
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_comm",
             "--sharded-child", str(n)],
            env=env, capture_output=True, text=True, timeout=1800,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"sharded child N={n} failed:\n{proc.stdout}\n{proc.stderr}"
            )
        line = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("SHARDED_CHILD ")][-1]
        records.append(json.loads(line.split(" ", 1)[1]))
    return records


def print_sharded_table(records) -> None:
    """The bench-group ``comm-sharded`` headline: matmul vs ppermute."""
    print("\nsharded-vs-dense scaling (ring, warm us/iter, one node per "
          "forced host device):")
    print(f"{'N':>4} {'dense':>9} {'sharded':>9} {'ratio':>7} "
          f"{'KB/iter':>8} {'permutes':>9}")
    for r in records:
        ratio = r["sharded_us_iter"] / r["dense_us_iter"]
        print(f"{r['n']:>4} {r['dense_us_iter']:>8.0f} "
              f"{r['sharded_us_iter']:>8.0f} {ratio:>6.1f}x "
              f"{r['bytes_per_iter'] / 1024:>7.2f} "
              f"{r['permutes_per_iter']:>9.0f}")
    print("(dense = one-device matmul mixing; sharded = per-edge "
          "collective-permute on the node mesh. KB/iter is HLO-measured "
          "per-device collective traffic, not a model.)")


def main():
    if "--sharded-child" in sys.argv:
        _sharded_child(int(sys.argv[sys.argv.index("--sharded-child") + 1]))
        return
    data, graph, steady, res = measure()
    model = sparse_doubles_per_iter(data.n_nodes, data.k, 0)
    dense = dense_doubles_per_iter(graph, data.d)
    print("measured steady-state DOUBLEs/node/iter:",
          sorted(set(steady.reshape(-1).tolist())))
    print("closed-form (N-1)*k                     :", model)
    assert (steady == model).all()
    print("dense per-iter (deg*d) min..max          :",
          int(dense.min()), "..", int(dense.max()))
    print(f"sparse/dense ratio: {model / dense.max():.4f} "
          f"(= O(N rho d) / O(Delta d))")
    print("protocol reconstruction max error: "
          f"{res.extras['recon_max_err']:.2e}")

    times = warm_sweep_demo()
    warm = min(times[1:])
    print(f"\nrelay sweep latency: cold {times[0]:.2f}s (compiles the scan), "
          f"then {warm * 1e3:.0f}ms/alpha warm "
          f"({times[0] / warm:.0f}x — compiled-runner cache)")

    print("\nprojected per-iteration DOUBLEs at paper-scale datasets "
          "(N=10, ER(0.4) E[deg]~3.6):")
    print(f"{'dataset':>10} {'d':>9} {'k':>5} {'DSBA-s':>10} "
          f"{'dense':>12} {'ratio':>8}")
    for name in ("news20", "rcv1", "sector"):
        p = DATASET_PRESETS[name]
        s = sparse_doubles_per_iter(10, p["k"], 0)
        dd = 4 * p["d"]  # deg ~ 4
        print(f"{name:>10} {p['d']:>9} {p['k']:>5} {s:>10,} {dd:>12,} "
              f"{dd / s:>7.0f}x")

    topology_sweep()
    print_sharded_table(sharded_scaling_sweep())


if __name__ == "__main__":
    main()
