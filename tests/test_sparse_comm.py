"""DSBA-s (Section 5.1): protocol == dense algorithm, costs == O(N rho d).

All runs go through `core.solvers.solve` — the sparse relay is just the
`comm="sparse"` backend of the registry (backend options pass through
`comm_options`). The fast (default) tests share one compiled configuration
via a module fixture: a ridge/DSBA run on the paper's Erdős–Rényi topology,
executed by the dense backend, the vectorized relay engine (verify=True,
Pallas-routed delta path), and the legacy reference loop. The `slow`-marked
sweeps extend the same claims to every task x method x graph combination;
run them with `pytest -m ""`.
"""
import re

import numpy as np
import pytest

from repro.core import mixing
from repro.core.dsba import draw_indices
from repro.core.solvers import make_problem, solve, solve_many
from repro.core.sparse_comm import (
    dense_doubles_per_iter,
    sparse_doubles_per_iter,
)
from repro.data.synthetic import make_classification, make_regression

STEPS = 40


def _setup(task, n_nodes=6, q=8, d=24, k=4, seed=0, lam=None):
    if task == "ridge":
        data = make_regression(n_nodes, q, d, k=k, seed=seed)
    elif task == "logistic":
        data = make_classification(n_nodes, q, d, k=k, seed=seed)
    else:
        data = make_classification(n_nodes, q, d, k=k, positive_ratio=0.3,
                                   seed=seed)
    graph = mixing.erdos_renyi_graph(n_nodes, 0.4, seed=2)
    return make_problem(task, data, graph, lam=lam)


GRAPHS = {
    "ring": mixing.ring_graph,
    "erdos_renyi": lambda n: mixing.erdos_renyi_graph(n, 0.4, seed=2),
    # distance levels of widely different sizes, so each level's block of
    # the reconstruction ring has its own length: on 7 nodes a path holds
    # 12, 10, 8, 6, 4 and 2 (observer, source) pairs at distances 1..6, a
    # star 12 at distance 1 and 30 at distance 2
    "path": lambda n: mixing.Graph(n, tuple((i, i + 1) for i in range(n - 1))),
    "star": lambda n: mixing.Graph(n, tuple((0, i) for i in range(1, n))),
}


def _multi_hop_problem(task, gname, n_nodes=7):
    base = _setup(task, n_nodes=n_nodes, lam=1e-3)
    return make_problem(task, base.data, GRAPHS[gname](n_nodes), lam=1e-3)


def _assert_engines_agree(problem, method):
    """Vectorized (verified) == reference loop: z_trace, doubles, ints and
    recon error, over 40 steps."""
    steps = 40
    indices = draw_indices(steps, problem.data.n_nodes, problem.data.q,
                           seed=3)
    kw = dict(steps=steps, record_every=1, indices=indices, alpha=0.3)
    ref = solve(problem, method, comm="sparse",
                comm_options={"engine": "reference"}, **kw)
    vec = solve(problem, method, comm="sparse",
                comm_options={"verify": True}, **kw)
    np.testing.assert_allclose(
        vec.extras["z_trace"], ref.extras["z_trace"], rtol=0, atol=1e-12
    )
    assert (vec.doubles_received == ref.doubles_received).all()
    assert (vec.ints_received == ref.ints_received).all()
    assert vec.extras["recon_max_err"] < 1e-9
    assert ref.extras["recon_max_err"] < 1e-9


@pytest.fixture(scope="module")
def shared():
    """Dense + vectorized + reference runs of one shared configuration."""
    problem = _setup("ridge")
    indices = draw_indices(STEPS, problem.data.n_nodes, problem.data.q, seed=7)
    kw = dict(steps=STEPS, record_every=1, indices=indices, alpha=0.3)
    dense = solve(problem, "dsba", comm="dense", **kw)
    vec = solve(problem, "dsba", comm="sparse",
                comm_options={"verify": True}, **kw)
    ref = solve(problem, "dsba", comm="sparse",
                comm_options={"engine": "reference"}, **kw)
    return problem, dense, vec, ref


def test_sparse_comm_trajectory_equals_dense(shared):
    """The relay protocol must reproduce the dense trajectory exactly."""
    _, dense, vec, _ = shared
    np.testing.assert_allclose(vec.z, dense.z, rtol=0, atol=1e-12)
    assert vec.extras["recon_max_err"] < 1e-9, vec.extras["recon_max_err"]


def test_vectorized_engine_matches_reference(shared):
    """Ring-buffer engine == legacy loop: trajectory, costs, recon error."""
    _, _, vec, ref = shared
    np.testing.assert_allclose(
        vec.extras["z_trace"], ref.extras["z_trace"], rtol=0, atol=1e-12
    )
    assert (vec.doubles_received == ref.doubles_received).all()
    assert (vec.ints_received == ref.ints_received).all()
    assert ref.extras["recon_max_err"] < 1e-9
    assert vec.extras["recon_max_err"] < 1e-9


def test_solve_result_schema_uniform_across_backends(shared):
    """One schema: both backends fill iters/metrics/comm the same way."""
    _, dense, vec, _ = shared
    assert (dense.iters == vec.iters).all()
    n = dense.doubles_received.shape[1]
    assert vec.doubles_received.shape == dense.doubles_received.shape
    assert (dense.ints_received == 0).all()  # dense blocks carry no indices
    # dense accounting is the closed-form deg*D model at every record point
    problem = shared[0]
    per_node = dense_doubles_per_iter(problem.graph, problem.dim)
    assert (dense.doubles_received
            == dense.iters[:, None] * per_node[None, :]).all()
    assert dense.wall_time > 0 and vec.wall_time > 0
    assert dense.z.shape == vec.z.shape == (n, shared[0].dim)


def test_sparse_comm_cost_is_o_n_rho_d(shared):
    """Steady-state per-iteration DOUBLEs: (N-1)*k  vs  dense deg*d."""
    problem, _, vec, _ = shared
    data, graph = problem.data, problem.graph
    per_iter = np.diff(vec.doubles_received, axis=0)[-10:]  # steady state
    expect = sparse_doubles_per_iter(data.n_nodes, data.k, 0)
    assert (per_iter == expect).all(), (per_iter, expect)

    # the headline claim at paper-like dimension (cost model is d-free on
    # the sparse side; the dense side scales with d): rho*d << d
    d_paper = 600
    dense_cost = dense_doubles_per_iter(graph, d_paper)
    assert per_iter.max() * 10 < dense_cost.min()


def test_sparse_comm_warmup_cost_is_one_time(shared):
    problem, _, vec, _ = shared
    data, graph = problem.data, problem.graph
    E = graph.diameter
    total_warmup = vec.doubles_received[E + 1].max()
    # warm-up includes the one-time dense z^1 flood: (N-1)*D doubles
    assert total_warmup >= (data.n_nodes - 1) * data.d
    # after warm-up, growth is exactly the sparse rate
    growth = np.diff(vec.doubles_received, axis=0)[E + 2 :]
    assert (growth == sparse_doubles_per_iter(data.n_nodes, data.k, 0)).all()


def test_sparse_comm_requires_a_sparse_backend(shared):
    """comm="sparse" on a dense-only method is a clear error, not a fallback."""
    problem = shared[0]
    with pytest.raises(ValueError, match="sparse-communication backend"):
        solve(problem, "extra", comm="sparse", steps=4)


def test_verify_mode_catches_protocol_violations(shared, monkeypatch):
    """A corrupted relay schedule must trip the availability guard."""
    import repro.core.sparse_comm as sc

    problem = _setup("ridge", lam=1e-3)
    indices = draw_indices(8, problem.data.n_nodes, problem.data.q, seed=7)

    real_tables = sc._protocol_tables

    def shallow_tables(g, wt):
        # depth=2 makes the write slot collide with the s-2 read slot, so
        # reconstructions consume clobbered history — exactly the class of
        # bookkeeping bug verify= exists to catch.
        import dataclasses as dc

        return dc.replace(real_tables(g, wt), depth=2)

    monkeypatch.setattr(sc, "_protocol_tables", shallow_tables)
    with pytest.raises(sc.ProtocolViolation):
        solve(problem, "dsba", comm="sparse", steps=8, indices=indices,
              alpha=0.3, comm_options={"verify": True, "use_pallas": "off"})


@pytest.mark.parametrize("gname", ["path", "star"])
def test_vectorized_matches_reference_on_uneven_levels(gname):
    """Engine parity where every distance level is a ring block of its own
    length (the slow matrix below covers ring and Erdős–Rényi graphs)."""
    _assert_engines_agree(_multi_hop_problem("ridge", gname), "dsba")


def test_batched_relay_verified_on_uneven_levels():
    """The vmapped relay sweep (run_sparse_many) over the per-level ring
    blocks, verified: each run bit-equal to its sequential solve."""
    problem = _multi_hop_problem("ridge", "path")
    grid = [{"alpha": 0.3}, {"alpha": 0.6}]
    seeds = [3, 4]
    kw = dict(steps=24, record_every=8, comm_options={"verify": True})
    many = solve_many(problem, "dsba", comm="sparse", grid=grid,
                      seeds=seeds, **kw)
    assert many.extras["batched"] is True
    for b, hp in enumerate(grid):
        seq = solve(problem, "dsba", comm="sparse", seed=seeds[b], **kw,
                    **hp)
        run = many.extras["per_run_extras"][b]
        assert np.array_equal(run["z_trace"], seq.extras["z_trace"])
        assert np.array_equal(many.doubles_received[b], seq.doubles_received)
        assert run["recon_max_err"] < 1e-9


def test_resume_refuses_a_ring_of_another_layout(tmp_path):
    """A relay checkpoint whose ring leaf has the (depth, N, N, D) shape of
    the earlier per-observer layout is refused up front, naming the leaf
    and the shape the scan carries."""
    import json

    import repro.core.sparse_comm as sc
    from repro.ckpt import CheckpointSpec

    problem = _setup("ridge")
    kw = dict(record_every=5, seed=3, alpha=0.3,
              comm_options={"use_pallas": "off"})
    solve(problem, "dsba", comm="sparse", steps=10,
          checkpoint=CheckpointSpec(tmp_path, every=10), **kw)
    n, dim = problem.data.n_nodes, problem.dim
    tb = sc._protocol_tables(problem.graph, mixing.w_tilde(problem.w))
    ring = "['carry']/[2]"
    expected = (tb.depth * tb.n_rows, 1, 128)  # D = 24: one 128-lane row
    saved = tmp_path / "step_10"
    leaves = json.loads((saved / "manifest.json").read_text())["leaves"]
    entry = next(e for e in leaves if e["path"] == ring)
    assert tuple(entry["shape"]) == expected
    np.save(saved / entry["file"], np.zeros((tb.depth, n, n, dim)))
    with pytest.raises(ValueError, match=re.escape(ring) + ".*" + re.escape(
            str(expected))):
        solve(problem, "dsba", comm="sparse", steps=20, resume=str(tmp_path),
              **kw)


def test_fast_path_reports_nan_recon_err(shared):
    """Without verify= the engine skips truth checking (allocation-lean)."""
    problem = _setup("ridge")
    indices = draw_indices(4, problem.data.n_nodes, problem.data.q, seed=7)
    res = solve(problem, "dsba", comm="sparse", steps=4, indices=indices,
                alpha=0.3, comm_options={"use_pallas": "off"})
    assert np.isnan(res.extras["recon_max_err"])


# ---------------------------------------------------------------------------
# Exhaustive sweeps (slow): every task x method against the dense backend,
# and engine parity on ring + Erdős–Rényi graphs for all three tasks.
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("task", ["ridge", "logistic", "auc"])
@pytest.mark.parametrize("method", ["dsba", "dsa"])
def test_sparse_comm_trajectory_equals_dense_matrix(task, method):
    problem = _setup(task)
    steps = 60
    indices = draw_indices(steps, problem.data.n_nodes, problem.data.q, seed=7)
    kw = dict(steps=steps, record_every=steps, indices=indices, alpha=0.3)
    dense = solve(problem, method, comm="dense", keep_snapshots=True, **kw)
    sparse = solve(problem, method, comm="sparse",
                   comm_options={"verify": True}, **kw)

    np.testing.assert_allclose(sparse.z, dense.z, rtol=0, atol=1e-12)
    err = sparse.extras["recon_max_err"]
    assert err < 1e-9, err


@pytest.mark.slow
@pytest.mark.parametrize("gname", ["ring", "erdos_renyi"])
@pytest.mark.parametrize("task", ["ridge", "logistic", "auc"])
@pytest.mark.parametrize("method", ["dsba", "dsa"])
def test_vectorized_matches_reference_matrix(gname, task, method):
    """Parity on multi-hop topologies: z_trace, doubles, ints, recon err."""
    _assert_engines_agree(_multi_hop_problem(task, gname), method)


@pytest.mark.slow
def test_sparse_comm_reconstruction_on_larger_diameter_graph():
    """Ring graph (diameter 3): deltas arrive with multi-hop delays."""
    base = _setup("ridge", n_nodes=7)
    graph = mixing.ring_graph(7)
    problem = make_problem("ridge", base.data, graph, lam=1e-3)
    steps = 40
    indices = draw_indices(steps, 7, problem.data.q, seed=3)
    kw = dict(steps=steps, record_every=steps, indices=indices, alpha=0.3)
    dense = solve(problem, "dsba", comm="dense", **kw)
    sparse = solve(problem, "dsba", comm="sparse",
                   comm_options={"verify": True}, **kw)
    np.testing.assert_allclose(sparse.z, dense.z, atol=1e-12)
    assert sparse.extras["recon_max_err"] < 1e-9


@pytest.mark.slow
def test_sparse_comm_cost_at_paper_dimension():
    """Seed-strength cost check: measured accounting at d=600."""
    problem = _setup("ridge", n_nodes=6, d=600, k=5, lam=1e-3)
    steps = 30
    indices = draw_indices(steps, 6, problem.data.q, seed=3)
    res = solve(problem, "dsba", comm="sparse", steps=steps, record_every=1,
                indices=indices, alpha=0.3)
    per_iter = np.diff(res.doubles_received, axis=0)[-10:]
    expect = sparse_doubles_per_iter(6, problem.data.k, problem.spec.tail_dim)
    assert (per_iter == expect).all(), (per_iter, expect)
    dense_cost = dense_doubles_per_iter(problem.graph, problem.data.d)
    assert per_iter.max() * 10 < dense_cost.min()
