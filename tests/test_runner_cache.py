"""Compiled-runner cache + ``solve_many`` sweep-engine semantics.

Three claims (ISSUE 5 / docs/solvers.md):

1. Keying — distinct problems (different N/d/dtype/operator family) never
   collide; a problem rebuilt around the same data/graph (fresh equal W,
   new lam) shares one runner.
2. No retrace on hyperparameter sweeps — a second ``solve()`` on the same
   (problem shape, method, comm) with NEW hp values must not re-trace:
   asserted via the cache's trace counter, which is incremented from
   *inside* the traced function (counts XLA traces, not calls).
3. Correctness — warm-cache results are bit-equal to a cold call, and the
   vmapped ``solve_many`` grid — dense AND sparse — is bit-identical to
   sequential ``solve()`` calls (with the documented sequential fallback
   for ``engine="reference"`` and for grids that vary a static
   hyperparameter).
"""
import numpy as np
import pytest

from repro.core import mixing, runner_cache
from repro.core.solvers import (
    clear_runner_caches,
    make_problem,
    runner_cache_stats,
    solve,
    solve_many,
)
from repro.data.synthetic import make_classification, make_regression

STEPS = 24
REC = 8


def _problem(task="ridge", n_nodes=5, q=6, d=16, k=4, lam=1e-2, seed=0,
             dtype=np.float64):
    if task == "ridge":
        data = make_regression(n_nodes, q, d, k=k, seed=seed, dtype=dtype)
    else:
        data = make_classification(n_nodes, q, d, k=k, seed=seed)
    graph = mixing.erdos_renyi_graph(n_nodes, 0.5, seed=1)
    return make_problem(task, data, graph, lam=lam)


@pytest.fixture(autouse=True)
def _fresh_caches():
    clear_runner_caches()
    yield
    clear_runner_caches()


# ---------------------------------------------------------------------------
# no-retrace: hp values are traced arguments, not cache-key material
# ---------------------------------------------------------------------------


def test_second_solve_with_new_hp_does_not_retrace():
    problem = _problem()
    solve(problem, "dsba", steps=STEPS, record_every=REC, alpha=0.3)
    s0 = runner_cache_stats()["dense"]
    assert s0["misses"] == 1 and s0["traces"] >= 1
    solve(problem, "dsba", steps=STEPS, record_every=REC, alpha=0.9)
    s1 = runner_cache_stats()["dense"]
    assert s1["traces"] == s0["traces"], "new alpha must not recompile"
    assert s1["hits"] == s0["hits"] + 1
    assert s1["misses"] == s0["misses"]


def test_new_lam_on_same_data_does_not_retrace():
    """bench_table1's sweep shape: fresh Problem per lam, same data/graph."""
    data = make_regression(5, 6, 16, k=4, seed=0)
    graph = mixing.ring_graph(5)
    for lam in (1e-1, 1e-2, 1e-3):
        problem = make_problem("ridge", data, graph, lam=lam)
        solve(problem, "dsba", steps=STEPS, record_every=REC, alpha=0.5)
    s = runner_cache_stats()["dense"]
    assert s["misses"] == 1 and s["hits"] == 2


def test_sparse_second_call_with_new_hp_does_not_retrace():
    problem = _problem()
    solve(problem, "dsba", comm="sparse", steps=STEPS, record_every=REC,
          alpha=0.3)
    s0 = runner_cache_stats()["sparse"]
    assert s0["misses"] == 1 and s0["traces"] == 1
    solve(problem, "dsba", comm="sparse", steps=STEPS, record_every=REC,
          alpha=0.7)
    s1 = runner_cache_stats()["sparse"]
    assert s1["traces"] == s0["traces"], "new alpha must not recompile"
    assert s1["hits"] == s0["hits"] + 1


def test_static_hp_change_recompiles_but_value_sweep_does_not():
    problem = _problem()
    solve(problem, "ssda", steps=4, record_every=4, eta=0.05)
    s0 = runner_cache_stats()["dense"]
    solve(problem, "ssda", steps=4, record_every=4, eta=0.01, momentum=0.9)
    s1 = runner_cache_stats()["dense"]
    assert s1["traces"] == s0["traces"]  # eta/momentum are traced
    solve(problem, "ssda", steps=4, record_every=4, inner_newton=4)
    s2 = runner_cache_stats()["dense"]
    assert s2["misses"] == s1["misses"] + 1  # structural: new runner


# ---------------------------------------------------------------------------
# keying: distinct problems never collide
# ---------------------------------------------------------------------------


def test_fault_plans_share_runners_by_fault_structure():
    """A p=0 plan runs the plan-free runner; a p>0 plan builds one fault
    runner, and a second p>0 plan of another rate and seed reuses it with
    no new trace (the masks are scan inputs, not cache-key material)."""
    from repro.core.solvers import FaultPlan, LinkFault

    problem = _problem()
    kw = dict(steps=STEPS, record_every=REC, alpha=0.3)

    def plan(p, seed):
        return {"fault_plan": FaultPlan(link=LinkFault(p=p, seed=seed))}

    solve(problem, "dsba", **kw)
    s0 = runner_cache_stats()["dense"]
    solve(problem, "dsba", comm_options=plan(0.0, 1), **kw)
    s1 = runner_cache_stats()["dense"]
    assert s1["hits"] == s0["hits"] + 1 and s1["misses"] == s0["misses"]
    assert s1["traces"] == s0["traces"]
    faulty = solve(problem, "dsba", comm_options=plan(0.2, 1), **kw)
    assert faulty.extras["faults"]["drop_rate"] > 0
    s2 = runner_cache_stats()["dense"]
    assert s2["misses"] == s1["misses"] + 1 and s2["size"] == 2
    solve(problem, "dsba", comm_options=plan(0.4, 9), **kw)
    s3 = runner_cache_stats()["dense"]
    assert s3["hits"] == s2["hits"] + 1 and s3["misses"] == s2["misses"]
    assert s3["traces"] == s2["traces"], "a new rate must not recompile"


def test_distinct_problems_do_not_collide():
    problems = [
        _problem(),                      # base
        _problem(n_nodes=6),             # different N (and graph)
        _problem(d=24),                  # different d
        _problem(dtype=np.float32),      # different dtype
        _problem(task="logistic"),       # different operator family
    ]
    results = [
        solve(p, "dsba", steps=STEPS, record_every=REC, alpha=0.3)
        for p in problems
    ]
    assert runner_cache_stats()["dense"]["misses"] == len(problems)
    # every cached runner keeps answering for ITS problem
    for p, r in zip(problems, results):
        again = solve(p, "dsba", steps=STEPS, record_every=REC, alpha=0.3)
        assert np.array_equal(r.z, again.z)
    s = runner_cache_stats()["dense"]
    assert s["misses"] == len(problems) and s["hits"] == len(problems)


def test_same_shape_different_data_objects_do_not_collide():
    """Identity keying: equal shapes but different samples must miss."""
    graph = mixing.ring_graph(5)
    pa = make_problem(
        "ridge", make_regression(5, 6, 16, k=4, seed=0), graph, lam=1e-2
    )
    pb = make_problem(
        "ridge", make_regression(5, 6, 16, k=4, seed=7), graph, lam=1e-2
    )
    ra = solve(pa, "dsba", steps=STEPS, record_every=REC, alpha=0.3)
    rb = solve(pb, "dsba", steps=STEPS, record_every=REC, alpha=0.3)
    assert runner_cache_stats()["dense"]["misses"] == 2
    assert not np.array_equal(ra.z, rb.z)


# ---------------------------------------------------------------------------
# correctness: cached == cold, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method,hp", [
    ("dsba", {"alpha": 0.4}),
    ("dsa", {"alpha": 0.2}),
    ("extra", {"alpha": 0.2}),
    ("dlm", {"c": 0.3, "beta": 1.0}),
    ("ssda", {"eta": 0.05, "momentum": 0.5}),
])
def test_cached_results_bit_equal_to_cold(method, hp):
    problem = _problem()
    problem.solve_star()
    kw = dict(steps=STEPS, record_every=REC, keep_snapshots=True)
    cold = solve(problem, method, **kw, **hp)
    # pollute the runner with other hp values, then replay the originals
    other = {k: 0.5 * v for k, v in hp.items()}
    solve(problem, method, **kw, **other)
    warm = solve(problem, method, **kw, **hp)
    assert runner_cache_stats()["dense"]["hits"] >= 2
    assert np.array_equal(cold.z, warm.z)
    assert np.array_equal(cold.zs, warm.zs)
    assert np.array_equal(cold.dist2, warm.dist2)
    assert np.array_equal(cold.consensus, warm.consensus)


def test_sparse_cached_bit_equal_to_cold():
    problem = _problem()
    kw = dict(comm="sparse", steps=STEPS, record_every=REC)
    cold = solve(problem, "dsba", **kw, alpha=0.3)
    solve(problem, "dsba", **kw, alpha=0.8)
    warm = solve(problem, "dsba", **kw, alpha=0.3)
    assert np.array_equal(cold.z, warm.z)
    assert np.array_equal(
        cold.extras["z_trace"], warm.extras["z_trace"]
    )
    assert np.array_equal(cold.doubles_received, warm.doubles_received)


# ---------------------------------------------------------------------------
# solve_many: vmapped grid == sequential solves; documented fallbacks
# ---------------------------------------------------------------------------


def test_solve_many_grid_matches_sequential_bit_equal():
    problem = _problem()
    problem.solve_star()
    grid = [{"alpha": 0.3}, {"alpha": 0.5}, {"alpha": 0.8}]
    many = solve_many(problem, "dsba", steps=STEPS, record_every=REC,
                      grid=grid, keep_snapshots=True)
    assert many.extras["batched"] is True
    assert many.dist2.shape == (3, len(many.iters))
    for b, hp in enumerate(grid):
        seq = solve(problem, "dsba", steps=STEPS, record_every=REC,
                    keep_snapshots=True, **hp)
        assert np.array_equal(many.z[b], seq.z)
        assert np.array_equal(many.zs[b], seq.zs)
        assert np.array_equal(many.dist2[b], seq.dist2)
        assert np.array_equal(many.consensus[b], seq.consensus)
        assert np.array_equal(many.doubles_received[b], seq.doubles_received)


def test_solve_many_seed_axis_matches_sequential():
    problem = _problem()
    seeds = [3, 4, 5]
    many = solve_many(problem, "dsba", steps=STEPS, record_every=REC,
                      seeds=seeds, alpha=0.4)
    for b, s in enumerate(seeds):
        seq = solve(problem, "dsba", steps=STEPS, record_every=REC,
                    seed=s, alpha=0.4)
        assert np.array_equal(many.z[b], seq.z)


def test_solve_many_sparse_batched_matches_sequential_bit_equal():
    """The vmapped relay sweep is bit-identical to sequential solve()s,
    including the closed-form message accounting (hoisted out of the scan,
    so batching cannot perturb it)."""
    problem = _problem()
    grid = [{"alpha": 0.3}, {"alpha": 0.6}]
    seeds = [3, 4]
    many = solve_many(problem, "dsba", comm="sparse", steps=STEPS,
                      record_every=REC, grid=grid, seeds=seeds)
    assert many.extras["batched"] is True
    assert many.doubles_received.shape[0] == 2
    for b, hp in enumerate(grid):
        seq = solve(problem, "dsba", comm="sparse", steps=STEPS,
                    record_every=REC, seed=seeds[b], **hp)
        assert np.array_equal(many.z[b], seq.z)
        assert np.array_equal(many.doubles_received[b], seq.doubles_received)
        assert np.array_equal(many.ints_received[b], seq.ints_received)
        assert np.array_equal(
            many.extras["per_run_extras"][b]["z_trace"],
            seq.extras["z_trace"],
        )


def test_solve_many_sparse_reference_engine_falls_back_sequential():
    """The per-observer oracle loop is not vmappable: engine="reference"
    declines the batch and runs the documented sequential path."""
    problem = _problem()
    many = solve_many(problem, "dsba", comm="sparse", steps=STEPS,
                      record_every=REC, grid=[{"alpha": 0.3}, {"alpha": 0.6}],
                      comm_options={"engine": "reference"})
    assert many.extras["batched"] is False
    assert many.z.shape[0] == 2


def test_solve_many_static_hp_grid_falls_back_sequential():
    problem = _problem()
    many = solve_many(problem, "ssda", steps=4, record_every=4,
                      grid=[{"inner_newton": 4}, {"inner_newton": 8}])
    assert many.extras["batched"] is False
    assert many.z.shape[0] == 2


def test_solve_many_validation():
    problem = _problem()
    with pytest.raises(ValueError, match="grid, seeds"):
        solve_many(problem, "dsba", steps=4)
    with pytest.raises(ValueError, match="pair up"):
        solve_many(problem, "dsba", steps=4, grid=[{}], seeds=[0, 1])
    with pytest.raises(ValueError, match="at least one"):
        solve_many(problem, "dsba", steps=4, grid=[])
    with pytest.raises(TypeError, match="unknown hyperparameters"):
        solve_many(problem, "dsba", steps=4, grid=[{"learning_rate": 0.1}])
    with pytest.raises(ValueError, match="indices"):
        solve_many(problem, "dsba", steps=40, seeds=[0, 1],
                   indices=np.zeros((2, 10, 5), np.int32))


def test_factory_hp_guard_is_a_mapping_of_statics_only():
    """Reading a runtime-traced name at factory time fails loudly; the
    Mapping protocol (in / get / iteration) stays honest for probing."""
    from repro.core.solvers import TracedHPError, _FactoryHP

    fhp = _FactoryHP({"alpha": 0.3, "inner": 4}, static=("inner",))
    assert fhp["inner"] == 4
    with pytest.raises(TracedHPError, match="runtime-traced"):
        fhp["alpha"]
    with pytest.raises(KeyError):
        fhp["nope"]
    assert "alpha" not in fhp and "inner" in fhp
    assert fhp.get("alpha", None) is None  # probing never explodes
    assert dict(fhp) == {"inner": 4}


def test_cache_is_lru_bounded():
    cap = runner_cache.DENSE.capacity
    runner_cache.DENSE.capacity = 2
    try:
        problems = [_problem(seed=s) for s in range(3)]
        for p in problems:
            solve(p, "dsba", steps=4, record_every=4, alpha=0.3)
        s = runner_cache_stats()["dense"]
        assert s["size"] == 2 and s["evictions"] == 1
        # evicted (oldest) problem rebuilds; the newest still hits
        solve(problems[-1], "dsba", steps=4, record_every=4, alpha=0.5)
        assert runner_cache_stats()["dense"]["hits"] >= 1
        solve(problems[0], "dsba", steps=4, record_every=4, alpha=0.3)
        assert runner_cache_stats()["dense"]["misses"] == 4
    finally:
        runner_cache.DENSE.capacity = cap
