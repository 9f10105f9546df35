"""Multi-device tier: sharded comm parity + gossip spmd backend, 8 devices.

These tests require a real (forced-host) multi-device runtime:
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` must be set BEFORE
jax initializes, which a normal pytest process cannot retrofit. The tier-1
driver ``tests/test_sharded.py`` runs this directory in a fresh subprocess
with the flag set (the ``forced_devices_pytest`` fixture in conftest.py);
collected in an ordinary single-device run, everything here skips.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs >= 8 devices (run via tests/test_sharded.py)",
)

N = 8
METHOD_HP = {
    "dsba": {"alpha": 0.05},
    "dsa": {"alpha": 0.05},
    "extra": {"alpha": 0.05},
    "dlm": {"c": 0.5, "beta": 1.0},
    "ssda": {"eta": 0.05},
    # accelerated/sliding methods route K inner gossip rounds (resp. the
    # periodic mixing select) through the same comm.matvec primitive
    "mudag": {"eta": 0.5, "momentum": 0.5, "gossip_rounds": 2},
    "sliding": {"alpha": 0.05, "comm_period": 2},
}


def _problem(topology):
    from repro.core import mixing
    from repro.core.solvers import make_problem
    from repro.data.synthetic import make_regression

    data = make_regression(N, 12, 6, k=4, seed=0)
    if topology == "ring":
        graph = mixing.ring_graph(N)
    else:
        graph = mixing.erdos_renyi_graph(N, 0.4, seed=1)
    return make_problem("ridge", data, graph, lam=1e-2)


@pytest.mark.parametrize("topology", ["ring", "erdos_renyi"])
@pytest.mark.parametrize("method", sorted(METHOD_HP))
def test_sharded_matches_dense(method, topology):
    """Every method, both graphs: shard_map mixing == dense matmul 1e-12."""
    from repro.core.solvers import solve

    problem = _problem(topology)
    hp = METHOD_HP[method]
    rd = solve(problem, method, steps=20, record_every=10, seed=1,
               comm="dense", **hp)
    rs = solve(problem, method, steps=20, record_every=10, seed=1,
               comm="sharded", **hp)
    np.testing.assert_allclose(
        np.asarray(rs.z), np.asarray(rd.z), atol=1e-12, rtol=0
    )
    np.testing.assert_allclose(
        np.asarray(rs.dist2), np.asarray(rd.dist2), atol=1e-12, rtol=1e-9
    )


def test_dsgda_sharded_matches_dense_on_bilinear():
    """The minimax family through the sharded backend: same 1e-12 parity."""
    from repro.core import mixing
    from repro.core.solvers import make_problem, solve
    from repro.data.synthetic import make_regression

    data = make_regression(N, 12, 6, k=4, seed=2)
    problem = make_problem(
        "bilinear", data, mixing.ring_graph(N), lam=5e-2
    )
    problem.solve_star()
    kw = dict(steps=20, record_every=10, seed=1, alpha=0.2, eta=0.2)
    rd = solve(problem, "dsgda", comm="dense", **kw)
    rs = solve(problem, "dsgda", comm="sharded", **kw)
    np.testing.assert_allclose(
        np.asarray(rs.z), np.asarray(rd.z), atol=1e-12, rtol=0
    )
    np.testing.assert_allclose(
        np.asarray(rs.dist2), np.asarray(rd.dist2), atol=1e-12, rtol=1e-9
    )


def test_sharded_capability_matrix_no_third_outcome():
    """The sharded leg of tests/test_capabilities.py: every (method,
    family) on a 4-node ring either solves under comm="sharded" or raises
    CapabilityError, in exact agreement with the capability record."""
    from repro.core import mixing
    from repro.core.operators import FAMILIES
    from repro.core.solvers import (
        CapabilityError, available_solvers, make_problem, solve,
    )
    from repro.data.synthetic import make_classification, make_regression

    n, q, d = 4, 4, 6
    hp = {"ssda": dict(eta=1e-3, momentum=0.0),
          "mudag": dict(eta=0.5, momentum=0.5)}
    for family in FAMILIES:
        if family in ("ridge", "bilinear"):
            data = make_regression(n, q, d, k=3, seed=0)
        else:
            data = make_classification(n, q, d, k=3, positive_ratio=0.5,
                                       seed=0)
        problem = make_problem(family, data, mixing.ring_graph(n), lam=1e-2)
        for method, caps in sorted(available_solvers().items()):
            try:
                res = solve(problem, method, comm="sharded", steps=2,
                            record_every=2, seed=0, **hp.get(method, {}))
            except CapabilityError as e:
                assert not caps.supports("sharded", family)
                assert (e.method, e.comm, e.family) == (
                    method, "sharded", family
                )
                continue
            assert caps.supports("sharded", family), (method, family)
            assert np.isfinite(np.asarray(res.z)).all(), (method, family)


def test_measured_collective_bytes_accounting():
    """SolveResult carries HLO-measured collective traffic, scaling with
    iterations, and denser graphs move proportionally more bytes."""
    from repro.core.solvers import solve

    res = {}
    for topology in ("ring", "erdos_renyi"):
        problem = _problem(topology)
        r = solve(problem, "dsba", steps=20, record_every=5, seed=1,
                  comm="sharded", alpha=0.05)
        mb = np.asarray(r.measured_collective_bytes)
        assert mb.shape == r.iters.shape
        assert (mb > 0).all()
        # linear in iteration count: bytes/iter is a compile-time constant
        np.testing.assert_allclose(mb / r.iters, mb[0] / r.iters[0])
        assert r.extras["collectives"]["count_per_iter"] > 0
        assert r.extras["mesh_devices"] == N
        res[topology] = r
    ring, er = res["ring"], res["erdos_renyi"]
    # the ER draw has more edges than the ring -> more collective traffic
    assert (
        er.extras["collectives"]["bytes_per_iter"]
        > ring.extras["collectives"]["bytes_per_iter"]
    )
    # dense comm never reports measured bytes
    rd = solve(_problem("ring"), "dsba", steps=4, seed=1, alpha=0.05)
    assert rd.measured_collective_bytes is None


def test_explicit_mesh_and_runner_cache_key():
    """A prebuilt mesh via comm_options reuses the cached sharded runner."""
    from repro.core import runner_cache
    from repro.core.solvers import solve
    from repro.launch.mesh import make_node_mesh

    problem = _problem("ring")
    mesh = make_node_mesh(N)
    before = runner_cache.SHARDED.stats()["misses"]
    r1 = solve(problem, "dsba", steps=8, seed=1, comm="sharded",
               alpha=0.05, comm_options={"mesh": mesh})
    mid = runner_cache.SHARDED.stats()
    r2 = solve(problem, "dsba", steps=8, seed=1, comm="sharded",
               alpha=0.1, comm_options={"mesh": mesh})
    after = runner_cache.SHARDED.stats()
    assert mid["misses"] == before + 1
    assert after["misses"] == mid["misses"]  # second call: pure hits
    assert after["hits"] > mid["hits"]
    assert not np.array_equal(np.asarray(r1.z), np.asarray(r2.z))


def test_sharded_rejects_wrong_mesh_and_options():
    from repro.core.comm import ShardedComm
    from repro.core.solvers import solve
    from repro.launch.mesh import make_node_mesh

    problem = _problem("ring")
    small = make_node_mesh(4)
    with pytest.raises(ValueError, match="node"):
        ShardedComm(problem.graph, small)
    with pytest.raises(ValueError, match="comm_options"):
        solve(problem, "dsba", steps=2, comm_options={"mesh": small})
    with pytest.raises(ValueError, match="unknown sharded comm_options"):
        solve(problem, "dsba", steps=2, comm="sharded",
              comm_options={"engine": "vectorized"})


def test_gossip_dense_mix_spmd_matches_local():
    """The pod-axis gossip mixing: shard_map backend == local roll backend."""
    from jax.sharding import PartitionSpec as P

    from repro.core.gossip import GossipConfig, make_dense_mix

    gc = GossipConfig(n_pods=8, topology="ring")
    from repro.launch.mesh import make_test_mesh

    mesh = make_test_mesh((8,), ("pod",))
    leaf_specs = {"a": P(), "b": P()}
    rng = np.random.default_rng(3)
    tree = {
        "a": jnp.asarray(rng.standard_normal((8, 5, 3))),
        "b": jnp.asarray(rng.standard_normal((8, 4))),
    }
    local = make_dense_mix(None, gc, None)(tree)
    spmd = jax.jit(make_dense_mix(mesh, gc, leaf_specs))(tree)
    for k in tree:
        np.testing.assert_allclose(
            np.asarray(spmd[k]), np.asarray(local[k]), atol=1e-12
        )
