"""Tier-1 driver for the multi-device tier + single-device sharded errors.

The sharded comm backend and the gossip spmd path need >= 8 devices, which
only exist if ``--xla_force_host_platform_device_count`` was set before
jax initialized. The driver spawns tests/multidevice/ in a subprocess with
the flag forced (``forced_devices_pytest`` in conftest.py) and asserts the
whole inner tier ran — zero skips — and passed. The error-path tests below
need no devices and run inline.
"""
import re

import numpy as np
import pytest

from repro.core import mixing
from repro.core.comm import DenseComm, ShardedComm, edge_coloring


def test_multidevice_tier_passes(forced_devices_pytest):
    proc = forced_devices_pytest("tests/multidevice", n_devices=8)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out
    m = re.search(r"(\d+) passed", out)
    assert m, out
    # 14 parity cases (7 methods x 2 graphs) + the dsgda/bilinear parity,
    # the sharded capability matrix, the accounting/cache/error/gossip
    # tests, and the dynamic-network leg (churn shrink 8->6 parity + the
    # schedule switch): the tier must actually RUN under 8 devices, not
    # skip itself away
    assert int(m.group(1)) >= 24, out
    assert "skipped" not in out, out


def test_make_node_mesh_raises_with_reproduction_hint():
    from repro.launch.mesh import make_node_mesh

    import jax

    n = len(jax.devices()) + 1
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_node_mesh(n)


def test_production_mesh_raises_on_short_devices():
    """The old behavior built a short-device mesh and failed inside jax's
    reshape; now the device-count check fails first, with the fix."""
    from repro.launch.mesh import make_production_mesh

    with pytest.raises(ValueError, match="256 devices"):
        make_production_mesh()
    with pytest.raises(ValueError, match="512 devices"):
        make_production_mesh(multi_pod=True)


def test_edge_coloring_is_a_partition_into_matchings():
    graph = mixing.erdos_renyi_graph(12, 0.4, seed=3)
    colors = edge_coloring(graph.edges, graph.n)
    seen = []
    for color in colors:
        nodes = [v for e in color for v in e]
        assert len(nodes) == len(set(nodes))  # a matching
        seen.extend(tuple(sorted(e)) for e in color)
    assert sorted(seen) == sorted(tuple(sorted(e)) for e in graph.edges)
    maxdeg = max(
        sum(1 for e in graph.edges if v in e) for v in range(graph.n)
    )
    assert len(colors) <= 2 * maxdeg - 1
    # deterministic: same input, same schedule (stable HLO across processes)
    assert colors == edge_coloring(graph.edges, graph.n)


def test_dense_comm_matvec_is_the_matmul():
    import jax.numpy as jnp

    graph = mixing.ring_graph(6)
    w = mixing.metropolis_mixing(graph)
    comm = DenseComm(graph)
    x = np.random.default_rng(0).standard_normal((6, 4))
    got = comm.matvec(w, jnp.float64)(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(
        jnp.asarray(w, jnp.float64) @ jnp.asarray(x)))
    np.testing.assert_array_equal(
        np.asarray(comm.local(jnp.asarray(x))), x
    )


def test_sharded_comm_rejects_off_graph_matrix():
    from repro.core.comm import _check_support

    graph = mixing.ring_graph(5)
    m = np.asarray(mixing.metropolis_mixing(graph))
    bad = m.copy()
    bad[0, 2] = 0.1  # (0, 2) is not a ring edge
    with pytest.raises(ValueError, match="not an edge"):
        _check_support(bad, graph)
    _check_support(m, graph)  # the real mixing matrix passes


def test_sharded_comm_requires_node_axis_mesh():
    from repro.launch.mesh import make_test_mesh

    graph = mixing.ring_graph(4)
    mesh = make_test_mesh((1,), ("pod",))
    with pytest.raises(ValueError, match="'node' mesh axis"):
        ShardedComm(graph, mesh)
