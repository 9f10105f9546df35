"""Mesh construction: production pod meshes + the node-axis solver mesh.

Defined as FUNCTIONS so importing this module never touches jax device
state. Single pod = 16x16 = 256 chips ('data', 'model'); multi-pod adds the
'pod' axis (2 pods = 512 chips) — the decentralized-learning graph axis of
the paper (DESIGN.md §3).

``make_node_mesh`` is the solver-facing variant: a 1-D ``"node"`` axis
placing one graph node per device, the substrate of the ``comm="sharded"``
backend (``core.comm.ShardedComm``). On CPU, simulate N devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set BEFORE jax is
imported — tests spawn a subprocess tier for this, see tests/conftest.py).

Every mesh here has Auto axes: the model code places activations with
``with_sharding_constraint``, which refuses the Explicit axes that
``jax.make_mesh`` makes by default.
"""
from __future__ import annotations

import jax
import numpy as np


def _auto_mesh(shape, axes, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis Auto (see the module docstring)."""
    return jax.make_mesh(
        shape, axes, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_node_mesh(n: int, devices=None) -> jax.sharding.Mesh:
    """1-D mesh with a ``"node"`` axis of ``n`` devices, one graph node each.

    devices: explicit device list (defaults to ``jax.devices()``); the
    first ``n`` back the mesh. Raises with a reproduction hint when fewer
    than ``n`` devices exist rather than building a short mesh.
    """
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < n:
        raise ValueError(
            f"node mesh needs {n} devices, found {len(devs)}; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            "importing jax"
        )
    return _auto_mesh((n,), ("node",), np.asarray(devs[:n]))


def make_production_mesh(*, multi_pod: bool = False):
    """The (pod,) data x model production mesh over exactly-counted devices.

    Raises when fewer devices exist than the mesh shape needs instead of
    silently handing ``jax.make_mesh`` a short device array (which used to
    fail deep inside jax's mesh reshape with an inscrutable error).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    avail = len(jax.devices())
    if avail == n:
        return _auto_mesh(shape, axes)
    if avail < n:
        raise ValueError(
            f"production mesh {dict(zip(axes, shape))} needs {n} devices, "
            f"found {avail}; for a host dry-run set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}"
        )
    # host-device dry-run with a surplus: the first n placeholders back it
    return _auto_mesh(shape, axes, np.asarray(jax.devices()[:n]))


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over however many host devices a test configured."""
    n = int(np.prod(shape))
    return _auto_mesh(shape, axes, np.asarray(jax.devices()[:n]))
