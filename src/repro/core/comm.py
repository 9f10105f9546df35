"""Pluggable communication primitives: how a solver's mixing step executes.

Every solver in ``core.solvers`` is written against two primitives instead
of a literal matmul (docs/solvers.md has the authoring contract):

* ``comm.matvec(M, dtype)`` returns ``mix(X)`` computing ``M @ X`` for a
  graph-supported matrix ``M`` (off-diagonal nonzeros only on edges of the
  communication graph — W, W~, the Laplacian and I - W all qualify);
* ``comm.local(x)`` returns the caller's node-block of a leading-N array
  (the node-local data slice inside the traced step).

``DenseComm`` is the single-device backend: ``mix`` is the matmul itself
and ``local`` is the identity, so the compiled step is byte-for-byte the
pre-refactor inlined ``W @ X`` program. ``ShardedComm`` places one graph
node per device of a ``"node"``-axis mesh (``launch.mesh.make_node_mesh``)
and executes ``mix`` as real neighbor exchange: the graph's edges are
greedily edge-colored into matchings and each matching becomes ONE
``lax.ppermute`` carrying both directions, so a step moves O(deg) blocks
per node — never O(N) — and the emitted ``collective-permute`` ops are
measurable from HLO (``launch.hlo_analysis.collective_stats``).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.mixing import Graph

NODE_AXIS = "node"


def edge_coloring(edges, n: int) -> list[list[tuple[int, int]]]:
    """Greedy proper edge coloring: partition ``edges`` into matchings.

    Each color class touches every node at most once, so its edges — both
    directions — fit in a single ``lax.ppermute`` (whose source/dest lists
    must each be distinct). Greedy over the sorted edge list uses at most
    2*maxdeg - 1 colors (Vizing needs maxdeg + 1; the difference is a few
    extra ppermutes, not correctness) and is deterministic, keeping the
    compiled HLO stable across processes.
    """
    colors: list[list[tuple[int, int]]] = []
    busy: list[set[int]] = []
    for i, j in sorted(edges):
        for c, nodes in enumerate(busy):
            if i not in nodes and j not in nodes:
                colors[c].append((i, j))
                nodes.update((i, j))
                break
        else:
            colors.append([(i, j)])
            busy.append({i, j})
    return colors


def _check_support(m: np.ndarray, graph: Graph, atol: float = 0.0) -> None:
    """Reject matrices with off-diagonal mass outside the graph's edges."""
    mask = np.zeros((graph.n, graph.n), dtype=bool)
    for i, j in graph.edges:
        mask[i, j] = mask[j, i] = True
    np.fill_diagonal(mask, True)
    bad = np.abs(np.where(mask, 0.0, m))
    if bad.max(initial=0.0) > atol:
        i, j = np.unravel_index(int(bad.argmax()), bad.shape)
        raise ValueError(
            f"matrix entry ({i}, {j}) = {m[i, j]} is nonzero but ({i}, {j}) "
            "is not an edge of the communication graph; sharded mixing only "
            "moves data along edges"
        )


class DenseComm:
    """Single-device backend: ``mix`` is the matmul, ``local`` the identity."""

    name = "dense"

    def __init__(self, graph: Graph):
        """Bind the communication graph (unused beyond documentation)."""
        self.graph = graph

    def matvec(self, m: np.ndarray, dtype) -> Callable[[jax.Array], jax.Array]:
        """``mix(X) = M @ X`` with ``M`` baked as a device constant.

        The product runs at full precision: the TPU's default matmul
        precision rounds f32 operands to bf16, which would make the dense
        backend a lower-precision algorithm than the sparse relay and the
        sharded backend (both mix with exact elementwise sums). The CPU
        ignores the setting, so CPU results are unchanged.
        """
        m_j = jnp.asarray(m, dtype)
        return lambda x: jnp.matmul(m_j, x, precision=lax.Precision.HIGHEST)

    def local(self, x: jax.Array) -> jax.Array:
        """Identity: the whole array is this (only) caller's block."""
        return x


class ShardedComm:
    """One graph node per mesh device; ``mix`` is edge-wise ``ppermute``.

    Requires ``mesh`` to carry a ``"node"`` axis of size exactly
    ``graph.n`` — the mapping of nodes to devices is positional. All
    methods other than the constructor must run INSIDE a ``shard_map``
    over that mesh (they read ``lax.axis_index``).
    """

    name = "sharded"
    axis = NODE_AXIS

    def __init__(self, graph: Graph, mesh: jax.sharding.Mesh):
        """Validate the mesh and precompute the edge-coloring schedule."""
        if self.axis not in mesh.axis_names:
            raise ValueError(
                f"sharded comm needs a {self.axis!r} mesh axis; "
                f"got axes {mesh.axis_names}"
            )
        n_devices = mesh.shape[self.axis]
        if n_devices != graph.n:
            raise ValueError(
                f"sharded comm places one graph node per device: graph has "
                f"{graph.n} nodes but the {self.axis!r} axis has {n_devices} "
                "devices (run under XLA_FLAGS="
                "--xla_force_host_platform_device_count=N to simulate)"
            )
        self.graph = graph
        self.mesh = mesh
        self.colors = edge_coloring(graph.edges, graph.n)
        # each matching -> one ppermute moving both directions at once
        self.perms = [
            [pair for (i, j) in color for pair in ((i, j), (j, i))]
            for color in self.colors
        ]

    def matvec(self, m: np.ndarray, dtype) -> Callable[[jax.Array], jax.Array]:
        """``mix(X) = M @ X`` as diag + one ``ppermute`` per edge color.

        The returned closure maps this device's (1, ...) block: it scales
        by ``M``'s diagonal, then for every color receives the permuted
        neighbor blocks and accumulates them weighted by the matching
        ``M[dest, src]`` entries (rows without an edge of that color
        receive zeros from ``ppermute`` and carry weight 0).
        """
        m = np.asarray(m)
        _check_support(m, self.graph)
        diag_j = jnp.asarray(np.diag(m).copy(), dtype)
        wrecvs = []
        for color in self.colors:
            wrecv = np.zeros(self.graph.n, dtype=m.dtype)
            for i, j in color:
                wrecv[i] = m[i, j]
                wrecv[j] = m[j, i]
            wrecvs.append(jnp.asarray(wrecv, dtype))

        def shaped(w_col, x):
            return w_col.reshape((-1,) + (1,) * (x.ndim - 1))

        def mix(x):
            out = shaped(self.local(diag_j), x) * x
            for perm, wrecv in zip(self.perms, wrecvs):
                recv = lax.ppermute(x, self.axis, perm)
                out = out + shaped(self.local(wrecv), x) * recv
            return out

        return mix

    def local(self, x: jax.Array) -> jax.Array:
        """This device's node block: row ``axis_index('node')`` of ``x``."""
        i = lax.axis_index(self.axis)
        return lax.dynamic_slice_in_dim(x, i, 1, axis=0)


# ---------------------------------------------------------------------------
# Fault-injecting backends (ft.faults plans, resolved to per-step masks)
# ---------------------------------------------------------------------------


class FaultyDenseComm(DenseComm):
    """DenseComm with link-drop masks and straggler delivery buffers.

    The fault runner in ``core.solvers`` drives the trace-time context:
    inside the scan body it calls ``begin_step(mask_t, deliv_t, bufs)``
    before the solver step and ``end_step()`` after, so the ``mix``
    closures (created once at factory time) read the CURRENT iteration's
    masks and buffers as captured tracers.

    Link faults (``has_link``): ``mix`` becomes a masked matvec with
    row-renormalization — dropped neighbor entries are zeroed and their
    mass redirected to the receiver's own (always fresh) value, so a
    row-stochastic ``W`` stays row-stochastic under any drop pattern.

    Stragglers (``has_straggler``): each ``mix`` invocation owns one
    last-delivered-value buffer slot, consumed in trace order (the same
    order every trace, since the step function is fixed). A sender whose
    ``deliv_t`` bit is off contributes its buffered value instead of the
    fresh one; the buffer then carries whatever value receivers actually
    used. The diagonal (self) term always reads the fresh value — a node
    never straggles to itself. Slot shapes are discovered by an abstract
    probe evaluation of the step function (``begin_probe``/``end_probe``)
    before the runner's scan carry is assembled.
    """

    name = "dense"

    def __init__(self, graph: Graph, has_link: bool, has_straggler: bool):
        """Bind the graph and which fault families are active."""
        super().__init__(graph)
        self.has_link = bool(has_link)
        self.has_straggler = bool(has_straggler)
        self._probing = False
        self._probe_shapes: list[jax.ShapeDtypeStruct] = []
        self._mask = None
        self._deliv = None
        self._bufs: tuple = ()
        self._new_bufs: list = []
        self._slot = 0

    # -- trace-time context driven by the fault runner ----------------------

    def begin_probe(self) -> None:
        """Enter shape-probe mode: ``mix`` runs plain, ``_use`` records."""
        self._probing = True
        self._probe_shapes = []

    def end_probe(self) -> list:
        """Leave probe mode; the recorded buffer slot shapes, in order."""
        self._probing = False
        shapes, self._probe_shapes = self._probe_shapes, []
        return shapes

    def begin_step(self, mask, deliv, bufs) -> None:
        """Install this iteration's masks and buffers (scan-body call)."""
        self._mask = mask
        self._deliv = deliv
        self._bufs = bufs
        self._new_bufs = []
        self._slot = 0

    def end_step(self) -> tuple:
        """The updated buffer tuple for the scan carry."""
        new = tuple(self._new_bufs)
        self._mask = self._deliv = None
        self._bufs, self._new_bufs = (), []
        return new

    def _use(self, x: jax.Array) -> jax.Array:
        """The value receivers see from each sender: fresh or buffered."""
        if not self.has_straggler:
            return x
        if self._probing:
            self._probe_shapes.append(jax.ShapeDtypeStruct(x.shape, x.dtype))
            return x
        buf = self._bufs[self._slot]
        self._slot += 1
        d = self._deliv.reshape((-1,) + (1,) * (x.ndim - 1))
        x_used = jnp.where(d, x, buf)
        self._new_bufs.append(x_used)
        return x_used

    def matvec(self, m: np.ndarray, dtype) -> Callable[[jax.Array], jax.Array]:
        """``mix(X) = M_eff(t) @ X_used(t)``: masked rows, buffered senders."""
        m_j = jnp.asarray(m, dtype)
        diag_j = jnp.asarray(np.diag(np.asarray(m)).copy(), dtype)

        def col(v, x):
            return v.reshape((-1,) + (1,) * (x.ndim - 1))

        def mix(x):
            if self._probing:
                return m_j @ self._use(x)
            x_used = self._use(x)
            if self.has_link:
                mask = self._mask
                zero = jnp.zeros((), dtype)
                kept = jnp.where(mask, m_j, zero)
                dropped = jnp.where(mask, zero, m_j).sum(axis=1)
                # dropped neighbor mass redirects to self — always fresh
                out = kept @ x_used + col(dropped, x) * x
            else:
                out = m_j @ x_used
            if self.has_straggler:
                # the self term must read the fresh value, not the buffer
                out = out + col(diag_j, x) * (x - x_used)
            return out

        return mix


class FaultyShardedComm(ShardedComm):
    """ShardedComm with a per-step link delivery mask (no stragglers).

    Each edge-color ``ppermute`` still executes physically — a dropped
    message is discarded at the RECEIVER (its weight is zeroed and the
    mass redirected to self), so the HLO-measured collective bytes are
    identical to the fault-free program while the modeled
    ``doubles_received`` accounting counts only delivered traffic
    (docs/solvers.md). The mask arrives replicated; each device reads its
    own row and, per color, the bit of its peer in that matching.
    """

    name = "sharded"

    def __init__(self, graph: Graph, mesh: jax.sharding.Mesh):
        """Precompute, per color, each node's peer index in the matching."""
        super().__init__(graph, mesh)
        self.srcs = []
        for color in self.colors:
            src = np.arange(graph.n)
            for i, j in color:
                src[i] = j
                src[j] = i
            self.srcs.append(jnp.asarray(src, jnp.int32))
        self._mask = None

    def begin_step(self, mask) -> None:
        """Install this iteration's (N, N) delivery mask (scan-body call)."""
        self._mask = mask

    def end_step(self) -> None:
        """Clear the per-step mask (no carried buffers on this backend)."""
        self._mask = None

    def matvec(self, m: np.ndarray, dtype) -> Callable[[jax.Array], jax.Array]:
        """Masked, renormalized ``mix``: ppermute everything, keep delivered."""
        m = np.asarray(m)
        _check_support(m, self.graph)
        diag_j = jnp.asarray(np.diag(m).copy(), dtype)
        wrecvs = []
        for color in self.colors:
            wrecv = np.zeros(self.graph.n, dtype=m.dtype)
            for i, j in color:
                wrecv[i] = m[i, j]
                wrecv[j] = m[j, i]
            wrecvs.append(jnp.asarray(wrecv, dtype))

        def shaped(w_col, x):
            return w_col.reshape((-1,) + (1,) * (x.ndim - 1))

        def mix(x):
            mask_row = self.local(self._mask)[0]  # (N,) — this node's row
            out = shaped(self.local(diag_j), x) * x
            dropped = jnp.zeros((1,) + (1,) * (x.ndim - 1), dtype)
            for perm, wrecv, src in zip(self.perms, wrecvs, self.srcs):
                recv = lax.ppermute(x, self.axis, perm)
                w_c = shaped(self.local(wrecv), x)
                peer = self.local(src)[0]  # this node's partner (self if none)
                deliv = jnp.take(mask_row, peer)  # diag is always True
                out = out + jnp.where(deliv, w_c, jnp.zeros_like(w_c)) * recv
                dropped = dropped + jnp.where(
                    deliv, jnp.zeros_like(w_c), w_c
                )
            return out + dropped * x

        return mix
