"""DSBA-s: the sparse-communication implementation of Section 5.1.

This module is the ``comm="sparse"`` backend of the solver registry —
callers go through ``core.solvers.solve(problem, method, comm="sparse")``,
which forwards backend options (``engine``, ``verify``, ``use_pallas``)
into `run_sparse` and folds its accounting into the uniform SolveResult.

Every iteration each node broadcasts ONLY its sparse update difference
delta_n^t (eq. 27) — nnz = one data sample's pattern — and every other node
reconstructs the delayed network state from received deltas via the update
recursion (eq. 28), exactly as Algorithm 2 prescribes. Messages advance one
hop per iteration along BFS trees (the F_j^t relay of the paper), so node u
learns delta_l^tau at iteration tau + xi(l, u); the duplicate-suppression
rule ("only the minimum-index neighbor forwards") means each delta is
received exactly once per node, giving the paper's O(N rho d) per-node
per-iteration communication.

Availability invariant (proved by induction in the paper):
  node u can reconstruct z_l^s at iteration t  iff  s <= t + 1 - xi(l, u),
so in particular neighbors' *current* iterates z_m^t are reconstructable at
iteration t — which is exactly what psi_n^t (eq. 29) needs.

Initialization: the t=0 update (eq. 31) involves the dense, node-private
phibar_n^0, so z^1 cannot be reconstructed from deltas alone. The protocol
therefore floods the (dense) z^1 once during warm-up — a one-time O(N d)
cost that we account for honestly. z^0 is the shared consensus initializer.

Vectorized engine (default, ``engine="vectorized"``)
----------------------------------------------------
The eq. 28 recursion is the SAME affine map for every (observer, source)
pair, so the simulator batches it instead of looping in Python:

* **Ring-buffer reconstruction.** Per-pair stores keep only the last
  ``diameter + 2`` reconstructed iterates — O(N^2 * diam * d) memory
  instead of the previous O(N^2 * T * d) NaN-filled array. Each (observer,
  source) pair owns one row per slot: the N self pairs first (row u holds
  node u's own iterate), then one block per distance level xi = 1..dmax
  with exactly that level's pairs (``_Tables.row``/``start``). Slots follow
  one another along the ring's one row axis, ``R[(s % depth) * n_rows +
  row(u, l)] =`` node u's copy of ``z_l^s``, and a row is the vector as
  whole (8, 128) tiles (``_to_rows``), so every read is a row gather in the
  ring's own layout with only the slot traced, and every write is one
  contiguous block in place. Dense per-source deltas live in a matching
  ``depth * N``-row ring.
* **Distance waves.** At iteration t, pair (u, l) at distance xi advances by
  exactly one state, ``s = t + 1 - xi``. The levels are unrolled (dmax is
  static) and advanced farthest-first (the paper's V_j ordering) so a
  distance-xi pair can consume the value its distance-(xi+1) neighbor
  produced this same iteration. Each level gathers its pairs' neighbour
  rows at slots s-1 and s-2, applies the fused AXPY and writes its block
  at slot s; the one-time dense z^1 flood is the block's value at
  t == xi, and before it (warm-up) the block keeps its old rows.
* **Single XLA program.** The whole run — warm-up flood, waves, mixing rows,
  and the shared local update (core.dsba.make_step_fn) — is one jitted
  ``lax.scan``; per-iteration state never round-trips through NumPy.
* **Closed-form message accounting.** ``doubles_received``/``ints_received``
  are computed after the scan from the per-iteration nnz log:
  ``doubles[t, u] = sum_l nnz[t - xi(u,l), l] + tail`` (+ the one-time dense
  z^1 flood of D doubles at ``t == xi``), instead of inside the hop loop.
* **Pallas hot path.** Densifying the per-node sparse deltas is routed
  through ``kernels.ops.saga_sparse_axpy`` (one-hot select scatter on the
  TPU; ``interpret=True`` fallback off-TPU). The interpret-mode
  compute_dtype policy lives in kernels/ops.py — f64 runs stay bit-exact
  without this module re-deriving the dtype per call site.

``verify=True`` (debug mode) additionally carries an iterate-tag ring and a
truth ring through the scan: every read is checked against the availability
invariant (a violation raises ``ProtocolViolation``) and every reconstructed
value is compared against the true trajectory, reported as
``recon_max_err``. The fast path skips both and reports ``nan``.

``engine="reference"`` is the original per-observer Python loop (kept as the
parity oracle for tests; it always verifies).

Cost model (doubles_received): a delta message carries nnz(delta) = k values
(+ tail_dim scalars for AUC); index integers are tracked separately as
`ints_received` since the paper's C_max counts DOUBLEs. Dense baselines
receive deg(n) * d doubles per iteration.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import runner_cache
from repro.core.dsba import DSBAConfig, init_state, make_step_fn
from repro.core.mixing import Graph, w_tilde
from repro.kernels.ops import saga_sparse_axpy


class ProtocolViolation(AssertionError):
    """A reconstruction consumed a value the relay had not yet delivered."""


@dataclasses.dataclass
class SparseRunResult:
    """What `run_sparse` returns — the module's output contract.

    z_trace is the TRUE trajectory (identical across engines and to a dense
    `solve(..., comm="dense")` run with the same index stream — pinned by
    parity tests);
    doubles/ints are the paper's C_max message accounting (doubles exclude
    index ints by convention); recon_max_err is nan unless `verify=True`
    (the fast path does not carry the truth ring).
    """

    z_trace: np.ndarray  # (T+1, N, D)   true trajectory (z^0 .. z^T)
    doubles_received: np.ndarray  # (T, N) cumulative DOUBLEs per node
    ints_received: np.ndarray  # (T, N) cumulative index ints per node
    recon_max_err: float  # max |reconstruction - truth|; nan unless verified
    state: object | None = None  # final solver state (schedule chaining)


@dataclasses.dataclass(frozen=True)
class _Tables:
    """Static per-graph tables for the vectorized engine (the reference
    engine keeps its own inline dist/neighbor bookkeeping, verbatim from the
    original loop, so the parity oracle stays independent).

    The reconstruction ring gives each (observer, source) pair one row per
    slot: the N self pairs first (row u is pair (u, u)), then one block
    per distance level xi = 1..dmax, holding that level's pairs in
    ``pairs[xi]`` order from row ``start[xi]`` on.
    """

    dist: np.ndarray  # (N, N) BFS distances xi (-1: unreachable)
    nbr_pad: np.ndarray  # (N, A) sorted neighbors + self, padded with self
    wt_pad: np.ndarray  # (N, A) matching W~ weights (0 on padding)
    pad_mask: np.ndarray  # (N, A) True on real entries
    pairs: dict[int, tuple[np.ndarray, np.ndarray]]  # xi -> (obs, src)
    row: np.ndarray  # (N, N) ring row of pair (u, l); -1 if unreachable
    start: dict[int, int]  # xi -> first ring row of its block
    n_rows: int  # ring rows per slot: N self rows + every level's pairs
    dmax: int
    depth: int  # ring-buffer depth = diameter + 2

    @property
    def row_src(self) -> np.ndarray:
        """(n_rows,) the source node of each ring row."""
        src = np.empty(self.n_rows, np.int32)
        src[: len(self.dist)] = np.arange(len(self.dist))
        for xi, (_, l_xi) in self.pairs.items():
            src[self.start[xi]: self.start[xi] + len(l_xi)] = l_xi
        return src


def _protocol_tables(graph: Graph, wt: np.ndarray) -> _Tables:
    n = graph.n
    dist = np.stack([graph.distances_from(u) for u in range(n)])
    lists = [sorted(graph.neighbors(u)) + [u] for u in range(n)]
    width = max(len(x) for x in lists)
    nbr_pad = np.empty((n, width), dtype=np.int32)
    wt_pad = np.zeros((n, width), dtype=wt.dtype)
    pad_mask = np.zeros((n, width), dtype=bool)
    for u, lst in enumerate(lists):
        nbr_pad[u, : len(lst)] = lst
        nbr_pad[u, len(lst) :] = u  # padding reads a live slot, weight 0
        wt_pad[u, : len(lst)] = wt[u, lst]
        pad_mask[u, : len(lst)] = True
    dmax = int(dist.max())
    pairs = {
        xi: tuple(np.nonzero(dist == xi)) for xi in range(1, dmax + 1)
    }
    row = np.full((n, n), -1, dtype=np.int32)
    row[np.arange(n), np.arange(n)] = np.arange(n)
    start, nxt = {}, n
    for xi, (u_xi, l_xi) in pairs.items():
        start[xi] = nxt
        row[u_xi, l_xi] = nxt + np.arange(len(u_xi))
        nxt += len(u_xi)
    return _Tables(dist, nbr_pad, wt_pad, pad_mask, pairs, row, start,
                   n_rows=nxt, dmax=dmax, depth=max(3, dmax + 2))


def _closed_form_costs(
    nnz_log: np.ndarray, dist: np.ndarray, tail: int, d_total: int,
    restart: bool = False, sent: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Cumulative (doubles, ints) per node from the per-iteration nnz log.

    The delta broadcast by source l at iteration tau reaches observer u at
    iteration tau + xi(u, l); the dense z^1 flood (d_total doubles) arrives
    exactly at t == xi. Equivalent to the reference engine's in-loop
    accounting, but one vectorized pass over the (T, N, N) arrival grid.

    ``restart=True`` (a schedule-segment resync) charges a SECOND dense
    flood at t == xi: after a graph change the segment-entry iterates z^0
    are node-private (unlike the consensus-shared initializer of a fresh
    run), so they must be flooded alongside z^1 before any delta-based
    reconstruction can proceed.

    ``sent``: optional (T, N) link-fault mask — a suppressed broadcast
    (``sent[tau, l] == False``) never arrives anywhere, so neither its
    nnz payload nor the per-message tail is charged (delivered-only
    accounting; the one-time floods are fault-exempt, see run_sparse).
    """
    steps, n = nnz_log.shape
    ts = np.arange(steps)[:, None, None]  # (T, 1, 1)
    xi = dist[None, :, :]  # (1, obs, src)
    t_src = ts - xi  # broadcast delta emission time
    arrived = (t_src >= 0) & (xi > 0)
    src = np.arange(n)[None, None, :]
    if sent is not None:
        arrived &= sent[np.clip(t_src, 0, None), src]
    nnz = nnz_log[np.clip(t_src, 0, None), src]  # (T, obs, src)
    ints_inc = np.where(arrived, nnz, 0).sum(axis=2)
    doubles_inc = np.where(arrived, nnz + tail, 0).sum(axis=2)
    floods = 2 if restart else 1
    doubles_inc += floods * d_total * ((ts == xi) & (xi > 0)).sum(axis=2)
    return np.cumsum(doubles_inc, axis=0), np.cumsum(ints_inc, axis=0)


def run_sparse(
    cfg: DSBAConfig,
    data,
    graph: Graph,
    w: np.ndarray,
    steps: int,
    indices: np.ndarray,
    z0: np.ndarray | None = None,
    *,
    state0=None,
    engine: str = "vectorized",
    verify: bool = False,
    use_pallas: str = "auto",
    sent_mask: np.ndarray | None = None,
    ckpt_every: int | None = None,
    ckpt_save=None,
    resume=None,
) -> SparseRunResult:
    """Run DSBA-s (or DSA-s) for `steps` iterations on `graph`.

    engine: "vectorized" (batched jitted scan, default) or "reference"
        (the original per-observer Python loop; always verifies).
    verify: vectorized engine only — check the availability invariant and
        compare every reconstruction against the truth (recon_max_err).
    use_pallas: "auto" routes delta densification through the Pallas kernel
        (compiled on TPU for f32/bf16 data, interpret=True fallback
        elsewhere; f64 data on a TPU takes the jnp scatter, as Mosaic has no
        f64); "on" forces the compiled kernel, "interpret" forces interpret
        mode, and "off" uses a plain jnp scatter (fastest to trace on CPU).
    state0: carried DSBAState from a previous schedule segment. When given,
        the run is a RESTART on (possibly new) `graph`/`w`: the solver
        continues from state0 (its SAGA tables, deltas and step counter
        intact), the t=0 mixing is ``w_tilde(w) @ (2 z - z_prev)`` from the
        carried iterates, and the segment-entry z^0 is flooded densely
        alongside z^1 (charged in the accounting — see _closed_form_costs).
        A ``state0`` whose step counter was REANCHORED to 0 (a churn
        segment — ``solvers._elastic_remap``) instead re-runs the eq. 31
        anchored t=0 update, mixing ``w @ state0.z``. ``z0`` must be None
        in either case.
    sent_mask: optional (steps, N) bool — link-fault injection. A False
        entry suppresses that node's delta broadcast for that iteration:
        every observer's reconstruction proceeds on a zeroed delta (the
        graceful-degradation path) and the closed-form accounting charges
        neither payload nor tail for it. The one-time z^1 / restart z^0
        floods are fault-exempt (they seed the protocol; dropping them
        would desynchronize the ring permanently, not degrade it).
        Vectorized engine only, and incompatible with ``verify`` (the
        truth check asserts exact reconstruction by design).
    ckpt_every / ckpt_save / resume: crash-safe chunked execution driven
        by ``solvers.solve(..., checkpoint=/resume=)``. The scan runs in
        chunks of ``ckpt_every`` iterations; after each boundary
        ``ckpt_save(t_done, tree)`` receives the raw carry plus the
        accumulated (zs, nnzs) logs. ``resume=(t_done, leaves)`` restores
        from ``ckpt.load_checkpoint`` leaves and continues — bit-equal to
        an uninterrupted run (absolute iteration numbers ride in the scan
        xs, so chunk boundaries are invisible to the per-step math).
    """
    if steps < 1:
        raise ValueError("steps must be >= 1")
    if state0 is not None and z0 is not None:
        raise ValueError("pass either z0 (fresh start) or state0 (restart)")
    if sent_mask is not None and verify:
        raise ValueError(
            "verify=True is incompatible with a link-fault sent_mask: the "
            "relay invariant check asserts exact reconstruction, which "
            "injected faults violate by design"
        )
    if engine == "reference":
        if sent_mask is not None:
            raise ValueError(
                "link faults need engine='vectorized' (the reference "
                "per-observer oracle assumes lossless broadcasts)"
            )
        if ckpt_every is not None or resume is not None:
            raise ValueError(
                "checkpoint/resume needs engine='vectorized'"
            )
        return _run_reference(cfg, data, graph, w, steps, indices, z0,
                              state0=state0)
    if engine != "vectorized":
        raise ValueError(f"unknown engine {engine!r}")
    return _run_vectorized(
        cfg, data, graph, w, steps, indices, z0, state0=state0,
        verify=verify, use_pallas=use_pallas, sent_mask=sent_mask,
        ckpt_every=ckpt_every, ckpt_save=ckpt_save, resume=resume,
    )


# ---------------------------------------------------------------------------
# Vectorized engine
# ---------------------------------------------------------------------------

def _sparse_scan_key(cfg, data, graph, w, verify, kernel_mode,
                     faulty=False):
    """(key, guards) for one compiled relay scan (see core.runner_cache).

    alpha/lam are NOT keyed — they are traced scan arguments, so a
    hyperparameter sweep over the same (method, problem shape, graph)
    reuses one executable. ``verify`` changes the carry structure,
    ``kernel_mode`` the densification lowering, and ``faulty`` the scan
    xs (the per-iteration sent mask), so each recompiles. The fault-free
    program stays byte-identical to the pre-fault build — p=0 plans are
    bit-equal by ROUTING, not by masked arithmetic.
    """
    key = (
        "relay",
        cfg.method,
        runner_cache.problem_fingerprint(data, cfg.spec, graph, w),
        bool(verify),
        kernel_mode,
        bool(faulty),
    )
    return key, (data,)


_LANES = 128


def _to_rows(x):
    """(M, D) vectors -> (M, C, 128) ring rows, zero-padded to C * 128.

    A row of whole (8, 128) TPU tiles lies contiguous in memory, so the
    scan gathers rows straight from the ring and writes blocks in place;
    a (rows, D) ring would be staged or re-laid-out for every gather. The
    padding columns stay zero and meet no real column.
    """
    m, d = x.shape
    c = -(-d // _LANES)
    return jnp.pad(x, ((0, 0), (0, c * _LANES - d))).reshape(m, c, _LANES)


def _from_rows(x, d):
    """(M, C, 128) ring rows -> (M, d) vectors; inverse of ``_to_rows``."""
    return x.reshape(x.shape[0], -1)[:, :d]


def _build_sparse_scan(cfg, data, graph, w, *, verify, kernel_mode,
                       faulty=False):
    """Compile the whole-run relay scan with (alpha, lam) traced.

    Returns ``(scan, tb)``: the jitted
    ``scan(carry0, xs, mix0, hp) -> (carry, (zs, nnzs))`` and the static
    protocol tables (the closed-form accounting needs ``tb.dist``).
    """
    spec = cfg.spec
    n = data.n_nodes
    q = data.q
    tail = spec.tail_dim
    d = data.d
    D = d + tail
    dt = data.val.dtype

    wt = w_tilde(w)
    tb = _protocol_tables(graph, wt)
    depth, dmax = tb.depth, tb.dmax
    scale = (q - 1.0) / q

    step = make_step_fn(cfg, data, w)

    # constants baked into the compiled scan
    n_rows = tb.n_rows
    width = tb.nbr_pad.shape[1]
    wtn_j = jnp.asarray(tb.wt_pad, dt)
    # ring rows each read gathers, per slot: the mixing rows read node u's
    # copies of its neighbours; a level-xi pair (u, l) reads u's copies of
    # l's neighbours. Only the slot is traced.
    mix_rows_idx = tb.row[np.arange(n)[:, None], tb.nbr_pad]  # (N, A)
    levels = []  # farthest-first (paper's V_j ordering)
    for xi in range(dmax, 0, -1):
        u_xi, l_xi = tb.pairs[xi]
        levels.append((
            xi,
            tb.start[xi],
            len(u_xi),
            tb.row[u_xi[:, None], tb.nbr_pad[l_xi]],  # (P, A)
            l_xi,
        ))

    def rows(ring, slot, idx, per_slot):
        """Rows ``idx`` (static) of ring slot ``slot`` (traced)."""
        return ring.at[slot * per_slot + jnp.asarray(idx, jnp.int32)].get(
            mode="promise_in_bounds"
        )

    def lead(ring, row):
        return (row,) + (jnp.int32(0),) * (ring.ndim - 1)

    def block(ring, slot, first, size):
        """The ``size`` contiguous rows of slot ``slot`` from ``first``."""
        return jax.lax.dynamic_slice(
            ring, lead(ring, slot * n_rows + first), (size,) + ring.shape[1:]
        )

    def put(ring, slot, first, new, per_slot=n_rows):
        """Write ``new`` over rows ``first..`` of ring slot ``slot``."""
        return jax.lax.dynamic_update_slice(
            ring, new, lead(ring, slot * per_slot + first)
        )

    def densify_delta(st) -> jax.Array:
        """(N, D) dense delta rows from the padded-CSR delta of this step."""
        base = jnp.zeros((n, D), dt)
        if tail:
            base = base.at[:, d:].set(st.dtail_prev)
        # compute_dtype is NOT passed: kernels.ops resolves it centrally
        # (interpret -> psi.dtype, so the f64 relay stays bit-exact;
        # compiled -> f32). See the sparse_axpy registry policy.
        return saga_sparse_axpy(
            base, st.didx_prev, st.dval_prev, st.dg_prev,
            jnp.ones((n,), dt), use_pallas=kernel_mode,
        )

    def neighborhood_sum(g_cur, g_prev, wts):
        """sum_m wt[.,m] * (2 z_m^s - z_m^{s-1}), reference add order."""
        acc = jnp.zeros(g_cur.shape[:1] + g_cur.shape[2:], dt)  # (P, C, L)
        for a in range(width):
            acc = acc + wts[:, a, None, None] * (
                2.0 * g_cur[:, a] - g_prev[:, a]
            )
        return acc

    def scan_all(carry0, xs, mix0, hp):
        # runs only while tracing: counts compiles, not calls
        runner_cache.SPARSE.note_trace()
        alpha, lam = hp["alpha"], hp["lam"]
        return jax.lax.scan(
            lambda carry, x: body(carry, x, mix0, alpha, lam, hp), carry0, xs
        )

    def body(carry, xs, mix0, alpha, lam, hp):
        state, z1, R, DD, SR, Z, err, ok = carry
        if faulty:
            t, i_t, sent_t = xs
        else:
            t, i_t = xs
        jt = t % depth
        jtm1 = (t - 1) % depth
        z_t = _to_rows(state.z)

        # -- own history: z^t is exact and free (computed locally last step)
        R = put(R, jt, 0, z_t)
        if verify:
            SR = put(SR, jt, 0, jnp.full((n,), t, jnp.int32))
            Z = put(Z, jt, 0, z_t, per_slot=n)
        z1 = jnp.where(t == 1, z_t, z1)

        # -- reconstruction waves, farthest-first (paper's V_j ordering) ----
        # Every pair at distance xi advances by exactly one reconstructed
        # state, s = t + 1 - xi, written as one block of the ring. The
        # one-time dense z^1 flood is that block's value at t == xi
        # (s == 1); before it (warm-up) the block keeps its old value.
        # Reads of not-yet-valid slots hit zero-initialized memory
        # (finite), and the value is discarded.
        for xi, first, size, nb_idx, lp in levels:
            s = t + 1 - xi
            j1, j2, jn = (s - 1) % depth, (s - 2) % depth, s % depth
            G1 = rows(R, j1, nb_idx, n_rows)  # (P, A, C, L)
            G2 = rows(R, j2, nb_idx, n_rows)
            mix = neighborhood_sum(G1, G2, wtn_j[lp])
            corr = alpha * (scale * rows(DD, j2, lp, n) - rows(DD, j1, lp, n))
            self1 = block(R, j1, first, size)
            if cfg.method == "dsba":
                new = (mix + alpha * lam * self1 + corr) / (1.0 + alpha * lam)
            else:  # dsa
                self2 = block(R, j2, first, size)
                new = mix + corr - alpha * lam * (self1 - self2)
            new = jnp.where(t == xi, z1[lp], new)
            new = jnp.where(t >= xi, new, block(R, jn, first, size))
            R = put(R, jn, first, new)
            if verify:
                S1 = rows(SR, j1, nb_idx, n_rows)
                S2 = rows(SR, j2, nb_idx, n_rows)
                reads = (S1 == s - 1) & (S2 == s - 2)
                checked = tb.pad_mask[lp] & (t >= xi + 1)
                ok &= jnp.all(jnp.where(checked, reads, True))
                SR = put(SR, jn, first, jnp.where(
                    t >= xi, jnp.full((size,), s, jnp.int32),
                    block(SR, jn, first, size),
                ))
                err = jnp.maximum(err, jnp.where(
                    t >= xi + 1,
                    jnp.max(jnp.abs(new - rows(Z, jn, lp, n))),
                    0.0,
                ))

        # -- mixing rows from each node's OWN reconstruction store ----------
        g_cur = rows(R, jt, mix_rows_idx, n_rows)  # (N, A, C, L)
        g_prev = rows(R, jtm1, mix_rows_idx, n_rows)
        mix_rows = _from_rows(neighborhood_sum(g_cur, g_prev, wtn_j), D)
        mix_rows = jnp.where(t == 0, mix0, mix_rows)
        if verify:
            s_cur = rows(SR, jt, mix_rows_idx, n_rows)
            s_prev = rows(SR, jtm1, mix_rows_idx, n_rows)
            ok &= (t == 0) | jnp.all(
                jnp.where(tb.pad_mask, (s_cur == t) & (s_prev == t - 1), True)
            )

        # -- advance all nodes with the shared local update -----------------
        state = step(state, i_t, mix_rows, hp=hp)
        dd = densify_delta(state)
        nnz_t = jnp.sum(state.dval_prev != 0, axis=-1).astype(jnp.int32)
        if faulty:
            # a suppressed broadcast: observers see a ZEROED delta in the
            # ring (their reconstructions degrade gracefully) and the nnz
            # log drops the row (delivered-only accounting). The source's
            # own row of R stays exact — a node always has its own state.
            dd = jnp.where(sent_t[:, None], dd, jnp.zeros_like(dd))
            nnz_t = jnp.where(sent_t, nnz_t, 0)
        DD = put(DD, jt, 0, _to_rows(dd), per_slot=n)
        return (state, z1, R, DD, SR, Z, err, ok), (state.z, nnz_t)

    return jax.jit(scan_all), tb


def _relay_carry0(cfg, data, z0, tb, verify, state0=None):
    """The relay scan's initial carry at the shared starting point ``z0``.

    With ``state0`` (a schedule-segment restart) the carried solver state is
    used as-is and the reconstruction ring is seeded with its iterates: the
    segment-entry z^0 := state0.z is flooded at segment start (see
    _closed_form_costs), so every observer's store legitimately holds it.
    Rings hold one slot after another, one ring row per vector (see
    ``_to_rows``): ``depth * tb.n_rows`` rows of reconstructions, in
    ``tb.row`` order, and ``depth * N`` rows of dense deltas.
    """
    n = data.n_nodes
    dt = data.val.dtype
    depth, n_rows = tb.depth, tb.n_rows
    if state0 is not None:
        z0 = state0.z
    else:
        state0 = init_state(cfg, data, jnp.asarray(z0))
    z0 = _to_rows(jnp.asarray(z0, dt))
    row = z0.shape[1:]
    R0 = jnp.zeros((depth * n_rows, *row), dt).at[:n_rows].set(
        z0[tb.row_src]
    )
    DD0 = jnp.zeros((depth * n, *row), dt)
    if verify:
        SR0 = jnp.full((depth * n_rows,), -(2**30), jnp.int32)
        SR0 = SR0.at[:n_rows].set(0)
        Z0 = jnp.zeros((depth * n, *row), dt).at[:n].set(z0)
    else:  # zero-size placeholders keep the carry structure uniform
        SR0 = jnp.zeros((0,), jnp.int32)
        Z0 = jnp.zeros((0,), dt)
    return (
        state0,
        jnp.zeros((n, *row), dt),  # z^1, captured at t == 1
        R0,
        DD0,
        SR0,
        Z0,
        jnp.zeros((), dt),
        jnp.ones((), bool),
    )


def _resolve_kernel_mode(use_pallas: str, dtype) -> str:
    """Resolve the relay's ``use_pallas`` option to a concrete kernel mode.

    "auto" compiles the kernel on a TPU for the dtypes Mosaic has. It has
    no float64, so f64 data on a TPU takes the jnp scatter ("off"), which
    is bit-exact to the kernel's f64 interpret path (the relay's
    delta-densification policy in kernels/ops.py).
    """
    if use_pallas not in ("auto", "on", "interpret", "off"):
        raise ValueError(f"unknown use_pallas mode {use_pallas!r}")
    if use_pallas == "auto":
        if jax.default_backend() != "tpu":
            return "interpret"
        return "off" if jnp.dtype(dtype) == jnp.float64 else "on"
    return use_pallas


def _carry_from_leaves(carry0, leaves):
    """Rebuild a relay carry from ``ckpt.load_checkpoint`` leaves.

    ``carry0`` templates the structure (the carry is run-length
    independent); leaves are path-matched under the ``{"carry": ...}``
    wrapper the checkpointing driver saved them with.
    """
    from repro.ckpt.checkpoint import _flatten_with_paths

    paths, tleaves, treedef = _flatten_with_paths({"carry": carry0})
    new = []
    for p, like in zip(paths, tleaves):
        if p not in leaves:
            raise ValueError(f"checkpoint is missing carry leaf {p!r}")
        if np.shape(leaves[p]) != np.shape(like):
            raise ValueError(
                f"checkpoint carry leaf {p!r} has shape "
                f"{np.shape(leaves[p])}, expected {np.shape(like)}: it was "
                "written by a relay with another ring layout"
            )
        new.append(jnp.asarray(leaves[p], getattr(like, "dtype", None)))
    return jax.tree_util.tree_unflatten(treedef, new)["carry"]


def _run_vectorized(
    cfg, data, graph, w, steps, indices, z0, *, state0=None, verify,
    use_pallas, sent_mask=None, ckpt_every=None, ckpt_save=None,
    resume=None,
) -> SparseRunResult:
    spec = cfg.spec
    n = data.n_nodes
    tail = spec.tail_dim
    D = data.d + tail
    dt = data.val.dtype
    restart = state0 is not None
    reanchored = restart and int(np.asarray(state0.step)) == 0
    if restart:
        z0 = np.asarray(state0.z)
    elif z0 is None:
        z0 = np.zeros((n, D), dtype=dt)
    faulty = sent_mask is not None
    if faulty:
        sent_mask = np.asarray(sent_mask, dtype=bool)
        if sent_mask.shape != (steps, n):
            raise ValueError(
                f"sent_mask must be (steps, N) = ({steps}, {n}), "
                f"got {sent_mask.shape}"
            )

    # This path follows the protocol spec rather than kernels.ops "auto"
    # (which falls back to the jnp oracle off-TPU): the relay's delta
    # densification stays on the Pallas kernel everywhere, interpret=True
    # being the CPU fallback. Resolve "auto" here, dispatch through ops.
    kernel_mode = _resolve_kernel_mode(use_pallas, dt)

    key, guards = _sparse_scan_key(
        cfg, data, graph, w, verify, kernel_mode, faulty=faulty
    )
    scan, tb = runner_cache.SPARSE.get_or_build(
        key, guards,
        lambda: _build_sparse_scan(
            cfg, data, graph, w, verify=verify, kernel_mode=kernel_mode,
            faulty=faulty,
        ),
    )

    carry0 = _relay_carry0(cfg, data, z0, tb, verify, state0=state0)
    ts = jnp.arange(steps, dtype=jnp.int32)
    idx_j = jnp.asarray(indices[:steps], jnp.int32)
    if reanchored:
        # a churn-remapped state: the step counter was reset to 0 (the
        # DSBA reanchor), so the scan's first iteration re-runs the
        # eq. 31 anchored update — its t=0 mixing is W against the
        # remapped iterates. The restart z^0 flood is still charged:
        # post-churn iterates are node-private, not consensus-shared.
        mix0 = jnp.asarray(w @ np.asarray(state0.z), dt)
    elif restart:
        # carried state: step > 0 routes through the eq. 29 psi path, whose
        # t=0 mixing is W~ against (2 z - z_prev) of the carried iterates
        mix0 = jnp.asarray(
            w_tilde(w) @ (2.0 * np.asarray(state0.z)
                          - np.asarray(state0.z_prev)), dt
        )
    else:
        mix0 = jnp.asarray(w @ z0, dt)  # t=0: z^0 is consensus-shared
    hp = {"alpha": float(cfg.alpha), "lam": float(cfg.lam)}

    def seg_xs(lo, hi):
        xs = (ts[lo:hi], idx_j[lo:hi])
        if faulty:
            xs = (*xs, jnp.asarray(sent_mask[lo:hi]))
        return xs

    if ckpt_every is None and resume is None:
        with obs.span("solve.run"):
            carry_f, (zs, nnzs) = scan(carry0, seg_xs(0, steps), mix0, hp)
        with obs.span("solve.readout"):
            zs, nnzs = np.asarray(zs), np.asarray(nnzs)
    else:
        # chunked execution of the SAME cached scan: absolute iteration
        # numbers ride in the xs, so chunk boundaries are invisible to
        # the per-step math — resumed runs are bit-equal to uninterrupted
        start = 0
        carry = carry0
        zs_parts, nnz_parts = [], []
        if resume is not None:
            t_done, leaves = resume
            if not 0 < t_done <= steps:
                raise ValueError(
                    f"resume step {t_done} outside (0, {steps}]"
                )
            carry = _carry_from_leaves(carry0, leaves)
            zs_parts.append(np.asarray(leaves["['zs']"]))
            nnz_parts.append(np.asarray(leaves["['nnzs']"]))
            start = int(t_done)
        every = int(ckpt_every) if ckpt_every is not None else steps
        marks = sorted({*range(start + every, steps, every), steps})
        prev = start
        for mk in marks:
            if mk <= prev:
                continue  # resumed at (or past) this boundary already
            carry, (zs_c, nnz_c) = scan(carry, seg_xs(prev, mk), mix0, hp)
            zs_parts.append(np.asarray(zs_c))
            nnz_parts.append(np.asarray(nnz_c))
            prev = mk
            if ckpt_save is not None and mk % every == 0:
                ckpt_save(mk, {
                    "carry": carry,
                    "zs": np.concatenate(zs_parts),
                    "nnzs": np.concatenate(nnz_parts),
                })
        carry_f = carry
        zs = np.concatenate(zs_parts)
        nnzs = np.concatenate(nnz_parts)
    state_f, err, ok = carry_f[0], carry_f[-2], carry_f[-1]

    if verify and not bool(ok):
        raise ProtocolViolation(
            "relay schedule consumed a value before its arrival"
        )
    with obs.span("solve.readout"):
        z_trace = np.concatenate([np.asarray(z0)[None], zs])
    doubles, ints = _closed_form_costs(
        nnzs, tb.dist, tail, D, restart=restart, sent=sent_mask
    )
    return SparseRunResult(
        z_trace=z_trace,
        doubles_received=doubles,
        ints_received=ints,
        recon_max_err=float(err) if verify else float("nan"),
        state=state_f,
    )


def run_sparse_many(
    cfg: DSBAConfig,
    data,
    graph: Graph,
    w: np.ndarray,
    steps: int,
    indices: np.ndarray,
    alphas,
    z0: np.ndarray | None = None,
    *,
    verify: bool = False,
    use_pallas: str = "auto",
) -> list[SparseRunResult]:
    """Run B relay sweeps as ONE vmapped scan: per-run seeds and alphas.

    ``indices`` is (B, >= steps, N) — one sample stream per run — and
    ``alphas`` a length-B sequence of step sizes (``cfg.alpha`` is ignored;
    ``cfg.lam``/``cfg.method`` are shared). The compiled relay scan is the
    SAME cached executable family as ``run_sparse``'s (hp values are traced
    arguments), wrapped in ``jax.vmap`` over (carry, indices, alpha) and
    re-jitted once per batch size. The per-run message accounting is
    already hoisted out of the scan (closed form over the nnz log), so
    batching adds no accounting approximation — results are bit-identical
    to B sequential ``run_sparse`` calls (pinned in tests/test_solvers.py).

    The starting point ``z0`` is shared across runs (it is consensus
    state, not a sweep axis). Returns one SparseRunResult per run.
    """
    spec = cfg.spec
    n = data.n_nodes
    tail = spec.tail_dim
    D = data.d + tail
    dt = data.val.dtype
    if z0 is None:
        z0 = np.zeros((n, D), dtype=dt)
    indices = np.asarray(indices)
    B = len(alphas)
    if indices.ndim != 3 or indices.shape[0] != B or indices.shape[1] < steps:
        raise ValueError(
            f"indices must be (B, >= steps, N) = ({B}, >={steps}, {n}), "
            f"got {indices.shape}"
        )
    kernel_mode = _resolve_kernel_mode(use_pallas, dt)

    key, guards = _sparse_scan_key(cfg, data, graph, w, verify, kernel_mode)
    scan, tb = runner_cache.SPARSE.get_or_build(
        key, guards,
        lambda: _build_sparse_scan(
            cfg, data, graph, w, verify=verify, kernel_mode=kernel_mode
        ),
    )
    # The batched variant lives in the same cache under a derived key, so
    # it shares the LRU/stats machinery and is evicted with its parent.
    scan_b = runner_cache.SPARSE.get_or_build(
        ("batched", key), guards,
        lambda: jax.jit(jax.vmap(
            scan, in_axes=(0, (None, 0), None, {"alpha": 0, "lam": None})
        )),
    )

    carry0 = _relay_carry0(cfg, data, z0, tb, verify)
    carry0_b = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (B,) + x.shape), carry0
    )
    ts = jnp.arange(steps, dtype=jnp.int32)
    idx_j = jnp.asarray(indices[:, :steps], jnp.int32)
    mix0 = jnp.asarray(w @ z0, dt)  # t=0 mixing: z^0 is consensus-shared
    # alphas in the DATA dtype: batched arithmetic then promotes exactly
    # like the sequential path's weak-typed python-float scalar
    hp = {"alpha": jnp.asarray(np.asarray(alphas, dtype=dt)),
          "lam": float(cfg.lam)}

    (_, _, _, _, _, _, err, ok), (zs, nnzs) = scan_b(
        carry0_b, (ts, idx_j), mix0, hp
    )

    if verify and not np.all(np.asarray(ok)):
        raise ProtocolViolation(
            "relay schedule consumed a value before its arrival"
        )
    zs = np.asarray(zs)
    nnzs = np.asarray(nnzs)
    err = np.asarray(err)
    out = []
    for b in range(B):
        doubles, ints = _closed_form_costs(nnzs[b], tb.dist, tail, D)
        out.append(SparseRunResult(
            z_trace=np.concatenate([np.asarray(z0)[None], zs[b]]),
            doubles_received=doubles,
            ints_received=ints,
            recon_max_err=float(err[b]) if verify else float("nan"),
        ))
    return out


# ---------------------------------------------------------------------------
# Reference engine — the original per-observer loop (parity oracle). Slow:
# O(N^2 T) Python-level reconstruct calls and an O(N^2 T D) store.
# ---------------------------------------------------------------------------

def _run_reference(
    cfg, data, graph, w, steps, indices, z0=None, state0=None
) -> SparseRunResult:
    spec = cfg.spec
    alpha, lam = cfg.alpha, cfg.lam
    n = data.n_nodes
    q, k = data.q, data.k
    tail = spec.tail_dim
    d = data.d
    D = d + tail
    dt = data.val.dtype
    restart = state0 is not None
    if restart:
        z0 = np.asarray(state0.z)
    elif z0 is None:
        z0 = np.zeros((n, D), dtype=dt)

    dist = np.stack([graph.distances_from(u) for u in range(n)])  # (N, N)
    wt = w_tilde(w)
    neighbors = {u: sorted(graph.neighbors(u)) for u in range(n)}

    state = state0 if restart else init_state(cfg, data, jnp.asarray(z0))
    step_fn = jax.jit(make_step_fn(cfg, data, w))

    # --- per-observer reconstruction stores ---------------------------------
    # recon[u, l, s] = node u's reconstruction of z_l^s (NaN = not yet known)
    recon = np.full((n, n, steps + 2, D), np.nan, dtype=dt)
    recon[:, :, 0, :] = z0[None, :, :]
    s_next = np.full((n, n), 2, dtype=np.int64)  # next s to reconstruct

    # true trajectory + delta log (the scheduler enforces availability)
    z_hist = np.zeros((steps + 2, n, D), dtype=dt)
    z_hist[0] = z0
    dg_log = np.zeros((steps, n), dtype=dt)
    didx_log = np.zeros((steps, n, k), dtype=np.int64)
    dval_log = np.zeros((steps, n, k), dtype=dt)
    dtail_log = np.zeros((steps, n, tail), dtype=dt)

    doubles = np.zeros((steps, n), dtype=np.int64)
    ints = np.zeros((steps, n), dtype=np.int64)
    recon_err = 0.0

    def delta_vec(t_src, l):
        v = np.zeros(D, dtype=dt)
        np.add.at(v[:d], didx_log[t_src, l], dg_log[t_src, l] * dval_log[t_src, l])
        if tail:
            v[d:] += dtail_log[t_src, l]
        return v

    def reconstruct(u, l, s, t):
        """z_l^s from u's store via the update recursion (eq. 28 + lam)."""
        mix = np.zeros(D, dtype=dt)
        for m in neighbors[l] + [l]:
            zm1 = recon[u, m, s - 1]
            zm2 = recon[u, m, s - 2]
            assert not np.isnan(zm1).any(), ("recon needs", u, m, s - 1, "at", t)
            assert not np.isnan(zm2).any(), ("recon needs", u, m, s - 2, "at", t)
            mix += wt[l, m] * (2.0 * zm1 - zm2)
        dm1 = delta_vec(s - 1, l)
        dm2 = delta_vec(s - 2, l)
        corr = alpha * ((q - 1.0) / q * dm2 - dm1)
        if cfg.method == "dsba":
            return (mix + alpha * lam * recon[u, l, s - 1] + corr) / (
                1.0 + alpha * lam
            )
        # dsa
        return mix + corr - alpha * lam * (recon[u, l, s - 1] - recon[u, l, s - 2])

    for t in range(steps):
        # ---- message arrivals + reconstruction, per observer --------------
        if t >= 1:
            for u in range(n):
                # own history is exact and free (z^t was computed locally
                # at the end of the previous iteration)
                recon[u, u, : t + 1, :] = z_hist[: t + 1, u]
                # arrivals first: dense z^1 warm-up flood + today's deltas
                for l in range(n):
                    if l == u:
                        continue
                    xi = dist[u, l]
                    if t == xi:
                        recon[u, l, 1] = z_hist[1, l]
                        doubles[t, u] += D  # one-time dense z^1 flood
                        if restart:
                            doubles[t, u] += D  # z^0 resync flood
                    if t - xi >= 0:
                        nnz = int((dval_log[t - xi, l] != 0).sum())
                        doubles[t, u] += nnz + tail
                        ints[t, u] += nnz
                # reconstruct farthest-first (paper's V_j ordering): a node
                # at distance xi+1 must advance before its distance-xi
                # neighbor consumes its s-1 value this same iteration.
                order = sorted(
                    (l for l in range(n) if l != u),
                    key=lambda l: -dist[u, l],
                )
                for l in order:
                    xi = dist[u, l]
                    while s_next[u, l] <= t + 1 - xi:
                        s = int(s_next[u, l])
                        # availability: uses delta_l^{s-1}; assert schedule
                        assert (s - 1) + xi <= t, (u, l, s, t)
                        recon[u, l, s] = reconstruct(u, l, s, t)
                        s_next[u, l] = s + 1

        # ---- mixing rows from each node's OWN reconstruction store --------
        if t == 0 and restart and int(np.asarray(state0.step)) == 0:
            # churn-reanchored state (step counter reset to 0): the scan
            # re-runs the eq. 31 anchored update, mixing W @ z
            mix = w @ np.asarray(state0.z)
        elif t == 0 and restart:
            # carried state: the eq. 29 psi path mixes W~ against
            # (2 z - z_prev) of the carried iterates
            mix = wt @ (2.0 * np.asarray(state0.z)
                        - np.asarray(state0.z_prev))
        elif t == 0:
            mix = w @ z_hist[0]  # z^0 is consensus-shared; local compute
        else:
            mix = np.zeros((n, D), dtype=dt)
            for u in range(n):
                for m in neighbors[u] + [u]:
                    zm_t = recon[u, m, t]
                    zm_tm1 = recon[u, m, t - 1]
                    assert not np.isnan(zm_t).any(), (u, m, t)
                    assert not np.isnan(zm_tm1).any(), (u, m, t - 1)
                    mix[u] += wt[u, m] * (2.0 * zm_t - zm_tm1)

        # ---- advance all nodes with the shared local update ----------------
        i_t = jnp.asarray(indices[t], jnp.int32)
        state = step_fn(state, i_t, jnp.asarray(mix))
        z_hist[t + 1] = np.asarray(state.z)
        dg_log[t] = np.asarray(state.dg_prev)
        didx_log[t] = np.asarray(state.didx_prev)
        dval_log[t] = np.asarray(state.dval_prev)
        if tail:
            dtail_log[t] = np.asarray(state.dtail_prev)

        # ---- verify reconstructions against truth --------------------------
        if t >= 1:
            for u in range(n):
                for l in range(n):
                    if l == u:
                        continue
                    hi = int(s_next[u, l])
                    diff = recon[u, l, 1:hi] - z_hist[1:hi, l]
                    diff = diff[~np.isnan(diff)]
                    if diff.size:
                        recon_err = max(recon_err, float(np.abs(diff).max()))

    return SparseRunResult(
        z_trace=z_hist[: steps + 1],
        doubles_received=np.cumsum(doubles, axis=0),
        ints_received=np.cumsum(ints, axis=0),
        recon_max_err=recon_err,
        state=state,
    )


# ---------------------------------------------------------------------------
# Closed-form communication cost models (validated against the simulator) —
# used by benchmarks for long horizons without running the full protocol.
# ---------------------------------------------------------------------------

def sparse_doubles_per_iter(n_nodes: int, k: int, tail_dim: int) -> int:
    """Steady-state DOUBLEs received per node per iteration under DSBA-s."""
    return (n_nodes - 1) * (k + tail_dim)


def dense_doubles_per_iter(graph: Graph, d_total: int) -> np.ndarray:
    """Per-node DOUBLEs received per iteration with dense neighbor exchange."""
    return graph.degrees * d_total
