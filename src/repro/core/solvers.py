"""One solver API: ``Problem`` + ``SolverSpec`` registry + ``solve()``.

The paper recasts decentralized learning as monotone-operator root finding;
this module makes that the *interface*: a ``Problem`` bundles the operator
family, the node-local data, the communication graph, the mixing matrix and
the ``z*`` oracle, while a ``SolverSpec`` registry (mirroring the
``KernelSpec`` registry in ``kernels/ops.py``) makes the *method*
(``dsba``/``dsa`` per Algorithm 1 and Remark 5.1, ``extra``/``dlm``/``ssda``
per the deterministic baselines of Table 1) and the *communication backend*
(``dense`` neighbor exchange vs. the paper's sparse delta relay of Section
5.1) two orthogonal axes of a single call::

    problem = make_problem("ridge", data, graph)
    problem.solve_star()                      # cache the centralized root
    res = solve(problem, method="dsba", comm="sparse", steps=4000)

``solve`` is the per-run entrypoint; ``solve_many`` runs a whole
hyperparameter/seed grid as one vmapped computation. Both are backed by a
keyed cache of compiled runners (``core.runner_cache``): the jitted chunked
scan is compiled once per (method, comm, problem shape, static-hp
structure) with hyperparameter *values* passed as traced arguments, so
sweep-shaped experiments (bench_table1's lam grid, seed replications) pay
XLA compilation once. ``core.dsba.run`` and
``core.baselines.run_extra/run_dlm/run_ssda`` are thin deprecated shims
delegating here, pinned trace-identical by ``tests/test_solvers.py``.

Every run returns the same ``SolveResult`` schema, including cumulative
communicated DOUBLEs/ints per node: measured by the relay's closed-form
accounting when ``comm="sparse"``, and from the ``deg(n) * D`` dense-exchange
model otherwise — so sparse-vs-dense communication cost is comparable in one
result type. Authoring contract and backend-resolution rules are documented
in docs/solvers.md.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import time
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import reference, runner_cache
from repro.core.comm import (
    DenseComm,
    FaultyDenseComm,
    FaultyShardedComm,
    ShardedComm,
)
from repro.core.dsba import (
    DSBAConfig,
    draw_indices,
    init_state as _dsba_init_state,
    make_step_fn as _dsba_make_step_fn,
)
from repro.core.mixing import Graph, laplacian_mixing, spectral_gap, w_tilde
from repro.core.operators import (
    FAMILIES,
    MINIMIZATION_FAMILIES,
    OperatorSpec,
)
from repro.core.runner_cache import (
    clear as clear_runner_caches,  # noqa: F401  (public re-export)
    stats as runner_cache_stats,  # noqa: F401  (public re-export)
)
from repro.core import sparse_comm as _sparse_comm
from repro.core.sparse_comm import dense_doubles_per_iter

COMM_BACKENDS = ("dense", "sparse", "sharded")


# ---------------------------------------------------------------------------
# Problem: everything a solver needs, bundled once
# ---------------------------------------------------------------------------


def graph_from_mixing(w: np.ndarray, atol: float = 1e-12) -> Graph:
    """Recover the communication ``Graph`` from a mixing matrix's support.

    Section 4's sparsity condition makes W and the graph carry the same
    information (``w[m,l] != 0`` iff ``(m,l)`` is an edge or ``m == l``), so
    legacy callers that only pass W still get full communication accounting.
    """
    w = np.asarray(w)
    n = w.shape[0]
    edges = tuple(
        (i, j)
        for i in range(n)
        for j in range(i + 1, n)
        if abs(w[i, j]) > atol
    )
    return Graph(n, edges)


@dataclasses.dataclass
class Problem:
    """A decentralized root-finding problem instance.

    Bundles the operator family (``spec``), the per-node data (padded-CSR
    ``SparseDataset``), the communication ``graph``, the mixing matrix ``w``
    (defaults to the paper's Laplacian weights on ``graph``), the l2
    regularizer ``lam`` (part of the *problem*, not the solver), and an
    optional cached centralized root ``z_star``.

    ``lam`` may be a scalar or an (N,) per-node array (personalization);
    per-node lam runs on ``comm="dense"`` with methods advertising
    ``supports_per_node_lam`` — anything else is a ``CapabilityError``.

    ``schedule`` makes the network axis time-varying: a sequence of
    ``(start_iter, Graph-or-W)`` segments. ``solve()`` runs each segment
    through its own cached runner (edge colorings / relay waves re-derived
    per segment) carrying the solver state across boundaries, and records
    each segment's spectral gap in ``SolveResult.extras["schedule"]``. A
    segment given as a ``Graph`` gets the paper's Laplacian mixing; one
    given as a W matrix recovers its graph from the support. If no segment
    starts at 0, the problem's own (graph, w) opens the schedule.
    """

    spec: OperatorSpec
    data: Any  # repro.data.synthetic.SparseDataset (duck-typed)
    graph: Graph
    w: np.ndarray | None = None
    lam: float | np.ndarray = 0.0
    z_star: np.ndarray | None = None
    schedule: Any = None  # normalized to ((start, Graph, W), ...) or None

    def __post_init__(self):
        """Default ``w`` to Laplacian mixing and sanity-check shapes."""
        if self.w is None:
            self.w = laplacian_mixing(self.graph)
        self.w = np.asarray(self.w)
        if self.w.shape != (self.graph.n, self.graph.n):
            raise ValueError(
                f"mixing matrix {self.w.shape} != graph size {self.graph.n}"
            )
        if self.data.n_nodes != self.graph.n:
            raise ValueError(
                f"data has {self.data.n_nodes} nodes, graph {self.graph.n}"
            )
        if np.ndim(self.lam) > 0:
            self.lam = np.asarray(self.lam, dtype=np.float64)
            if self.lam.shape != (self.graph.n,):
                raise ValueError(
                    f"per-node lam must be ({self.graph.n},), "
                    f"got {self.lam.shape}"
                )
        if self.schedule is not None:
            self.schedule = _normalize_schedule(
                self.schedule, self.graph, self.w, self.data.n_nodes
            )

    @property
    def dim(self) -> int:
        """Total iterate dimension D = d + tail_dim."""
        return self.data.d + self.spec.tail_dim

    def solve_star(self, **kwargs) -> np.ndarray:
        """Compute (once) and cache the centralized root ``z*``.

        Delegates to ``reference.solve_root``; extra kwargs (``iters``,
        ``tol``) pass through. Idempotent: repeated calls return the cache.
        Per-node ``lam`` has no single centralized root — use
        ``personalized_root`` for those problems.
        """
        if self.z_star is None:
            if np.ndim(self.lam) > 0:
                raise ValueError(
                    "per-node lam has no centralized root; use "
                    "core.solvers.personalized_root for the coupled system"
                )
            self.z_star = reference.solve_root(
                self.spec, self.data, self.lam, **kwargs
            )
        return self.z_star


def _normalize_schedule(schedule, graph0: Graph, w0, n: int):
    """Normalize ``(start, Graph-or-W)`` entries to ``(start, Graph, W)``.

    Starts must be unique non-negative ints; segments are sorted and, when
    none starts at 0, the problem's own (graph, w) opens the schedule.
    """
    segs = []
    for start, g in schedule:
        start = int(start)
        if start < 0:
            raise ValueError(f"schedule segment start {start} < 0")
        if isinstance(g, Graph):
            seg_graph, seg_w = g, laplacian_mixing(g)
        else:
            seg_w = np.asarray(g)
            if seg_w.shape != (n, n):
                raise ValueError(
                    f"schedule segment W {seg_w.shape} != ({n}, {n})"
                )
            seg_graph = graph_from_mixing(seg_w)
        if seg_graph.n != n:
            raise ValueError(
                f"schedule segment graph has {seg_graph.n} nodes, "
                f"problem has {n}"
            )
        segs.append((start, seg_graph, seg_w))
    segs.sort(key=lambda s: s[0])
    starts = [s[0] for s in segs]
    if len(set(starts)) != len(starts):
        raise ValueError(f"duplicate schedule segment starts {starts}")
    if not segs or segs[0][0] != 0:
        segs.insert(0, (0, graph0, np.asarray(w0)))
    return tuple(segs)


def make_problem(
    task: str,
    data,
    graph: Graph,
    w: np.ndarray | None = None,
    lam: float | None = None,
    gamma: float = 1.0,
) -> Problem:
    """Build a ``Problem`` from a task name with the paper's conventions.

    task: ``"ridge" | "logistic" | "auc" | "bilinear"`` (AUC reads the
    positive-class ratio from the data; ``bilinear`` is the saddle-point
    minimax family with dual strong-concavity ``gamma``). ``lam`` defaults
    to the paper's 1/(10 Q); for ``bilinear`` it regularizes both blocks
    (+lam/2 on the primal, -lam/2 on the dual) so ``solve_star()`` is the
    regularized saddle point.
    """
    if task == "auc":
        spec = OperatorSpec("auc", p=data.positive_ratio())
    elif task == "bilinear":
        spec = OperatorSpec("bilinear", gamma=gamma)
    elif task in ("ridge", "logistic"):
        spec = OperatorSpec(task)
    else:
        raise ValueError(f"unknown task {task!r}; one of {FAMILIES}")
    if lam is None:
        lam = 1.0 / (10.0 * data.total)
    return Problem(spec=spec, data=data, graph=graph, w=w, lam=lam)


# ---------------------------------------------------------------------------
# Fault plans (churn / link faults / stragglers) applied mid-run by solve().
# The schemas live in ``repro.ft.faults`` (plain-numpy, import-light);
# ChurnEvent/ChurnPlan are re-exported here for the PR 8 call sites.
# ---------------------------------------------------------------------------

from repro.ft.faults import (  # noqa: E402  (grouped with the fault layer)
    ChurnEvent,
    ChurnPlan,
    FaultPlan,
    LinkFault,
    StragglerSpec,
    as_fault_plan,
    delivered_in_messages,
    fault_message_totals,
    link_delivered_mask,
    source_sent_mask,
    straggler_delivered_mask,
)
from repro.ckpt.checkpoint import (  # noqa: E402
    CheckpointManager,
    CheckpointSpec,
    load_checkpoint,
    restore_checkpoint,
)


# ---------------------------------------------------------------------------
# SolverSpec registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SolverSpec:
    """One solver's contract with ``solve()`` (see docs/solvers.md).

    ``init``/``step``/``z_of`` are *factories* over ``(problem, hp)`` so each
    entry can bake data and mixing matrices into device arrays exactly once
    per compiled runner. Hyperparameter VALUES are not baked: the functions
    a factory returns receive the runtime hyperparameters as a final ``hp``
    argument — a dict of scalars that the compiled-runner cache passes as
    *traced* jit arguments, so a sweep over values reuses one executable:

    - ``init(problem, hp, z0) -> state``: initial state pytree from a (N, D)
      starting point (scan-compatible: every leaf is a jax array).
    - ``step(problem, hp, comm) -> fn(state, i_t, hp) -> state``: the
      per-iteration transition, safe to call inside jit/lax.scan. ``i_t``
      is the (N,) sample draw of this iteration; deterministic solvers
      ignore it. The inner ``hp`` dict carries every non-static
      hyperparameter plus ``"lam"`` (unless ``bake_lam``). ``comm`` is the
      communication backend (``core.comm``): ALL neighbor exchange must go
      through ``comm.matvec(M, dtype)`` and all reads of node-indexed
      constants through ``comm.local`` — never an inline ``W @ X`` — so
      the one step definition runs under dense and sharded execution.
    - ``z_of(problem, hp, comm) -> fn(state, hp) -> (N, D)``: iterate
      read-out (SSDA's primal read-out is a real computation, hence a
      factory too; it receives ``comm`` for the same reason the step does).
    - ``defaults``: the solver's hyperparameters with default values; the
      keys are also the *schema* — ``solve()`` rejects unknown overrides.
    - ``static_hp``: names of hyperparameters that are *structural* (Python
      loop counts, shapes) and must be baked at factory time. They join the
      runner cache key; changing them recompiles. At factory time the ``hp``
      mapping resolves static names only — reading a runtime-traced name
      there raises, so a value can never be silently baked stale.
    - ``bake_lam``: bake ``problem.lam`` at factory time instead of tracing
      it (SSDA's conjugate-gradient map is factorized around ``lam``).
    - ``sparse_run``: optional sparse-communication backend with signature
      ``(problem, hp, steps, indices, z0, options) -> SparseRunResult``.
      ``None`` means the method has no sparse protocol (the deterministic
      baselines exchange dense vectors by construction).
    - ``sparse_run_many``: optional batched sparse backend with signature
      ``(problem, merged, steps, idx_b, z0, options) ->
      list[SparseRunResult] | None`` (``merged``: one resolved hp dict per
      run; ``idx_b``: (B, >= steps, N) sample streams). Returning ``None``
      declines the batch (e.g. ``engine="reference"``) and ``solve_many``
      falls back to sequential warm ``solve()`` calls.
    - ``problem_families``: the operator families (``OperatorSpec.kind``
      values) the method supports; ``solve()`` raises ``CapabilityError``
      for anything else (e.g. descent-only methods on saddle families).
    - ``supports_sharded``: whether the step is sharded-backend safe (all
      registered methods are today; the flag exists so a future
      non-``comm.matvec`` method degrades to a typed error, not a crash).
    - ``comm_rounds``: optional accounting hook mapping (resolved hp,
      cumulative iteration counts) -> cumulative *dense-exchange rounds*
      per node at those counts. ``None`` means one round per iteration
      (every pre-PR-7 method). Mudag's K inner gossip rounds (2K/iter)
      and sliding's skipped rounds (2*ceil(iters/period)) report through
      this hook, so ``SolveResult.doubles_received`` stays honest.
    - ``supports_schedule``: the method's fixed point is preserved under a
      mid-run change of the mixing matrix, so ``solve()`` may carry its
      state across the segments of a ``Problem.schedule``
      (restart-on-new-W — docs/algorithm.md). Methods whose *state*
      encodes W (EXTRA/DLM's duals, SSDA's dual momentum) must leave this
      False: carrying their state over a W change targets a stale fixed
      point, and that is a ``CapabilityError``, not a silent restart.
    - ``supports_churn``: the state pytree keeps all per-node quantities
      on leading-N leaves AND the fixed point survives membership change,
      so ``ft.elastic.ElasticGossip`` shrink/grow remapping is sound.
    - ``reanchor``: optional ``(state) -> state`` applied after an
      elastic churn remap. Difference-form methods (DSBA/DSA) conserve a
      telescoped mean-drift invariant anchored by their t=0 step; a
      membership change alters the node mean, so the anchor must re-run
      on the new membership or the run converges to the OLD system's
      root (docs/algorithm.md). A W-only switch preserves the invariant
      (1^T W = 1^T for any doubly stochastic W) and does NOT reanchor.
    - ``supports_per_node_lam``: the step accepts ``lam`` as an (N,)
      array (personalized regularization) — dense backend only.
    - ``supports_link_faults``: the step routes ALL neighbor exchange
      through ``comm.matvec``, so a per-step delivery mask (masked mixing
      rows with row-renormalization) injects cleanly. True for every
      registered method — the flag exists so a future method with
      out-of-band exchange degrades to a typed error.
    - ``supports_stragglers``: the step's matvec call sites are each
      invoked a FIXED number of times per iteration at the top level of
      the traced step, so last-delivered-value buffers can be threaded
      through the scan carry. False for methods that apply ``matvec``
      inside an inner traced loop (mudag's FastMix — the buffer write
      would escape the loop trace) or gate it on a traced round predicate
      (sliding — off-round iterations exchange nothing to delay).
    """

    name: str
    init: Callable[[Problem, Mapping[str, float], jax.Array], Any]
    step: Callable[[Problem, Mapping[str, float], Any], Callable]
    z_of: Callable[[Problem, Mapping[str, float], Any], Callable]
    defaults: Mapping[str, float]
    sparse_run: Callable | None = None
    sparse_run_many: Callable | None = None
    static_hp: tuple[str, ...] = ()
    bake_lam: bool = False
    problem_families: tuple[str, ...] = ("ridge", "logistic", "auc")
    supports_sharded: bool = True
    comm_rounds: Callable[[Mapping[str, float], np.ndarray], np.ndarray] | None = None
    supports_schedule: bool = False
    supports_churn: bool = False
    supports_per_node_lam: bool = False
    reanchor: Callable[[Any], Any] | None = None
    supports_link_faults: bool = True
    supports_stragglers: bool = True

    def supports_sparse_comm(self) -> bool:
        """Whether this method has a sparse-communication backend."""
        return self.sparse_run is not None

    def capabilities(self) -> "SolverCapabilities":
        """The typed capability record ``available_solvers()`` exposes."""
        return SolverCapabilities(
            supports_sparse_comm=self.sparse_run is not None,
            supports_sharded=self.supports_sharded,
            problem_families=tuple(self.problem_families),
            supports_schedule=self.supports_schedule,
            supports_churn=self.supports_churn,
            supports_per_node_lam=self.supports_per_node_lam,
            supports_link_faults=self.supports_link_faults,
            supports_stragglers=self.supports_stragglers,
        )


@dataclasses.dataclass(frozen=True)
class SolverCapabilities:
    """What one registered solver supports, as data (see docs/solvers.md).

    Returned per method by ``available_solvers()``. ``solve()`` enforces
    exactly this record: a (method, comm backend, operator family)
    combination outside it raises ``CapabilityError`` — never a silent
    fallback to a backend the caller did not ask for. The same rule
    covers the dynamic-network axes: a multi-segment ``schedule``, a
    churn ``fault_plan``, or a per-node ``lam`` on a method that does
    not advertise the capability raises before any factory runs — never
    a silent static fallback.
    """

    supports_sparse_comm: bool
    supports_sharded: bool
    problem_families: tuple[str, ...]
    supports_schedule: bool = False
    supports_churn: bool = False
    supports_per_node_lam: bool = False
    supports_link_faults: bool = True
    supports_stragglers: bool = True

    def comm_backends(self) -> tuple[str, ...]:
        """The comm backends this solver accepts (dense is universal)."""
        out = ["dense"]
        if self.supports_sparse_comm:
            out.append("sparse")
        if self.supports_sharded:
            out.append("sharded")
        return tuple(out)

    def supports(self, comm: str, family: str) -> bool:
        """Whether (comm backend, operator family) is inside this record."""
        return comm in self.comm_backends() and family in self.problem_families


class CapabilityError(ValueError):
    """A (method, comm backend, operator family) combination is unsupported.

    Subclasses ``ValueError`` so callers catching the registry's value
    errors keep working; carries the offending combination as attributes
    for programmatic handling.
    """

    def __init__(self, method: str, comm: str, family: str, reason: str):
        super().__init__(
            f"unsupported combination (method={method!r}, comm={comm!r}, "
            f"operator family={family!r}): {reason}"
        )
        self.method = method
        self.comm = comm
        self.family = family


def _check_capability(
    spec: "SolverSpec",
    comm: str,
    family: str,
    *,
    schedule: bool = False,
    churn: bool = False,
    per_node_lam: bool = False,
    link_faults: bool = False,
    stragglers: bool = False,
) -> None:
    """Raise ``CapabilityError`` unless (spec, comm, family) is supported.

    The keyword flags add the dynamic-network and fault-injection axes: a
    multi-segment graph ``schedule``, a ``churn`` plan, a ``per_node_lam``
    array, ``link_faults`` (per-edge drops) and ``stragglers`` (delayed
    delivery). Runs before any solver factory, so an unsupported
    combination can never silently fall back to a static run.
    """
    caps = spec.capabilities()
    if family not in caps.problem_families:
        raise CapabilityError(
            spec.name, comm, family,
            f"method {spec.name!r} supports operator families "
            f"{list(caps.problem_families)}",
        )
    if comm == "sparse" and not caps.supports_sparse_comm:
        raise CapabilityError(
            spec.name, comm, family,
            f"method {spec.name!r} has no sparse-communication backend",
        )
    if comm == "sharded" and not caps.supports_sharded:
        raise CapabilityError(
            spec.name, comm, family,
            f"method {spec.name!r} does not run under the sharded backend",
        )
    if schedule and not caps.supports_schedule:
        raise CapabilityError(
            spec.name, comm, family,
            f"method {spec.name!r} does not support graph schedules: its "
            "state would carry a stale fixed point across a W change",
        )
    if churn and not caps.supports_churn:
        raise CapabilityError(
            spec.name, comm, family,
            f"method {spec.name!r} does not support node churn "
            "(fault_plan): its state cannot be elastically remapped",
        )
    if link_faults and not caps.supports_link_faults:
        raise CapabilityError(
            spec.name, comm, family,
            f"method {spec.name!r} does not support link faults: its "
            "neighbor exchange does not route through comm.matvec",
        )
    if stragglers and not caps.supports_stragglers:
        raise CapabilityError(
            spec.name, comm, family,
            f"method {spec.name!r} does not support stragglers: its "
            "matvec call sites are not fixed-count per iteration "
            "(inner gossip loop or traced round gating)",
        )
    if stragglers and comm != "dense":
        raise CapabilityError(
            spec.name, comm, family,
            "stragglers (delayed delivery buffers) run on comm='dense' "
            "only; link faults cover the sharded and sparse backends",
        )
    if per_node_lam and not caps.supports_per_node_lam:
        raise CapabilityError(
            spec.name, comm, family,
            f"method {spec.name!r} does not support per-node lam "
            "(personalization); see available_solvers()",
        )
    if per_node_lam and comm != "dense":
        raise CapabilityError(
            spec.name, comm, family,
            "per-node lam (personalization) runs on comm='dense' only",
        )


#: per-backend comm_options schema enforced by ``_validate_options``
_COMM_OPTION_KEYS = {
    "dense": ("fault_plan",),
    "sparse": ("engine", "verify", "use_pallas", "fault_plan"),
    "sharded": ("mesh", "fault_plan"),
}


def _validate_options(comm: str, comm_options: Mapping | None) -> dict:
    """The one comm_options gate shared by every backend resolution path.

    Returns a mutable copy; unknown keys fail loudly instead of being
    silently dropped (dense accepts none — passing sparse-engine options
    to a dense run is a bug, not a no-op).
    """
    opts = dict(comm_options or {})
    allowed = _COMM_OPTION_KEYS[comm]
    unknown = sorted(set(opts) - set(allowed))
    if unknown:
        raise ValueError(
            f"unknown {comm} comm_options {unknown}; "
            f"accepts {sorted(allowed)}"
        )
    return opts


_REGISTRY: dict[str, SolverSpec] = {}


def register_solver(spec: SolverSpec) -> SolverSpec:
    """Add a ``SolverSpec`` to the registry (name must be unused)."""
    if spec.name in _REGISTRY:
        raise ValueError(f"solver {spec.name!r} already registered")
    _REGISTRY[spec.name] = spec
    return spec


def get_solver(name: str) -> SolverSpec:
    """Look up a registered solver by name."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown method {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def available_solvers() -> dict[str, SolverCapabilities]:
    """{name: SolverCapabilities} for every registered solver.

    The values are typed capability records (sparse/sharded backend
    support plus the supported operator families) — exactly what
    ``solve()`` enforces via ``CapabilityError``.
    """
    return {
        name: spec.capabilities() for name, spec in sorted(_REGISTRY.items())
    }


# ---------------------------------------------------------------------------
# Compiled-runner cache: one jitted chunked scan per (method, problem shape).
# Hyperparameter values are traced arguments — a sweep compiles once.
# ---------------------------------------------------------------------------


class TracedHPError(KeyError):
    """A factory read a runtime-traced hyperparameter at bake time."""

    def __str__(self):
        """The message verbatim (KeyError would repr-quote it)."""
        return self.args[0]


class _FactoryHP(Mapping):
    """Factory-time view of the hyperparameters: the *static* names only.

    Static names resolve to their values (they are part of the cache key);
    as a Mapping this contains nothing else, so ``in`` / ``.get`` /
    iteration answer honestly. Subscripting a runtime-traced name raises
    ``TracedHPError`` (a KeyError) with a pointer to the ``hp`` argument —
    a factory can never silently bake a value that later sweep calls would
    then reuse stale.
    """

    def __init__(self, values: Mapping[str, float], static: tuple[str, ...]):
        self._values = dict(values)
        self._static = frozenset(static) & set(self._values)

    def __getitem__(self, name: str):
        if name in self._static:
            return self._values[name]
        if name in self._values:
            raise TracedHPError(
                f"hyperparameter {name!r} is runtime-traced; read it from "
                "the hp argument inside the step/z_of function, or declare "
                "it in SolverSpec.static_hp"
            )
        raise KeyError(name)

    def __iter__(self):
        return iter(k for k in self._values if k in self._static)

    def __len__(self):
        return len(self._static)


def _dynamic_hp(spec: SolverSpec, problem: Problem, hp: Mapping) -> dict:
    """The runtime-traced hp dict: non-static names + lam (unless baked).

    Values are normalized to Python floats so jit sees one weak-typed
    scalar signature per runner — different values never retrace.
    """
    dyn = {
        k: float(v) for k, v in hp.items() if k not in spec.static_hp
    }
    if not spec.bake_lam:
        # per-node lam stays an (N,) array in the data dtype (one traced
        # signature); scalar lam stays a weak-typed python float
        dyn["lam"] = (
            float(problem.lam)
            if np.ndim(problem.lam) == 0
            else np.asarray(problem.lam, dtype=problem.data.val.dtype)
        )
    return dyn


def _runner_key(spec: SolverSpec, problem: Problem, hp: Mapping):
    """(key, guards) for one (method, problem shape, static-hp structure).

    The dataset enters by identity (guarded by a strong reference in the
    entry); the mixing matrix by content fingerprint, so problems rebuilt
    per sweep point (same data/graph, fresh equal W, different lam) share
    one compiled runner. Hyperparameter *values* never enter the key —
    only the static structure does.
    """
    key = (
        spec.name,
        runner_cache.problem_fingerprint(
            problem.data, problem.spec, problem.graph, problem.w
        ),
        tuple((k, float(hp[k])) for k in spec.static_hp),
        float(problem.lam) if spec.bake_lam else None,
    )
    return key, (problem.data,)


def _record_scan(run_chunk, z_fn, metrics):
    """The untraced ``record`` body shared by the dense and sharded runners.

    ``(state, idx_blocks (R, E, N), hp, ref, keep_snapshots) -> (state,
    dist2 (R,), consensus (R,), zs (R, N, D))``: a scan over R record
    points, each running one E-iteration chunk and then reducing its
    iterates on the device with ``_Recorder.push``'s definitions,
    ``consensus = mean_n ||z_n - zbar||^2`` and ``dist2 = mean_n ||z_n -
    z*||^2`` (``metrics``: ``_point_metrics`` over the runner's node
    axis). ``ref`` is None (``dist2`` None) or the root as a (hi, lo) pair
    in the reduction dtype, hi + lo = z*, so a float64 root keeps its
    precision against float32 iterates. ``zs`` is None unless
    ``keep_snapshots``.
    """

    def record(state, idx_blocks, hp_dyn, ref, keep_snapshots):
        def point(st, idx_block):
            st = run_chunk(st, idx_block, hp_dyn)
            z = z_fn(st, hp_dyn)
            dist2, cons = metrics(z, ref)
            return st, (dist2, cons, z if keep_snapshots else None)

        state, (dist2, cons, zs) = jax.lax.scan(point, state, idx_blocks)
        return state, dist2, cons, zs

    return record


def _point_metrics(z, ref, node_sum, n: int):
    """(dist2 or None, consensus) of one record point's iterates, traced;
    ``node_sum`` sums over the node axis (a ``psum`` over "node" inside
    ``shard_map``), ``n`` is the number of nodes."""
    z = z.astype(_reduce_dtype(z.dtype))
    zbar = node_sum(z) / n
    cons = node_sum(jnp.sum((z - zbar) ** 2, -1)) / n
    if ref is None:
        return None, cons
    return node_sum(jnp.sum(((z - ref[0]) - ref[1]) ** 2, -1)) / n, cons


def _dense_metrics(z, ref):
    """``_point_metrics`` of (N, D) iterates on one device."""
    return _point_metrics(z, ref, lambda x: jnp.sum(x, 0), z.shape[0])


def _reduce_dtype(dtype):
    """The device recorder's dtype: the iterates' own, at least float32
    (a TPU has no float64 arithmetic; float64 iterates reduce in float64)."""
    return jnp.promote_types(dtype, jnp.float32)


def _record_ref(z_star, dtype):
    """The root as the (hi, lo) pair ``_record_scan`` takes, or None."""
    if z_star is None:
        return None
    z_star = np.asarray(z_star)
    hi = z_star.astype(_reduce_dtype(dtype))
    return jnp.asarray(hi), jnp.asarray((z_star - hi).astype(hi.dtype))


@dataclasses.dataclass
class _DenseRunner:
    """One compiled dense-backend runner: chunked scan + iterate read-out.

    ``chunk``/``z_read`` are the jitted entrypoints; ``record`` runs a
    whole segment of record points in one program (``_record_scan``);
    ``run_chunk``/``z_fn`` are the untraced callables kept for
    ``solve_many`` to vmap (the batched variants compile lazily into
    ``batched``, keyed by vmap signature).
    """

    init: Callable  # jitted (z0) -> state
    run_chunk: Callable  # (state, idx_block, hp) -> state, untraced
    z_fn: Callable  # (state, hp) -> (N, D), untraced
    chunk: Callable  # jitted run_chunk (donated carry off-CPU)
    z_read: Callable  # jitted z_fn
    record: Callable  # jitted _record_scan (donated carry off-CPU)
    donates: bool  # whether chunk and record donate their carry argument
    batched: dict = dataclasses.field(default_factory=dict)


def _get_dense_runner(spec: SolverSpec, problem: Problem, hp: Mapping):
    """Fetch (or compile) the dense runner for this (spec, problem, hp)."""
    key, guards = _runner_key(spec, problem, hp)

    def build() -> _DenseRunner:
        comm = DenseComm(problem.graph)
        fhp = _FactoryHP(hp, spec.static_hp)
        step_fn = spec.step(problem, fhp, comm)
        z_fn = spec.z_of(problem, fhp, comm)

        def run_chunk(state, idx_block, hp_dyn):
            runner_cache.DENSE.note_trace()  # trace-time only
            st, _ = jax.lax.scan(
                lambda s, i: (step_fn(s, i, hp_dyn), None), state, idx_block
            )
            return st

        def read(state, hp_dyn):
            runner_cache.DENSE.note_trace()
            return z_fn(state, hp_dyn)

        record = _record_scan(run_chunk, z_fn, _dense_metrics)
        # donating the scan carry lets XLA reuse the state buffers in
        # place; CPU does not implement donation (it would only warn)
        donates = jax.default_backend() != "cpu"
        donate = (0,) if donates else ()
        return _DenseRunner(
            init=jax.jit(lambda z0: spec.init(problem, fhp, z0)),
            run_chunk=run_chunk,
            z_fn=z_fn,
            chunk=jax.jit(run_chunk, donate_argnums=donate),
            z_read=jax.jit(read),
            record=jax.jit(record, static_argnames=("keep_snapshots",),
                           donate_argnums=donate),
            donates=donates,
        )

    return runner_cache.DENSE.get_or_build(key, guards, build)


@dataclasses.dataclass
class _ShardedRunner:
    """One compiled sharded-backend runner: shard_mapped scan + read-out.

    ``chunk``/``z_read``/``record`` are jitted ``shard_map`` wrappers over
    the same programs the dense runner compiles — the solver step itself
    is shared; only the comm primitive differs (and ``record`` reduces
    its metrics with ``psum``). ``measured`` caches the HLO-derived
    per-iteration collective traffic of the chunk program, keyed by chunk
    length (each distinct length is its own compiled program).
    """

    init: Callable  # jitted (z0) -> state (global (N, ...) leaves)
    chunk: Callable  # jitted shard_map'd (state, idx_block, hp) -> state
    z_read: Callable  # jitted shard_map'd (state, hp) -> (N, D)
    record: Callable  # jitted shard_map'd _record_scan, psum reductions
    mesh: Any
    measured: dict = dataclasses.field(default_factory=dict)

    def collective_costs(self, state, idx_block, hp_dyn) -> dict:
        """Per-iteration collective bytes/counts of this chunk's program.

        Lowers and compiles the chunk AOT once per chunk length and parses
        the optimized HLO (``launch.hlo_analysis``). The duplicate compile
        is absorbed by jax's persistent compilation cache
        (``launch.compile_cache``), enabled on ``import repro.core``.
        """
        from repro.launch.hlo_analysis import compiled_collective_costs

        length = int(idx_block.shape[0])
        if length not in self.measured:
            compiled = self.chunk.lower(state, idx_block, hp_dyn).compile()
            self.measured[length] = compiled_collective_costs(
                compiled, iterations=length
            )
        return self.measured[length]


def _node_partition_specs(state_proto, n: int):
    """Partition specs for a state pytree: leading-N leaves shard on "node".

    Every registered solver keeps its per-node state with a leading N axis
    (docs/solvers.md authoring contract); scalars (step counters) are
    replicated. A leaf that is neither is ambiguous — fail loudly rather
    than silently replicate what should be distributed.
    """

    def spec_of(leaf):
        if leaf.ndim >= 1 and leaf.shape[0] == n:
            return P("node", *([None] * (leaf.ndim - 1)))
        if leaf.ndim == 0:
            return P()
        raise ValueError(
            f"state leaf with shape {leaf.shape} has no leading node axis "
            f"(N = {n}) and is not a scalar; the sharded backend cannot "
            "place it (see docs/solvers.md)"
        )

    return jax.tree_util.tree_map(spec_of, state_proto)


def _get_sharded_runner(
    spec: SolverSpec, problem: Problem, hp: Mapping, mesh
):
    """Fetch (or compile) the shard_map runner for (spec, problem, hp, mesh)."""
    base_key, guards = _runner_key(spec, problem, hp)
    key = base_key + (runner_cache.mesh_fingerprint(mesh),)

    def build() -> _ShardedRunner:
        comm = ShardedComm(problem.graph, mesh)
        fhp = _FactoryHP(hp, spec.static_hp)
        step_fn = spec.step(problem, fhp, comm)
        z_fn = spec.z_of(problem, fhp, comm)
        n, D = problem.graph.n, problem.dim
        dt = problem.data.val.dtype

        state_proto = jax.eval_shape(
            lambda z: spec.init(problem, fhp, z),
            jax.ShapeDtypeStruct((n, D), dt),
        )
        state_specs = _node_partition_specs(state_proto, n)
        hp_specs = {k: P() for k in _dynamic_hp(spec, problem, hp)}

        def run_chunk(state, idx_block, hp_dyn):
            runner_cache.SHARDED.note_trace()  # trace-time only
            st, _ = jax.lax.scan(
                lambda s, i: (step_fn(s, i, hp_dyn), None), state, idx_block
            )
            return st

        def read(state, hp_dyn):
            runner_cache.SHARDED.note_trace()
            return z_fn(state, hp_dyn)

        chunk = jax.jit(
            jax.shard_map(
                run_chunk, mesh=mesh,
                in_specs=(state_specs, P(None, "node"), hp_specs),
                out_specs=state_specs,
            )
        )
        z_read = jax.jit(
            jax.shard_map(
                read, mesh=mesh,
                in_specs=(state_specs, hp_specs),
                out_specs=P("node", None),
            )
        )
        body = _record_scan(
            run_chunk, z_fn,
            lambda z, ref: _point_metrics(
                z, ref, lambda x: jax.lax.psum(jnp.sum(x, 0), "node"), n
            ),
        )

        def record(state, idx_blocks, hp_dyn, ref, keep_snapshots):
            # the reductions are psums, so dist2 and consensus come out
            # replicated; the root is replicated too
            rec_spec = None if ref is None else P()
            return jax.shard_map(
                functools.partial(body, keep_snapshots=keep_snapshots),
                mesh=mesh,
                in_specs=(state_specs, P(None, None, "node"), hp_specs,
                          rec_spec),
                out_specs=(state_specs, rec_spec, P(),
                           P(None, "node", None) if keep_snapshots else None),
            )(state, idx_blocks, hp_dyn, ref)

        return _ShardedRunner(
            init=jax.jit(lambda z0: spec.init(problem, fhp, z0)),
            chunk=chunk,
            z_read=z_read,
            record=jax.jit(record, static_argnames=("keep_snapshots",)),
            mesh=mesh,
        )

    return runner_cache.SHARDED.get_or_build(key, (*guards, mesh), build)


@dataclasses.dataclass
class _DenseFaultRunner:
    """One compiled fault-injecting dense runner.

    The per-iteration delivery masks ride as scan inputs (like the
    hyperparameter values ride as traced arguments), so ONE compiled
    runner serves every drop rate, seed, and staleness bound of the same
    fault STRUCTURE — only which families are active enters the cache
    key (``runner_cache.fault_fingerprint``). Straggler last-delivered
    buffers thread through the scan carry next to the solver state.
    """

    init: Callable  # (z0) -> (state, bufs), eager
    chunk: Callable  # jitted (state, bufs, idx, mask, deliv, hp)
    z_read: Callable  # jitted (state, hp) -> (N, D)
    n_slots: int  # straggler buffer slots per iteration
    make_bufs: Callable = None  # () -> fresh zero buffers (phase entry)


def _get_dense_fault_runner(
    spec: SolverSpec, problem: Problem, hp: Mapping,
    *, has_link: bool, has_straggler: bool,
):
    """Fetch (or compile) the fault-injecting dense runner."""
    base_key, guards = _runner_key(spec, problem, hp)
    key = base_key + (
        runner_cache.fault_fingerprint(has_link, has_straggler),
    )

    def build() -> _DenseFaultRunner:
        comm = FaultyDenseComm(problem.graph, has_link, has_straggler)
        fhp = _FactoryHP(hp, spec.static_hp)
        step_fn = spec.step(problem, fhp, comm)
        z_fn = spec.z_of(problem, fhp, comm)
        n, D = problem.graph.n, problem.dim
        dt = problem.data.val.dtype

        # abstract probe: discover the straggler buffer slot shapes (one
        # per matvec invocation in the step) before assembling the carry
        comm.begin_probe()
        hp_probe = _dynamic_hp(spec, problem, hp)
        state_proto = jax.eval_shape(
            lambda z: spec.init(problem, fhp, z),
            jax.ShapeDtypeStruct((n, D), dt),
        )
        jax.eval_shape(
            lambda s, i: step_fn(s, i, hp_probe),
            state_proto,
            jax.ShapeDtypeStruct((n,), jnp.int32),
        )
        slots = comm.end_probe()

        def make_bufs():
            # buffers start at the t=0 "last delivered" convention: the
            # delivery masks force a fresh send on each node's first
            # iteration, so these zeros are never read
            return tuple(jnp.zeros(s.shape, s.dtype) for s in slots)

        def init(z0):
            return spec.init(problem, fhp, z0), make_bufs()

        def run_chunk(state, bufs, idx_block, mask_block, deliv_block,
                      hp_dyn):
            runner_cache.DENSE.note_trace()  # trace-time only

            def body(carry, xs):
                st, bf = carry
                i_t, mask_t, deliv_t = xs
                comm.begin_step(mask_t, deliv_t, bf)
                st2 = step_fn(st, i_t, hp_dyn)
                return (st2, comm.end_step()), None

            (st, bf), _ = jax.lax.scan(
                body, (state, bufs), (idx_block, mask_block, deliv_block)
            )
            return st, bf

        def read(state, hp_dyn):
            runner_cache.DENSE.note_trace()
            return z_fn(state, hp_dyn)

        return _DenseFaultRunner(
            init=init,
            chunk=jax.jit(run_chunk),
            z_read=jax.jit(read),
            n_slots=len(slots),
            make_bufs=make_bufs,
        )

    return runner_cache.DENSE.get_or_build(key, guards, build)


@dataclasses.dataclass
class _ShardedFaultRunner:
    """Sharded runner with a per-iteration link-delivery mask scan input.

    Every edge-color ``ppermute`` still executes physically (dropping at
    the receiver), so the HLO-measured collective traffic is identical to
    the fault-free program; only the modeled ``doubles_received`` counts
    delivered messages (see ``comm.FaultyShardedComm``).
    """

    init: Callable
    chunk: Callable  # jitted shard_map'd (state, idx, mask, hp) -> state
    z_read: Callable
    mesh: Any
    measured: dict = dataclasses.field(default_factory=dict)

    def collective_costs(self, state, idx_block, mask_block, hp_dyn) -> dict:
        """Per-iteration collective bytes/counts (same as fault-free)."""
        from repro.launch.hlo_analysis import compiled_collective_costs

        length = int(idx_block.shape[0])
        if length not in self.measured:
            compiled = self.chunk.lower(
                state, idx_block, mask_block, hp_dyn
            ).compile()
            self.measured[length] = compiled_collective_costs(
                compiled, iterations=length
            )
        return self.measured[length]


def _get_sharded_fault_runner(
    spec: SolverSpec, problem: Problem, hp: Mapping, mesh
):
    """Fetch (or compile) the link-fault shard_map runner."""
    base_key, guards = _runner_key(spec, problem, hp)
    key = base_key + (
        runner_cache.mesh_fingerprint(mesh),
        runner_cache.fault_fingerprint(True, False),
    )

    def build() -> _ShardedFaultRunner:
        comm = FaultyShardedComm(problem.graph, mesh)
        fhp = _FactoryHP(hp, spec.static_hp)
        step_fn = spec.step(problem, fhp, comm)
        z_fn = spec.z_of(problem, fhp, comm)
        n, D = problem.graph.n, problem.dim
        dt = problem.data.val.dtype

        state_proto = jax.eval_shape(
            lambda z: spec.init(problem, fhp, z),
            jax.ShapeDtypeStruct((n, D), dt),
        )
        state_specs = _node_partition_specs(state_proto, n)
        hp_specs = {k: P() for k in _dynamic_hp(spec, problem, hp)}

        def run_chunk(state, idx_block, mask_block, hp_dyn):
            runner_cache.SHARDED.note_trace()  # trace-time only

            def body(st, xs):
                i_t, mask_t = xs
                comm.begin_step(mask_t)
                st2 = step_fn(st, i_t, hp_dyn)
                comm.end_step()
                return st2, None

            st, _ = jax.lax.scan(body, state, (idx_block, mask_block))
            return st

        def read(state, hp_dyn):
            runner_cache.SHARDED.note_trace()
            return z_fn(state, hp_dyn)

        # the mask is replicated: each device reads its own row inside
        # the matvec via comm.local (see FaultyShardedComm)
        chunk = jax.jit(
            jax.shard_map(
                run_chunk, mesh=mesh,
                in_specs=(
                    state_specs, P(None, "node"), P(None, None, None),
                    hp_specs,
                ),
                out_specs=state_specs,
            )
        )
        z_read = jax.jit(
            jax.shard_map(
                read, mesh=mesh,
                in_specs=(state_specs, hp_specs),
                out_specs=P("node", None),
            )
        )
        return _ShardedFaultRunner(
            init=lambda z0: spec.init(problem, fhp, z0),
            chunk=chunk,
            z_read=z_read,
            mesh=mesh,
        )

    return runner_cache.SHARDED.get_or_build(key, (*guards, mesh), build)


def _get_batched_fns(runner: _DenseRunner, dyn_names) -> tuple:
    """(chunk, z_read, metrics) vmapped over a leading (grid/seed) axis,
    cached (``metrics`` is the solve() recorder's device reduction).

    hp entries map over axis 0 except ``lam`` (problem-level, shared);
    state and the index stream always carry the batch axis.
    """
    sig = tuple(sorted(dyn_names))
    if sig not in runner.batched:
        hp_axes = {k: (None if k == "lam" else 0) for k in sig}
        runner.batched[sig] = (
            jax.jit(jax.vmap(runner.run_chunk, in_axes=(0, 0, hp_axes))),
            jax.jit(jax.vmap(runner.z_fn, in_axes=(0, hp_axes))),
            jax.jit(jax.vmap(_dense_metrics, in_axes=(0, None))),
        )
    return runner.batched[sig]


# ---------------------------------------------------------------------------
# SolveResult + the shared metrics recorder
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SolveResult:
    """Uniform result of ``solve()`` for every method x comm backend.

    Record-point arrays all share the leading axis R = len(iters):
    ``dist2`` is empty when the problem has no cached ``z_star``;
    ``doubles_received``/``ints_received`` are *cumulative* per-node message
    counts at each record point (closed-form relay accounting for
    ``comm="sparse"``, the ``deg(n) * D`` dense-exchange model otherwise —
    index ints are zero for dense, the values travel as one dense block).
    ``state`` is the solver's final state pytree (``None`` for sparse runs:
    the relay engine returns trajectories, not solver internals);
    ``extras`` carries backend-specific outputs (sparse: ``z_trace``,
    ``recon_max_err``; sharded: the per-iteration ``collectives`` detail).

    ``measured_collective_bytes`` is populated by ``comm="sharded"`` only:
    cumulative bytes per device actually moved through collectives
    (``collective-permute`` etc.), measured from the compiled program's
    optimized HLO (``launch.hlo_analysis``) — the *measured* counterpart
    of the modeled ``doubles_received`` accounting.
    """

    method: str
    comm: str
    iters: np.ndarray  # (R,) iteration counts at record points
    dist2: np.ndarray  # (R,) mean_n ||z_n - z*||^2 (empty without z_star)
    consensus: np.ndarray  # (R,) mean_n ||z_n - zbar||^2
    doubles_received: np.ndarray  # (R, N) cumulative DOUBLEs per node
    ints_received: np.ndarray  # (R, N) cumulative index ints per node
    wall_time: float  # seconds in the solver (setup + scan + metrics)
    z: np.ndarray  # (N, D) final iterates
    state: Any  # final solver state pytree (None for sparse runs)
    zs: np.ndarray | None = None  # (R, N, D) snapshots if requested
    extras: dict = dataclasses.field(default_factory=dict)
    measured_collective_bytes: np.ndarray | None = None  # (R,) per device


def _cumulative_rounds(spec: SolverSpec, hp: Mapping, iters) -> np.ndarray:
    """Cumulative dense-exchange rounds per node at each record point.

    Default (hook unset): one neighbor exchange per iteration — the
    pre-PR-7 model. Methods with inner gossip loops (mudag) or skipped
    rounds (sliding) override via ``SolverSpec.comm_rounds``.
    """
    iters = np.asarray(iters)
    if spec.comm_rounds is None:
        return iters
    return np.rint(np.asarray(spec.comm_rounds(hp, iters))).astype(np.int64)


def _record_points(steps: int, record_every: int) -> list[int]:
    """Iteration counts to record at: every ``record_every``, plus the end."""
    pts = list(range(record_every, steps + 1, record_every))
    if not pts or pts[-1] != steps:
        pts.append(steps)
    return pts


def _record_segments(pts: list[int], start: int, every: int | None):
    """The record points after ``start``, split after each multiple of
    ``every`` (checkpoint boundaries); one segment when ``every`` is None."""
    segments, seg = [], []
    for pt in pts:
        if pt <= start:
            continue  # already covered by a restored checkpoint
        seg.append(pt)
        if every is not None and pt % every == 0:
            segments.append(seg)
            seg = []
    if seg:
        segments.append(seg)
    return segments


def _run_recorded(runner, state, indices, hp_dyn, ref, segments, start, rec,
                  keep_snapshots, save=None):
    """Run ``segments`` of record points through ``runner.record``.

    Each segment is one ``record`` call over its chunks of equal length
    (one more, with R = 1, for a shorter final chunk), then ONE read of its
    records to the host; the last segment's read brings the final iterates
    too. ``indices`` is the host's (steps, N) int32 stream; ``save(pt,
    state)`` runs after each segment. Returns ``(state, z, host_reads)``.
    """
    prev, z_final = start, None
    for k, seg in enumerate(segments):
        calls = []
        with obs.span("solve.run"):
            i = 0
            for length, group in itertools.groupby(np.diff([prev, *seg])):
                r = len(list(group))
                blocks = indices[prev:prev + r * length].reshape(r, length, -1)
                state, *out = runner.record(
                    state, blocks, hp_dyn, ref, keep_snapshots=keep_snapshots
                )
                calls.append((seg[i:i + r], out))
                i += r
                prev += r * length
            z_dev = None
            if k == len(segments) - 1:
                z_dev = runner.z_read(state, hp_dyn)
        with obs.span("solve.readout"):
            outs, z_final = jax.device_get(([o for _, o in calls], z_dev))
            for (its, _), (dist2, cons, zs) in zip(calls, outs):
                rec.extend(its, dist2, cons, zs)
        if save is not None:
            save(seg[-1], state)
    if not segments:
        # resumed at (or past) the final record point: nothing to re-run
        with obs.span("solve.readout"):
            z_final = jax.device_get(runner.z_read(state, hp_dyn))
    return state, z_final, max(len(segments), 1)


class _Recorder:
    """The one metrics recorder shared by every method and comm backend.

    Replaces the per-method metric loops the legacy entrypoints each
    reimplemented (``core.dsba.run``'s chunked loop, ``baselines``'
    ``_metrics_loop``): push (iteration, iterates) pairs, read back the
    uniform record arrays.
    """

    def __init__(self, z_star: np.ndarray | None, keep_snapshots: bool):
        self.z_star = None if z_star is None else np.asarray(z_star)
        self.iters: list[int] = []
        self.dist2: list[float] = []
        self.consensus: list[float] = []
        self.zs: list[np.ndarray] | None = [] if keep_snapshots else None

    def push(self, it: int, z, z_star=None) -> None:
        """Record consensus / distance-to-z* of iterates ``z`` at step ``it``.

        ``z`` is (N, D), or (B, N, D) for a batched ``solve_many`` run — the
        metrics reduce over the trailing (N, D) axes either way. ``z_star``
        overrides the recorder's reference root for this push — churn
        phases measure dist2 against the CURRENT membership's own root
        (only used when the recorder was built with a root at all, so
        ``dist2`` stays rectangular).
        """
        z = np.asarray(z)
        zbar = z.mean(-2, keepdims=True)
        self.iters.append(it)
        self.consensus.append(np.mean(np.sum((z - zbar) ** 2, -1), -1))
        if self.z_star is not None:
            ref = self.z_star if z_star is None else np.asarray(z_star)
            self.dist2.append(
                np.mean(np.sum((z - ref) ** 2, -1), -1)
            )
        if self.zs is not None:
            self.zs.append(z)

    def extend(self, iters, dist2, consensus, zs) -> None:
        """Append records the device already reduced (``_record_scan``):
        (R,) ``dist2`` (None without a root) and ``consensus``, and the
        (R, N, D) snapshots ``zs`` when the recorder keeps them."""
        self.iters.extend(int(it) for it in iters)
        self.consensus.extend(consensus)
        if self.z_star is not None:
            self.dist2.extend(dist2)
        if self.zs is not None:
            self.zs.extend(zs)

    def arrays(self) -> tuple[np.ndarray, np.ndarray, np.ndarray, Any]:
        """(iters, dist2, consensus, zs) as numpy arrays.

        Scalar pushes give (R,) metrics and (R, N, D) snapshots; batched
        pushes give (B, R) metrics and (B, R, N, D) snapshots — the record
        axis always ends up adjacent to the values it indexes.
        """

        def stack_metric(vals):
            a = np.asarray(vals)  # (R,) or (R, B)
            return a if a.ndim == 1 else np.moveaxis(a, 0, 1)

        zs = None
        if self.zs:
            zs = np.stack(self.zs)  # (R, [B,] N, D)
            if zs.ndim == 4:
                zs = np.moveaxis(zs, 0, 1)
        return (
            np.asarray(self.iters),
            stack_metric(self.dist2) if self.dist2 else np.zeros(0),
            stack_metric(self.consensus),
            zs,
        )


# ---------------------------------------------------------------------------
# Dynamic networks: phase resolution for schedules and churn plans
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Phase:
    """One static stretch of a dynamic run: fixed graph, W and membership.

    ``entry`` says how the phase was entered (how to transform the carried
    state at its start): None (run start), "switch" (new W, same
    membership — state carried as-is, the restart-on-new-W argument),
    "kill"/"join" (elastic remap via ``ft.elastic.ElasticGossip``).
    ``row_map`` maps this phase's nodes into the global accounting rows
    (N0 original nodes + one row per joined node); ``cols`` maps them
    into the columns of the master (steps, N0) sample-index stream.
    """

    start: int
    end: int
    problem: Problem
    entry: str | None
    event: ChurnEvent | None
    row_map: np.ndarray
    cols: np.ndarray


def _graph_fp(g: Graph | None):
    """Value fingerprint of an optional graph (for the churn-child cache)."""
    return None if g is None else (g.n, g.edges)


def _w_fp(w) -> bytes | None:
    """Value fingerprint of an optional mixing matrix."""
    return None if w is None else np.ascontiguousarray(w).tobytes()


def _churn_kill_child(problem: Problem, event: ChurnEvent):
    """(survivor Problem, keep list) for a kill event; memoized on problem.

    The child shares the parent's data arrays by slicing, so the runner
    cache compiles the survivor system once per distinct event shape even
    when the same plan replays across a sweep (children are memoized in
    ``problem.__dict__`` keyed by the event's value fingerprint).
    """
    n = problem.graph.n
    dead = sorted({int(x) for x in event.nodes})
    for x in dead:
        if not 0 <= x < n:
            raise ValueError(
                f"kill event names node {x} outside the current "
                f"membership 0..{n - 1}"
            )
    if len(dead) >= n:
        raise ValueError("kill event leaves no survivors")
    keep = [i for i in range(n) if i not in set(dead)]
    cache = problem.__dict__.setdefault("_churn_cache", {})
    key = ("kill", tuple(dead), _graph_fp(event.graph), _w_fp(event.w))
    if key not in cache:
        g = event.graph
        if g is None:
            g = problem.graph.subgraph(keep)
        if g.n != len(keep):
            raise ValueError(
                f"kill event graph has {g.n} nodes, {len(keep)} survive"
            )
        if not g.is_connected():
            raise ValueError(
                "survivor graph after kill is disconnected; pass "
                "ChurnEvent(graph=...) with a connected replacement"
            )
        data = problem.data
        ka = np.asarray(keep)
        child_data = dataclasses.replace(
            data, idx=data.idx[ka], val=data.val[ka], y=data.y[ka]
        )
        lam = problem.lam
        if np.ndim(lam) > 0:
            lam = np.asarray(lam)[ka]
        child = Problem(
            spec=problem.spec, data=child_data, graph=g, w=event.w, lam=lam
        )
        if problem.z_star is not None and np.ndim(lam) == 0:
            child.solve_star()  # the survivor system's own root
        cache[key] = child
    return cache[key], keep


def _churn_join_child(problem: Problem, event: ChurnEvent) -> Problem:
    """Grown Problem for a join event; newcomers replicate ``seed_from``'s
    data shard (the same seeding ``ElasticGossip.grow`` applies to state).
    Memoized like the kill children.
    """
    n = problem.graph.n
    sf = int(event.seed_from)
    if not 0 <= sf < n:
        raise ValueError(f"join seed_from {sf} outside membership 0..{n - 1}")
    cache = problem.__dict__.setdefault("_churn_cache", {})
    key = ("join", int(event.n_new), sf, _graph_fp(event.graph), _w_fp(event.w))
    if key not in cache:
        g = event.graph  # required (validated by ChurnEvent)
        if g.n != n + event.n_new:
            raise ValueError(
                f"join event graph has {g.n} nodes, membership grows "
                f"{n} -> {n + event.n_new}"
            )
        if not g.is_connected():
            raise ValueError("graph after join is disconnected")
        data = problem.data

        def rep(a):
            seed = np.broadcast_to(
                a[sf][None], (event.n_new,) + a.shape[1:]
            )
            return np.concatenate([a, seed], axis=0)

        child_data = dataclasses.replace(
            data, idx=rep(data.idx), val=rep(data.val), y=rep(data.y)
        )
        lam = problem.lam
        if np.ndim(lam) > 0:
            lam = np.concatenate(
                [np.asarray(lam), np.full(event.n_new, np.asarray(lam)[sf])]
            )
        child = Problem(
            spec=problem.spec, data=child_data, graph=g, w=event.w, lam=lam
        )
        if problem.z_star is not None and np.ndim(lam) == 0:
            child.solve_star()  # duplicated shards shift the global root
        cache[key] = child
    return cache[key]


def _resolve_phases(
    problem: Problem, steps: int, fault_plan
) -> list[_Phase]:
    """Split [0, steps) into static phases from a schedule or a fault plan.

    A single static run is the degenerate one-phase case; ``solve()``
    routes it through the ordinary static code path bit-for-bit.
    """
    n0 = problem.graph.n
    rows = np.arange(n0)
    if fault_plan is None:
        segs = [s for s in problem.schedule if s[0] < steps]
        phases = []
        for k, (start, g, w) in enumerate(segs):
            end = segs[k + 1][0] if k + 1 < len(segs) else steps
            if g is problem.graph and w is problem.w:
                child = problem
            else:
                child = dataclasses.replace(
                    problem, graph=g, w=w, schedule=None
                )
            phases.append(
                _Phase(
                    start, end, child, None if k == 0 else "switch",
                    None, rows, rows,
                )
            )
        return phases

    plan = fault_plan
    if isinstance(plan, ChurnEvent):
        plan = ChurnPlan((plan,))
    elif isinstance(plan, (list, tuple)):
        plan = ChurnPlan(tuple(plan))
    if not isinstance(plan, ChurnPlan):
        raise TypeError(
            f"fault_plan must be a ChurnPlan / ChurnEvent(s), got "
            f"{type(plan).__name__}"
        )
    for e in plan.events:
        if not 0 < e.at < steps:
            raise ValueError(
                f"churn event at iteration {e.at} outside (0, {steps})"
            )
    phases = []
    cur, cols, next_row = problem, np.arange(n0), n0
    start, entry, ev = 0, None, None
    for e in plan.events:
        phases.append(_Phase(start, int(e.at), cur, entry, ev, rows, cols))
        if e.kind == "kill":
            cur, keep = _churn_kill_child(cur, e)
            keep = np.asarray(keep)
            rows, cols = rows[keep], cols[keep]
        else:
            cur = _churn_join_child(cur, e)
            rows = np.concatenate(
                [rows, np.arange(next_row, next_row + e.n_new)]
            )
            # newcomers replay seed_from's sample stream — consistent
            # with their replicated data shard
            cols = np.concatenate(
                [cols, np.full(e.n_new, cols[int(e.seed_from)])]
            )
            next_row += e.n_new
        start, entry, ev = int(e.at), e.kind, e
    phases.append(_Phase(start, steps, cur, entry, ev, rows, cols))
    return phases


def _schedule_extras(phases: list[_Phase]) -> list[dict]:
    """The per-phase record for ``SolveResult.extras["schedule"]``."""
    return [
        {
            "start": ph.start,
            "end": ph.end,
            "n": ph.problem.graph.n,
            "spectral_gap": spectral_gap(ph.problem.w),
            "entry": ph.entry,
        }
        for ph in phases
    ]


def _elastic_remap(state, phase: _Phase, n_prev: int, spec: SolverSpec):
    """Apply a phase's entry transform to the carried solver state.

    Kill/join entries remap leading-N leaves through ``ElasticGossip``
    and then apply the solver's ``reanchor`` hook: difference-form
    methods conserve a mean-drift invariant whose level encodes the OLD
    membership's mean operator — without re-running the t=0 anchor on
    the survivors, the run stays pinned at the old system's root.
    A "switch" entry carries state untouched (the invariant only uses
    double stochasticity of W, which every segment satisfies).
    """
    if phase.entry not in ("kill", "join"):
        return state  # "switch" carries state as-is (restart-on-new-W)
    # lazy import: ft.elastic pulls in the training stack via core.gossip
    from repro.core.gossip import GossipConfig
    from repro.ft.elastic import ElasticGossip

    eg = ElasticGossip(GossipConfig(n_pods=n_prev))
    if phase.entry == "kill":
        dead = sorted({int(x) for x in phase.event.nodes})
        state, _ = eg.shrink(state, dead)
    else:
        state, _ = eg.grow(
            state, int(phase.event.n_new), int(phase.event.seed_from)
        )
    if spec.reanchor is not None:
        state = spec.reanchor(state)
    return state


def _rounds_at(spec: SolverSpec, hp: Mapping, t: int):
    """Cumulative dense-exchange rounds per node after ``t`` global steps.

    Global, not per-phase: solver step counters carry across phase
    boundaries, so e.g. sliding's communication cadence is a function of
    the global iteration. A phase's increment is the difference of this
    at its endpoints.
    """
    return _cumulative_rounds(spec, hp, np.asarray([t]))[0]


# ---------------------------------------------------------------------------
# Fault-mask resolution and delivered-only accounting
# ---------------------------------------------------------------------------


def _static_fault_masks(plan, graph, steps: int, start: int = 0):
    """Resolve a plan's link/straggler masks for one static phase.

    Returns ``(link_mask, strag_mask)`` with all-delivered masks
    collapsed to ``None`` — the caller routes a mask-free run through
    the PLAIN compiled runner, which makes a p=0 plan bit-equal to a
    plan-free run by construction (no masked arithmetic at all).
    """
    link_mask = strag_mask = None
    if plan is not None and plan.link is not None:
        m = link_delivered_mask(plan.link, graph, steps, start=start)
        if not bool(m.all()):
            link_mask = m
    if plan is not None and plan.straggler is not None:
        m = straggler_delivered_mask(
            plan.straggler, graph.n, steps, start=start
        )
        if not bool(m.all()):
            strag_mask = m
    return link_mask, strag_mask


def _fault_accounting(spec, hp, problem, link_mask, strag_mask, steps, iters):
    """Delivered-only doubles (R, N) plus the extras["faults"] record.

    The closed-form model charges one (D,)-double message per DELIVERED
    directed edge per exchange round: per-iteration delivered in-message
    counts from the masks, scaled by the method's rounds-per-iteration
    hook. With all-True masks this reduces exactly to the standard
    ``rounds * degree * D`` dense model.
    """
    D = problem.dim
    rr = _cumulative_rounds(spec, hp, np.arange(steps + 1))
    rdiff = np.diff(rr)  # rounds run during iteration t
    d_in = delivered_in_messages(problem.graph, link_mask, strag_mask, steps)
    per_step = rdiff[:, None] * d_in * D  # (steps, N)
    cumsum = np.cumsum(per_step, axis=0)
    doubles = cumsum[np.asarray(iters) - 1]  # (R, N)
    deg = np.asarray(problem.graph.degrees, dtype=np.int64)
    injected = int(rr[steps] * deg.sum())
    delivered = int((rdiff * d_in.sum(axis=1)).sum())
    extras = {
        "injected_messages": injected,
        "delivered_messages": delivered,
        "drop_rate": (
            0.0 if injected == 0 else 1.0 - delivered / injected
        ),
    }
    return doubles, extras


def _ckpt_meta(method: str, comm: str, record_every: int, rec) -> dict:
    """The JSON metadata committed with each ``solve()`` checkpoint.

    The recorder's scalars ride in the manifest (Python floats round-trip
    bit-exactly through ``repr`` in JSON), so resume can rebuild the
    record history without shape-templating run-length-dependent arrays.
    """
    return {
        "method": method,
        "comm": comm,
        "record_every": int(record_every),
        "rec_iters": [int(x) for x in rec.iters],
        "rec_dist2": [float(x) for x in rec.dist2],
        "rec_consensus": [float(x) for x in rec.consensus],
    }


# ---------------------------------------------------------------------------
# solve(): the single entrypoint
# ---------------------------------------------------------------------------


def _spanned(fn):
    """Wrap ``solve`` in the profiler span ``repro.solve``."""

    @functools.wraps(fn)
    def solve(problem, method="dsba", comm="dense", **kwargs):
        with obs.span("solve", method=method, comm=comm):
            return fn(problem, method, comm, **kwargs)

    return solve


@_spanned
def solve(
    problem: Problem,
    method: str = "dsba",
    comm: str = "dense",
    *,
    steps: int,
    record_every: int = 50,
    seed: int = 0,
    z0: np.ndarray | None = None,
    indices: np.ndarray | None = None,
    keep_snapshots: bool = False,
    comm_options: dict | None = None,
    checkpoint: CheckpointSpec | None = None,
    resume: str | None = None,
    **hyperparams,
) -> SolveResult:
    """Run ``method`` on ``problem`` over ``comm`` and return a SolveResult.

    Compilation is amortized across calls: the jitted runner is fetched
    from the keyed compiled-runner cache (``core.runner_cache``) and the
    hyperparameter values (plus ``lam``) are traced arguments, so repeated
    calls on the same problem shape — a sweep — skip XLA entirely. For a
    whole grid in one call see ``solve_many``.

    method: a registered solver name (``available_solvers()`` lists them).
    comm: ``"dense"`` (single-device neighbor exchange, the mixing
        matmul), ``"sparse"`` (the paper's delta relay — methods with a
        sparse backend only; see ``SolverSpec.supports_sparse_comm``), or
        ``"sharded"`` (one graph node per device of a ``"node"``-axis
        mesh; mixing runs as real ``collective-permute`` exchange and the
        result carries HLO-measured collective bytes).
    steps / record_every: iterations to run / metric recording period (the
        final iteration is always recorded).
    seed: RNG seed for the per-node sample draws when ``indices`` is not
        given; ``indices`` is an explicit (steps, N) stream for replayable
        runs (shared across methods and comm backends).
    z0: (N, D) starting point, default zeros.
    comm_options: backend passthrough for ``comm="sparse"`` (``engine``,
        ``verify``, ``use_pallas``) and ``comm="sharded"`` (``mesh``, a
        prebuilt ``"node"``-axis mesh; defaults to
        ``launch.mesh.make_node_mesh(N)``). Every backend additionally
        accepts ``fault_plan`` — a ``repro.ft.FaultPlan`` (or a bare
        ``ChurnEvent``/``ChurnPlan``) composing node churn, link faults,
        and stragglers; families gate on the solver's capability record
        (``supports_churn`` / ``supports_link_faults`` /
        ``supports_stragglers`` — stragglers are dense-only), and
        ``extras["faults"]`` reports injected-vs-delivered counts with
        the doubles accounting charging delivered traffic only.
    checkpoint: a ``repro.ckpt.CheckpointSpec`` — snapshot solver state +
        recorder at record boundaries every ``checkpoint.every``
        iterations (dense and sparse backends).
    resume: a checkpoint directory — restore the newest committed
        snapshot and continue BIT-EQUAL to an uninterrupted run.
    **hyperparams: solver hyperparameter overrides; the valid keys are the
        solver's ``defaults`` keys (anything else raises ``TypeError``).
    """
    spec = get_solver(method)
    if comm not in COMM_BACKENDS:
        raise ValueError(f"unknown comm backend {comm!r}; one of {COMM_BACKENDS}")
    # peek fault_plan before schema validation so an unsupported (method,
    # comm) x fault-family combination surfaces as the typed CapabilityError
    plan = as_fault_plan((comm_options or {}).get("fault_plan"))
    churn_plan = plan.churn if plan is not None else None
    want_link = plan is not None and plan.link is not None
    want_strag = plan is not None and plan.straggler is not None
    multi = problem.schedule is not None and len(problem.schedule) > 1
    if problem.schedule is not None and plan is not None:
        raise ValueError(
            "a graph schedule and a fault_plan cannot be combined in one "
            "run; encode the W changes as schedule segments instead"
        )
    _check_capability(
        spec, comm, problem.spec.kind,
        schedule=multi,
        churn=churn_plan is not None,
        per_node_lam=np.ndim(problem.lam) > 0,
        link_faults=want_link,
        stragglers=want_strag,
    )
    opts = _validate_options(comm, comm_options)
    opts.pop("fault_plan", None)
    if churn_plan is not None and keep_snapshots:
        raise ValueError(
            "keep_snapshots is unavailable with a fault_plan: snapshot "
            "shapes change across churn events"
        )
    if churn_plan is not None:
        # node ids are relabeled across membership segments, so explicit
        # node/edge targets in the other families become ambiguous
        if want_link and plan.link.edges is not None:
            raise ValueError(
                "scheduled link faults (edges=) cannot be combined with "
                "node churn: node ids are relabeled across membership "
                "changes; use a probabilistic LinkFault(p=...)"
            )
        if want_strag and plan.straggler.nodes is not None:
            raise ValueError(
                "a straggler node subset (nodes=) cannot be combined with "
                "node churn: node ids are relabeled across membership "
                "changes; use a global StragglerSpec(p=...)"
            )
    if steps < 1:
        raise ValueError("steps must be >= 1")
    if record_every < 1:
        raise ValueError("record_every must be >= 1")
    if checkpoint is not None and not isinstance(checkpoint, CheckpointSpec):
        raise TypeError(
            f"checkpoint must be a CheckpointSpec, got "
            f"{type(checkpoint).__name__}"
        )
    if checkpoint is not None or resume is not None:
        if comm == "sharded":
            raise ValueError(
                "checkpoint/resume supports comm='dense' and comm='sparse'; "
                "the sharded backend is not checkpointable"
            )
        if problem.schedule is not None:
            raise ValueError(
                "checkpoint/resume cannot be combined with a graph schedule "
                "(phase boundaries are not checkpoint boundaries)"
            )
        if plan is not None:
            raise ValueError(
                "checkpoint/resume cannot be combined with a fault_plan: "
                "fault masks and straggler buffers are not part of the "
                "snapshot schema"
            )
        if keep_snapshots:
            raise ValueError(
                "checkpoint/resume does not support keep_snapshots"
            )
    if (
        checkpoint is not None
        and comm == "dense"
        and checkpoint.every % record_every != 0
    ):
        raise ValueError(
            f"checkpoint.every={checkpoint.every} must be a multiple of "
            f"record_every={record_every} on the dense backend (snapshots "
            "happen at record boundaries)"
        )

    hp = dict(spec.defaults)
    unknown = set(hyperparams) - set(hp)
    if unknown:
        raise TypeError(
            f"{method!r} got unknown hyperparameters {sorted(unknown)}; "
            f"accepts {sorted(hp)}"
        )
    hp.update(hyperparams)

    data = problem.data
    n, D = data.n_nodes, problem.dim
    dt = data.val.dtype
    if z0 is None:
        z0 = np.zeros((n, D), dtype=dt)
    if indices is None:
        indices = draw_indices(steps, n, data.q, seed)
    indices = np.asarray(indices)
    if indices.ndim != 2 or indices.shape[0] < steps or indices.shape[1] != n:
        raise ValueError(
            f"indices must be (>= steps, N) = (>={steps}, {n}), "
            f"got {indices.shape}"
        )
    # dynamic-network resolution: a schedule or fault_plan becomes a list
    # of static phases; the single-phase case routes through the ordinary
    # static path below (bit-for-bit — only extras gains the segment log)
    phases = None
    sched_x = None
    if problem.schedule is not None or churn_plan is not None:
        phases = _resolve_phases(problem, steps, churn_plan)
        sched_x = _schedule_extras(phases)
        if len(phases) == 1:
            problem = phases[0].problem
            phases = None

    pts = _record_points(steps, record_every)
    rec = _Recorder(problem.z_star, keep_snapshots)

    if comm == "sparse":
        if phases is not None:
            if any(ph.entry in ("kill", "join") for ph in phases):
                return _solve_sparse_churn(
                    spec, method, phases, hp, steps, pts, rec, indices,
                    z0, opts, sched_x, plan,
                )
            return _solve_sparse_schedule(
                spec, method, phases, hp, steps, pts, rec, indices, z0,
                opts, sched_x,
            )
        fault_x = None
        if want_link:
            sent = source_sent_mask(plan.link, problem.graph, steps)
            n_bcast = steps * problem.graph.n
            fault_x = {
                "injected_broadcasts": int(n_bcast),
                "delivered_broadcasts": int(sent.sum()),
                "drop_rate": 1.0 - float(sent.sum()) / n_bcast,
            }
            if not bool(sent.all()):
                # all-delivered plans route through the plain (byte-
                # identical) relay program — p=0 is bit-equal by routing
                opts["sent_mask"] = sent
        mgr = None
        if checkpoint is not None:
            mgr = CheckpointManager(
                checkpoint.directory, keep_last=checkpoint.keep_last
            )
            meta = {"method": method, "comm": comm}
            opts["ckpt_every"] = int(checkpoint.every)
            opts["ckpt_save"] = (
                lambda t_done, tree: mgr.save(
                    t_done, tree, metadata=meta, async_=False
                )
            )
        if resume is not None:
            step_r, meta_r, leaves = load_checkpoint(resume)
            if step_r is None:
                raise ValueError(
                    f"no committed checkpoint to resume in {resume!r}"
                )
            for key, val in (("method", method), ("comm", comm)):
                if meta_r.get(key) != val:
                    raise ValueError(
                        f"checkpoint {key}={meta_r.get(key)!r} does not "
                        f"match the resuming run's {key}={val!r}"
                    )
            if step_r > steps:
                raise ValueError(
                    f"checkpoint at step {step_r} is beyond steps={steps}; "
                    "resume with steps >= the checkpointed iteration"
                )
            opts["resume"] = (int(step_r), leaves)
        t0 = time.perf_counter()
        sres = spec.sparse_run(problem, hp, steps, indices, z0, opts)
        wall = time.perf_counter() - t0
        with obs.span("solve.readout"):
            for pt in pts:
                rec.push(pt, sres.z_trace[pt])
        iters, dist2, cons, zs = rec.arrays()
        sel = np.asarray(pts) - 1
        extras = {
            "z_trace": sres.z_trace,
            "recon_max_err": sres.recon_max_err,
        }
        if fault_x is not None:
            extras["faults"] = fault_x
        if sched_x is not None:
            extras["schedule"] = sched_x
        return SolveResult(
            method=method,
            comm=comm,
            iters=iters,
            dist2=dist2,
            consensus=cons,
            doubles_received=sres.doubles_received[sel],
            ints_received=sres.ints_received[sel],
            wall_time=wall,
            z=sres.z_trace[-1],
            state=None,
            zs=zs,
            extras=extras,
        )

    if phases is not None:
        return _solve_phased(
            spec, method, comm, phases, hp, steps, pts, rec, indices, z0,
            opts, sched_x, plan,
        )

    if comm == "sharded":
        # ---- sharded backend: shard_map runner, measured collectives -----
        mesh = opts.pop("mesh", None)
        t0 = time.perf_counter()
        if mesh is None:
            from repro.launch.mesh import make_node_mesh

            mesh = make_node_mesh(n)
        hp_dyn = _dynamic_hp(spec, problem, hp)
        idx = np.asarray(indices[:steps], np.int32)
        link_mask, _ = _static_fault_masks(plan, problem.graph, steps)
        fault_x = None
        host_reads = None
        if link_mask is not None:
            # link-fault runner: every edge-color ppermute still executes
            # (measured bytes are identical); receivers drop masked edges
            # and redirect the lost mixing mass to their own iterate
            idx_j = jnp.asarray(idx)
            frunner = _get_sharded_fault_runner(spec, problem, hp, mesh)
            lm = jnp.asarray(link_mask)
            state = frunner.init(jnp.asarray(z0))
            costs = frunner.collective_costs(
                state, idx_j[: pts[0]], lm[: pts[0]], hp_dyn
            )
            prev = 0
            z_final = None
            for pt in pts:
                state = frunner.chunk(
                    state, idx_j[prev:pt], lm[prev:pt], hp_dyn
                )
                prev = pt
                z_final = frunner.z_read(state, hp_dyn)
                rec.push(pt, z_final)
            wall = time.perf_counter() - t0
            iters, dist2, cons, zs = rec.arrays()
            doubles, fault_x = _fault_accounting(
                spec, hp, problem, link_mask, None, steps, iters
            )
        else:
            runner = _get_sharded_runner(spec, problem, hp, mesh)
            state = runner.init(jnp.asarray(z0))
            costs = runner.collective_costs(state, idx[: pts[0]], hp_dyn)
            state, z_final, host_reads = _run_recorded(
                runner, state, idx, hp_dyn, _record_ref(rec.z_star, dt),
                _record_segments(pts, 0, None), 0, rec, keep_snapshots,
            )
            wall = time.perf_counter() - t0
            iters, dist2, cons, zs = rec.arrays()
            per_node = dense_doubles_per_iter(problem.graph, D)  # (N,)
            rounds = _cumulative_rounds(spec, hp, iters)
            doubles = rounds[:, None] * per_node[None, :]
            if plan is not None and want_link:
                _, fault_x = _fault_accounting(
                    spec, hp, problem, None, None, steps, iters
                )
        extras = {
            "collectives": costs,
            "mesh_devices": int(mesh.shape["node"]),
        }
        if host_reads is not None:
            extras["host_reads"] = host_reads
        if fault_x is not None:
            extras["faults"] = fault_x
        if sched_x is not None:
            extras["schedule"] = sched_x
        return SolveResult(
            method=method,
            comm=comm,
            iters=iters,
            dist2=dist2,
            consensus=cons,
            doubles_received=doubles,
            ints_received=np.zeros_like(doubles),
            wall_time=wall,
            z=np.asarray(z_final),
            state=state,
            zs=zs,
            extras=extras,
            # per-program measurement: collectives inside a traced-bound
            # inner loop (mudag's K gossip rounds) are counted once per
            # outer iteration — the modeled `doubles_received` carries the
            # K-aware accounting (docs/solvers.md)
            measured_collective_bytes=iters * costs["bytes_per_iter"],
        )

    # ---- dense backend: cached compiled runner, hp as traced arguments ----
    t0 = time.perf_counter()
    hp_dyn = _dynamic_hp(spec, problem, hp)
    idx = np.asarray(indices[:steps], np.int32)
    link_mask, strag_mask = _static_fault_masks(plan, problem.graph, steps)

    if link_mask is not None or strag_mask is not None:
        # fault-injecting runner: the per-iteration masks ride as scan
        # inputs; one compiled program per active-family STRUCTURE
        idx_j = jnp.asarray(idx)
        frunner = _get_dense_fault_runner(
            spec, problem, hp,
            has_link=link_mask is not None,
            has_straggler=strag_mask is not None,
        )
        lm = (
            jnp.asarray(link_mask)
            if link_mask is not None
            else jnp.ones((steps, 1, 1), bool)  # inert placeholder xs
        )
        sm = (
            jnp.asarray(strag_mask)
            if strag_mask is not None
            else jnp.ones((steps, 1), bool)
        )
        state, bufs = frunner.init(jnp.asarray(z0))
        prev = 0
        z_final = None
        for pt in pts:
            state, bufs = frunner.chunk(
                state, bufs, idx_j[prev:pt], lm[prev:pt], sm[prev:pt],
                hp_dyn,
            )
            prev = pt
            z_final = frunner.z_read(state, hp_dyn)
            rec.push(pt, z_final)
        wall = time.perf_counter() - t0
        iters, dist2, cons, zs = rec.arrays()
        doubles, fault_x = _fault_accounting(
            spec, hp, problem, link_mask, strag_mask, steps, iters
        )
        extras = {"faults": fault_x}
        if sched_x is not None:
            extras["schedule"] = sched_x
        return SolveResult(
            method=method,
            comm=comm,
            iters=iters,
            dist2=dist2,
            consensus=cons,
            doubles_received=doubles,
            ints_received=np.zeros_like(doubles),
            wall_time=wall,
            z=np.asarray(z_final),
            state=state,
            zs=zs,
            extras=extras,
        )

    runner = _get_dense_runner(spec, problem, hp)
    mgr = None
    if checkpoint is not None:
        mgr = CheckpointManager(
            checkpoint.directory, keep_last=checkpoint.keep_last
        )
    start = 0
    state = None
    if resume is not None:
        state, start = _restore_dense(
            resume, runner, rec, method=method, comm=comm,
            record_every=record_every, steps=steps, z0=z0,
        )
    if state is None:
        state = runner.init(jnp.asarray(z0))
        if runner.donates:
            # init factories may alias leaves (dsba's z/z_prev are the same
            # array at t=0); donation rejects duplicate buffers, so de-alias
            # the initial carry once — later carries are distinct scan
            # outputs
            state = jax.tree_util.tree_map(
                lambda x: jnp.array(x, copy=True), state
            )
    save = None
    if mgr is not None:

        def save(pt, state):
            if pt % checkpoint.every == 0:
                mgr.save(
                    pt, {"state": state},
                    metadata=_ckpt_meta(method, comm, record_every, rec),
                )

    segments = _record_segments(
        pts, start, None if mgr is None else checkpoint.every
    )
    state, z_final, host_reads = _run_recorded(
        runner, state, idx, hp_dyn, _record_ref(rec.z_star, dt), segments,
        start, rec, keep_snapshots, save,
    )
    if mgr is not None:
        mgr.wait()
    wall = time.perf_counter() - t0

    iters, dist2, cons, zs = rec.arrays()
    per_node = dense_doubles_per_iter(problem.graph, D)  # (N,)
    rounds = _cumulative_rounds(spec, hp, iters)
    doubles = rounds[:, None] * per_node[None, :]
    extras = {"host_reads": host_reads}
    if sched_x is not None:
        extras["schedule"] = sched_x
    if plan is not None and (want_link or want_strag):
        # p=0 plan: masks collapsed to the plain runner (bit-equal by
        # routing), but the delivered-vs-injected record is still reported
        _, fault_x = _fault_accounting(
            spec, hp, problem, None, None, steps, iters
        )
        extras["faults"] = fault_x
    return SolveResult(
        method=method,
        comm=comm,
        iters=iters,
        dist2=dist2,
        consensus=cons,
        doubles_received=doubles,
        ints_received=np.zeros_like(doubles),
        wall_time=wall,
        z=np.asarray(z_final),
        state=state,
        zs=zs,
        extras=extras,
    )


def _restore_dense(resume, runner, rec, *, method, comm, record_every,
                   steps, z0):
    """Restore a dense ``solve()`` from the newest committed checkpoint.

    Returns ``(state, start)``. The recorder history rides in the
    manifest metadata as Python floats (bit-exact JSON round-trip); the
    solver state restores strictly against a template built by the
    runner's own init (shapes are run-length independent).
    """
    step_r, meta, _ = load_checkpoint(resume)
    if step_r is None:
        raise ValueError(f"no committed checkpoint to resume in {resume!r}")
    for key, val in (("method", method), ("comm", comm),
                     ("record_every", record_every)):
        if meta.get(key) != val:
            raise ValueError(
                f"checkpoint {key}={meta.get(key)!r} does not match the "
                f"resuming run's {key}={val!r}"
            )
    if step_r > steps:
        raise ValueError(
            f"checkpoint at step {step_r} is beyond steps={steps}; "
            "resume with steps >= the checkpointed iteration"
        )
    template = runner.init(jnp.asarray(z0))
    tree, _ = restore_checkpoint(resume, {"state": template}, step=step_r)
    rec.iters.extend(int(x) for x in meta["rec_iters"])
    rec.dist2.extend(float(x) for x in meta["rec_dist2"])
    rec.consensus.extend(float(x) for x in meta["rec_consensus"])
    return tree["state"], int(step_r)


def _solve_phased(
    spec, method, comm, phases, hp, steps, pts, rec, indices, z0, opts,
    sched_x, plan=None,
) -> SolveResult:
    """Dense/sharded execution of a multi-phase (dynamic-network) run.

    Each phase runs through its own cached runner (edge colorings /
    meshes re-derived per phase); the solver state is carried across
    boundaries — as-is for a W switch (restart-on-new-W,
    docs/algorithm.md), elastically remapped for churn. Communication
    accounting folds per-phase increments into global per-row cumulative
    counts: rows are the N0 original nodes plus one row per joined node
    (``extras["churn_rows"]`` when membership changed).

    ``plan``: an optional ``FaultPlan`` whose link/straggler families
    compose with the churn phases — each phase resolves its own delivery
    masks against the phase graph (seeds fold the phase's global start
    iteration, so the mask stream is one continuous draw), straggler
    buffers re-zero at membership boundaries (the first post-churn
    iteration always delivers fresh), and the delivered-only accounting
    folds into the same per-row cumulative counts.
    """
    t0 = time.perf_counter()
    base = phases[0].problem
    D = base.dim
    total_rows = max(int(ph.row_map.max()) for ph in phases) + 1
    record_set = set(pts)
    cum = np.zeros(total_rows)
    doubles_rows: list[np.ndarray] = []
    measured: list[float] = []
    measured_base = 0.0
    costs0 = None
    mesh_opt = opts.get("mesh")
    mesh_devices = None
    state = None
    bufs = None
    z_final = None
    n_prev = base.graph.n
    injected_tot = delivered_tot = 0
    want_fault = plan is not None and (
        plan.link is not None or plan.straggler is not None
    )
    for ph in phases:
        p = ph.problem
        n_ph = p.graph.n
        seg = ph.end - ph.start
        if state is not None:
            state = _elastic_remap(state, ph, n_prev, spec)
        link_mask, strag_mask = _static_fault_masks(
            plan, p.graph, seg, start=ph.start
        )
        faulty = link_mask is not None or strag_mask is not None
        if comm == "sharded":
            if mesh_opt is not None and mesh_opt.shape["node"] == n_ph:
                mesh = mesh_opt
            else:
                from repro.launch.mesh import make_node_mesh

                mesh = make_node_mesh(n_ph)
            if faulty:
                runner = _get_sharded_fault_runner(spec, p, hp, mesh)
            else:
                runner = _get_sharded_runner(spec, p, hp, mesh)
            if mesh_devices is None:
                mesh_devices = int(mesh.shape["node"])
        elif faulty:
            runner = _get_dense_fault_runner(
                spec, p, hp,
                has_link=link_mask is not None,
                has_straggler=strag_mask is not None,
            )
        else:
            runner = _get_dense_runner(spec, p, hp)
        hp_dyn = _dynamic_hp(spec, p, hp)
        if state is None:
            if comm == "dense" and faulty:
                state, bufs = runner.init(jnp.asarray(z0))
            else:
                state = runner.init(jnp.asarray(z0))
            if comm == "dense" and not faulty and runner.donates:
                state = jax.tree_util.tree_map(
                    lambda x: jnp.array(x, copy=True), state
                )
        elif comm == "dense" and faulty:
            # straggler buffers do not survive membership remaps; the
            # phase's delivery masks force fresh sends at its first
            # iteration, so re-zeroed buffers are never read
            bufs = runner.make_bufs()
        if faulty:
            lm_ph = (
                jnp.asarray(link_mask)
                if link_mask is not None
                else jnp.ones((seg, 1, 1), bool)
            )
            sm_ph = (
                jnp.asarray(strag_mask)
                if strag_mask is not None
                else jnp.ones((seg, 1), bool)
            )
        rdiff_ph = np.diff(
            _cumulative_rounds(spec, hp, np.arange(ph.start, ph.end + 1))
        )
        d_in_ph = delivered_in_messages(p.graph, link_mask, strag_mask, seg)
        cum_ph = np.cumsum(rdiff_ph[:, None] * d_in_ph * D, axis=0)
        deg_ph = np.asarray(p.graph.degrees, dtype=np.int64)
        injected_tot += int(rdiff_ph.sum() * deg_ph.sum())
        delivered_tot += int((rdiff_ph * d_in_ph.sum(axis=1)).sum())
        costs = None
        marks = sorted(
            {pt for pt in pts if ph.start < pt <= ph.end} | {ph.end}
        )
        prev = ph.start
        for mk in marks:
            idx_blk = jnp.asarray(
                indices[prev:mk][:, ph.cols], jnp.int32
            )
            if comm == "sharded" and costs is None:
                if faulty:
                    costs = runner.collective_costs(
                        state, idx_blk, lm_ph[prev - ph.start:mk - ph.start],
                        hp_dyn,
                    )
                else:
                    costs = runner.collective_costs(state, idx_blk, hp_dyn)
                if costs0 is None:
                    costs0 = costs
            if not faulty:
                state = runner.chunk(state, idx_blk, hp_dyn)
            elif comm == "sharded":
                state = runner.chunk(
                    state, idx_blk,
                    lm_ph[prev - ph.start:mk - ph.start], hp_dyn,
                )
            else:
                state, bufs = runner.chunk(
                    state, bufs, idx_blk,
                    lm_ph[prev - ph.start:mk - ph.start],
                    sm_ph[prev - ph.start:mk - ph.start], hp_dyn,
                )
            prev = mk
            if mk in record_set:
                z_final = runner.z_read(state, hp_dyn)
                rec.push(mk, z_final, z_star=p.z_star)
                snap = cum.copy()
                snap[ph.row_map] += cum_ph[mk - ph.start - 1]
                doubles_rows.append(snap)
                if comm == "sharded":
                    measured.append(
                        measured_base
                        + (mk - ph.start) * costs["bytes_per_iter"]
                    )
        cum[ph.row_map] += cum_ph[-1]
        if comm == "sharded":
            measured_base += (ph.end - ph.start) * costs["bytes_per_iter"]
        n_prev = n_ph
    wall = time.perf_counter() - t0
    iters, dist2, cons, zs = rec.arrays()
    doubles = np.stack(doubles_rows)
    extras: dict = {"schedule": sched_x}
    if total_rows != base.graph.n or any(
        ph.entry in ("kill", "join") for ph in phases
    ):
        extras["churn_rows"] = total_rows
    if want_fault:
        extras["faults"] = {
            "injected_messages": injected_tot,
            "delivered_messages": delivered_tot,
            "drop_rate": (
                0.0 if injected_tot == 0
                else 1.0 - delivered_tot / injected_tot
            ),
        }
    if comm == "sharded":
        extras["collectives"] = costs0
        extras["mesh_devices"] = mesh_devices
    return SolveResult(
        method=method,
        comm=comm,
        iters=iters,
        dist2=dist2,
        consensus=cons,
        doubles_received=doubles,
        ints_received=np.zeros_like(doubles),
        wall_time=wall,
        z=np.asarray(z_final),
        state=state,
        zs=zs,
        extras=extras,
        measured_collective_bytes=(
            np.asarray(measured) if comm == "sharded" else None
        ),
    )


def _solve_sparse_schedule(
    spec, method, phases, hp, steps, pts, rec, indices, z0, opts, sched_x,
) -> SolveResult:
    """Sparse-relay execution of a graph schedule: chained segment runs.

    Each segment re-derives the relay protocol (reconstruction waves,
    broadcast trees) for its own graph; the solver state chains through
    ``SparseRunResult.state`` -> the next segment's ``state0`` (the
    restart path charges the extra z0-resync flood —
    ``core.sparse_comm``). Message accounting concatenates with each
    segment offset by the previous segment's final cumulative counts.
    """
    t0 = time.perf_counter()
    st = None
    z_traces = []
    doubles_parts, ints_parts = [], []
    d_off = i_off = 0  # int: keeps the concatenated counts integer-typed
    recon = []
    for k, ph in enumerate(phases):
        seg_steps = ph.end - ph.start
        o = dict(opts)
        if k == 0:
            sres = spec.sparse_run(
                ph.problem, hp, seg_steps,
                indices[ph.start:ph.end], z0, o,
            )
        else:
            o["state0"] = st
            sres = spec.sparse_run(
                ph.problem, hp, seg_steps,
                indices[ph.start:ph.end], None, o,
            )
        st = sres.state
        z_traces.append(sres.z_trace if k == 0 else sres.z_trace[1:])
        doubles_parts.append(sres.doubles_received + d_off)
        ints_parts.append(sres.ints_received + i_off)
        d_off = doubles_parts[-1][-1]
        i_off = ints_parts[-1][-1]
        recon.append(sres.recon_max_err)
    wall = time.perf_counter() - t0
    z_trace = np.concatenate(z_traces)  # (steps + 1, N, D)
    doubles_all = np.concatenate(doubles_parts)  # (steps, N) cumulative
    ints_all = np.concatenate(ints_parts)
    rc = np.asarray(recon, dtype=np.float64)
    recon_max = (
        float(np.nanmax(rc)) if not np.all(np.isnan(rc)) else float("nan")
    )
    for pt in pts:
        rec.push(pt, z_trace[pt])
    iters, dist2, cons, zs = rec.arrays()
    sel = np.asarray(pts) - 1
    return SolveResult(
        method=method,
        comm="sparse",
        iters=iters,
        dist2=dist2,
        consensus=cons,
        doubles_received=doubles_all[sel],
        ints_received=ints_all[sel],
        wall_time=wall,
        z=z_trace[-1],
        state=None,
        zs=zs,
        extras={
            "z_trace": z_trace,
            "recon_max_err": recon_max,
            "schedule": sched_x,
        },
    )


def _solve_sparse_churn(
    spec, method, phases, hp, steps, pts, rec, indices, z0, opts, sched_x,
    plan,
) -> SolveResult:
    """Sparse-relay execution of node churn: per-membership-segment relays.

    Each membership segment re-derives the relay protocol tables
    (reconstruction waves, DD delta ring, broadcast trees) for its own
    graph and chains through ``run_sparse(..., state0=)``. The carried
    state is elastically remapped at each boundary (``_elastic_remap``
    shrinks/grows the SAGA tables and applies the solver's ``reanchor``
    — DSBA resets its step counter to 0, so the segment re-runs the
    eq. 31 anchored update against the surviving/augmented membership
    and the restart path floods the remapped z0 once). Accounting folds
    per-segment delivered counts into global per-row cumulative totals,
    exactly like the dense churn path (rows = N0 originals + joiners).
    """
    t0 = time.perf_counter()
    base = phases[0].problem
    total_rows = max(int(ph.row_map.max()) for ph in phases) + 1
    cum_d = np.zeros(total_rows, dtype=np.int64)
    cum_i = np.zeros(total_rows, dtype=np.int64)
    out_d: list[np.ndarray] = []
    out_i: list[np.ndarray] = []
    recon = []
    injected_tot = delivered_tot = 0
    want_link = plan is not None and plan.link is not None
    st = None
    z_final = None
    for k, ph in enumerate(phases):
        p = ph.problem
        seg = ph.end - ph.start
        o = dict(opts)
        if want_link:
            sent = source_sent_mask(plan.link, p.graph, seg, start=ph.start)
            injected_tot += seg * p.graph.n
            delivered_tot += int(sent.sum())
            if not bool(sent.all()):
                o["sent_mask"] = sent
        idx_seg = indices[ph.start:ph.end][:, ph.cols]
        if st is None:
            sres = spec.sparse_run(p, hp, seg, idx_seg, z0, o)
        else:
            st = _elastic_remap(st, ph, n_prev, spec)
            o["state0"] = st
            sres = spec.sparse_run(p, hp, seg, idx_seg, None, o)
        st = sres.state
        n_prev = p.graph.n
        for pt in pts:
            if ph.start < pt <= ph.end:
                lt = pt - ph.start
                rec.push(pt, sres.z_trace[lt], z_star=p.z_star)
                snap_d = cum_d.copy()
                snap_d[ph.row_map] += sres.doubles_received[lt - 1]
                snap_i = cum_i.copy()
                snap_i[ph.row_map] += sres.ints_received[lt - 1]
                out_d.append(snap_d)
                out_i.append(snap_i)
        cum_d[ph.row_map] += sres.doubles_received[seg - 1]
        cum_i[ph.row_map] += sres.ints_received[seg - 1]
        recon.append(sres.recon_max_err)
        z_final = sres.z_trace[-1]
    wall = time.perf_counter() - t0
    rc = np.asarray(recon, dtype=np.float64)
    recon_max = (
        float(np.nanmax(rc)) if not np.all(np.isnan(rc)) else float("nan")
    )
    iters, dist2, cons, zs = rec.arrays()
    extras: dict = {
        "recon_max_err": recon_max,
        "schedule": sched_x,
        "churn_rows": total_rows,
    }
    if want_link:
        extras["faults"] = {
            "injected_broadcasts": injected_tot,
            "delivered_broadcasts": delivered_tot,
            "drop_rate": (
                0.0 if injected_tot == 0
                else 1.0 - delivered_tot / injected_tot
            ),
        }
    return SolveResult(
        method=method,
        comm="sparse",
        iters=iters,
        dist2=dist2,
        consensus=cons,
        doubles_received=np.stack(out_d),
        ints_received=np.stack(out_i),
        wall_time=wall,
        z=z_final,
        state=st,
        zs=zs,
        extras=extras,
    )


# ---------------------------------------------------------------------------
# solve_many(): the batched sweep entrypoint
# ---------------------------------------------------------------------------


def solve_many(
    problem: Problem,
    method: str = "dsba",
    comm: str = "dense",
    *,
    steps: int,
    grid: list[Mapping[str, float]] | None = None,
    seeds: list[int] | None = None,
    record_every: int = 50,
    seed: int = 0,
    z0: np.ndarray | None = None,
    indices: np.ndarray | None = None,
    keep_snapshots: bool = False,
    comm_options: dict | None = None,
    **common_hp,
) -> SolveResult:
    """Run a hyperparameter/seed sweep as ONE batched computation.

    The sweep axis B is ``len(grid)`` (per-entry hyperparameter overrides),
    ``len(seeds)`` (per-entry sample streams), or both (paired — equal
    lengths required). On the dense backend the whole grid is vmapped over
    a leading batch axis of the cached compiled runner: one executable,
    one scan, every grid point advancing in lockstep.

    ``comm="sparse"`` batches too: the relay scan is vmapped over (seed,
    alpha) with the closed-form message accounting applied per run after
    the scan, bit-identical to sequential calls. Fallback to the cached
    *sequential* path (one warm ``solve()`` per entry — still compile-free
    after the first) happens when the grid is not vmappable:

    - ``comm="sparse"`` with ``engine="reference"`` (the per-observer
      oracle loop) or a method without a batched sparse backend;
    - ``comm="sharded"`` — one mesh program advances one run; sweeps
      reuse the warm compiled runner sequentially;
    - a grid entry overrides a ``static_hp`` (structural, must recompile).

    Returns one ``SolveResult`` whose per-run arrays carry a leading B
    axis: ``dist2``/``consensus`` are (B, R), ``doubles_received``/
    ``ints_received`` (B, R, N), ``z`` (B, N, D), ``zs`` (B, R, N, D).
    ``iters`` stays (R,) — record points are shared. ``extras`` records
    ``grid``, ``seeds`` and whether the batched path ran (``"batched"``).

    indices: optional explicit sample streams — (>= steps, N) shared by
    every entry, or (B, >= steps, N) per entry. Defaults to
    ``draw_indices`` per entry seed (``seeds[b]``, else the shared
    ``seed``).
    """
    spec = get_solver(method)
    if comm not in COMM_BACKENDS:
        raise ValueError(f"unknown comm backend {comm!r}; one of {COMM_BACKENDS}")
    fault_plan = as_fault_plan((comm_options or {}).get("fault_plan"))
    if problem.schedule is not None and fault_plan is not None:
        raise ValueError(
            "a graph schedule and a fault_plan cannot be combined in one run"
        )
    _check_capability(
        spec, comm, problem.spec.kind,
        schedule=problem.schedule is not None and len(problem.schedule) > 1,
        churn=fault_plan is not None and fault_plan.churn is not None,
        per_node_lam=np.ndim(problem.lam) > 0,
        link_faults=fault_plan is not None and fault_plan.link is not None,
        stragglers=(
            fault_plan is not None and fault_plan.straggler is not None
        ),
    )
    _validate_options(comm, comm_options)
    # dynamic-network and fault-injected runs are per-entry sequential:
    # the vmapped batched paths assume one static fault-free (graph, W,
    # membership) for the whole scan
    dynamic = problem.schedule is not None or fault_plan is not None
    if grid is None and seeds is None:
        raise ValueError("solve_many needs a grid, seeds, or both")
    entries = [dict(e) for e in grid] if grid is not None else None
    if entries is not None and seeds is not None and len(entries) != len(seeds):
        raise ValueError(
            f"grid ({len(entries)}) and seeds ({len(seeds)}) must pair up"
        )
    n_runs = len(entries) if entries is not None else len(seeds)
    if n_runs < 1:
        raise ValueError("solve_many needs at least one grid/seed entry")
    if entries is None:
        entries = [{} for _ in range(n_runs)]
    seeds_list = list(seeds) if seeds is not None else [seed] * n_runs

    known = set(spec.defaults)
    for ent in (common_hp, *entries):
        unknown = set(ent) - known
        if unknown:
            raise TypeError(
                f"{method!r} got unknown hyperparameters {sorted(unknown)}; "
                f"accepts {sorted(known)}"
            )
    merged = [dict(spec.defaults, **common_hp, **e) for e in entries]

    data = problem.data
    n, q = data.n_nodes, data.q
    idx_b = _sweep_indices(indices, n_runs, steps, n, q, seeds_list)

    ragged = any(k in spec.static_hp for e in entries for k in e)
    if comm == "sparse" and not ragged and not dynamic:
        res = _solve_many_sparse_batched(
            problem, method, spec, steps=steps, record_every=record_every,
            z0=z0, keep_snapshots=keep_snapshots, comm_options=comm_options,
            merged=merged, entries=entries, seeds=seeds_list, idx_b=idx_b,
        )
        if res is not None:
            return res
    if comm != "dense" or ragged or dynamic:
        return _solve_many_sequential(
            problem, method, comm, steps=steps, record_every=record_every,
            z0=z0, keep_snapshots=keep_snapshots, comm_options=comm_options,
            merged=merged, entries=entries, seeds=seeds_list, idx_b=idx_b,
        )

    # ---- batched path: vmap the cached runner over the grid axis ----------
    if steps < 1:
        raise ValueError("steps must be >= 1")
    if record_every < 1:
        raise ValueError("record_every must be >= 1")
    D = problem.dim
    dt = data.val.dtype
    if z0 is None:
        z0 = np.zeros((n, D), dtype=dt)

    t0 = time.perf_counter()
    base_hp = dict(spec.defaults, **common_hp)
    runner = _get_dense_runner(spec, problem, base_hp)
    dyn_names = tuple(_dynamic_hp(spec, problem, base_hp))
    chunk_b, z_read_b, metrics_b = _get_batched_fns(runner, dyn_names)

    # hp arrays in the DATA dtype so batched arithmetic promotes exactly
    # like the sequential path's weak-typed python-float scalars
    hp_dyn = {
        k: np.asarray([m[k] for m in merged], dtype=dt)
        for k in dyn_names if k != "lam"
    }
    if "lam" in dyn_names:
        hp_dyn["lam"] = (
            float(problem.lam)
            if np.ndim(problem.lam) == 0
            else np.asarray(problem.lam, dtype=dt)
        )

    state0 = runner.init(jnp.asarray(z0))
    state = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n_runs,) + x.shape), state0
    )
    idx_j = jnp.asarray(idx_b[:, :steps], jnp.int32)
    pts = _record_points(steps, record_every)
    rec = _Recorder(problem.z_star, keep_snapshots)
    ref = _record_ref(rec.z_star, dt)
    prev = 0
    z_final = None
    for pt in pts:
        state = chunk_b(state, idx_j[:, prev:pt], hp_dyn)
        prev = pt
        z_final = z_read_b(state, hp_dyn)
        # reduced on the device as solve() reduces, so grid entries stay
        # bit-equal to their sequential solves
        z_final, dist2, cons = jax.device_get(
            (z_final, *metrics_b(z_final, ref))
        )
        rec.extend([pt], [dist2], [cons], [z_final])
    wall = time.perf_counter() - t0

    iters, dist2, cons, zs = rec.arrays()
    per_node = dense_doubles_per_iter(problem.graph, D)  # (N,)
    # rounds may differ per grid entry (e.g. a mudag gossip_rounds sweep)
    rounds_b = np.stack([_cumulative_rounds(spec, m, iters) for m in merged])
    doubles = rounds_b[:, :, None] * per_node[None, None, :]
    return SolveResult(
        method=method,
        comm=comm,
        iters=iters,
        dist2=dist2,
        consensus=cons,
        doubles_received=doubles,
        ints_received=np.zeros_like(doubles),
        wall_time=wall,
        z=np.asarray(z_final),
        state=state,
        zs=zs,
        extras={"batched": True, "grid": entries, "seeds": seeds_list},
    )


def _sweep_indices(indices, n_runs, steps, n, q, seeds_list) -> np.ndarray:
    """(B, >= steps, N) sample streams for a sweep, drawn or validated."""
    if indices is None:
        return np.stack(
            [draw_indices(steps, n, q, s) for s in seeds_list]
        )
    indices = np.asarray(indices)
    if indices.ndim == 2:
        indices = np.broadcast_to(
            indices[None], (n_runs,) + indices.shape
        )
    if (
        indices.ndim != 3
        or indices.shape[0] != n_runs
        or indices.shape[1] < steps
        or indices.shape[2] != n
    ):
        raise ValueError(
            f"indices must be (>= steps, N) or (B, >= steps, N) = "
            f"({n_runs}, >={steps}, {n}), got {indices.shape}"
        )
    return indices


def _solve_many_sparse_batched(
    problem, method, spec, *, steps, record_every, z0, keep_snapshots,
    comm_options, merged, entries, seeds, idx_b,
) -> SolveResult | None:
    """One vmapped relay scan for the whole sparse sweep, or None to decline.

    Declines (returns ``None``, sending ``solve_many`` to the sequential
    fallback) when the method has no batched sparse backend or the backend
    itself declines — e.g. ``engine="reference"``, the per-observer oracle
    loop. Results are bit-identical to the sequential path (the relay's
    message accounting is closed-form over the per-run nnz log, outside
    the scan). Capability (sparse backend present) is checked by
    ``solve_many`` before routing here.
    """
    if spec.sparse_run_many is None:
        return None
    if steps < 1:
        raise ValueError("steps must be >= 1")
    if record_every < 1:
        raise ValueError("record_every must be >= 1")
    t0 = time.perf_counter()
    sres = spec.sparse_run_many(
        problem, merged, steps, idx_b, z0, dict(comm_options or {})
    )
    if sres is None:
        return None
    wall = time.perf_counter() - t0
    pts = _record_points(steps, record_every)
    rec = _Recorder(problem.z_star, keep_snapshots)
    for pt in pts:
        rec.push(pt, np.stack([r.z_trace[pt] for r in sres]))
    iters, dist2, cons, zs = rec.arrays()
    sel = np.asarray(pts) - 1
    return SolveResult(
        method=method,
        comm="sparse",
        iters=iters,
        dist2=dist2,
        consensus=cons,
        doubles_received=np.stack([r.doubles_received[sel] for r in sres]),
        ints_received=np.stack([r.ints_received[sel] for r in sres]),
        wall_time=wall,
        z=np.stack([r.z_trace[-1] for r in sres]),
        state=None,
        zs=zs,
        extras={
            "batched": True,
            "grid": entries,
            "seeds": seeds,
            "per_run_extras": [
                {"z_trace": r.z_trace, "recon_max_err": r.recon_max_err}
                for r in sres
            ],
        },
    )


def _solve_many_sequential(
    problem, method, comm, *, steps, record_every, z0, keep_snapshots,
    comm_options, merged, entries, seeds, idx_b,
) -> SolveResult:
    """The documented fallback: one warm cached ``solve()`` per grid entry."""
    results = [
        solve(
            problem, method, comm, steps=steps, record_every=record_every,
            z0=z0, indices=idx_b[b], keep_snapshots=keep_snapshots,
            comm_options=comm_options, **merged[b],
        )
        for b in range(len(merged))
    ]
    r0 = results[0]
    return SolveResult(
        method=method,
        comm=comm,
        iters=r0.iters,
        dist2=np.stack([r.dist2 for r in results]),
        consensus=np.stack([r.consensus for r in results]),
        doubles_received=np.stack([r.doubles_received for r in results]),
        ints_received=np.stack([r.ints_received for r in results]),
        wall_time=sum(r.wall_time for r in results),
        z=np.stack([r.z for r in results]),
        state=[r.state for r in results],
        zs=(
            np.stack([r.zs for r in results])
            if keep_snapshots else None
        ),
        extras={
            "batched": False,
            "grid": entries,
            "seeds": seeds,
            "per_run_extras": [r.extras for r in results],
        },
    )


# ---------------------------------------------------------------------------
# Registry entries: DSBA / DSA (Algorithm 1 + Remark 5.1)
# ---------------------------------------------------------------------------


def _dsba_placeholder_cfg(problem: Problem, method: str) -> DSBAConfig:
    """Step config with hp placeholders (alpha/lam arrive traced at runtime).

    ``init_state`` reads only ``cfg.spec``; ``make_step_fn`` substitutes the
    traced values via its ``hp`` argument before any arithmetic touches the
    placeholders.
    """
    return DSBAConfig(spec=problem.spec, alpha=0.0, lam=0.0, method=method)


def _make_dsba_family(method: str, default_alpha: float) -> SolverSpec:
    """Registry entry for the stochastic family: shared step, both comms."""

    def init(problem, hp, z0):
        """SAGA-table warm start (Algorithm 1 line 1) at ``z0``."""
        return _dsba_init_state(
            _dsba_placeholder_cfg(problem, method), problem.data, z0
        )

    def step(problem, hp, comm):
        """Device-resident Algorithm-1 step via ``dsba.make_step_fn``.

        The mixing terms route through ``comm.matvec`` and the baked data
        arrays through ``comm.local`` inside ``make_step_fn``.
        """
        raw = _dsba_make_step_fn(
            _dsba_placeholder_cfg(problem, method), problem.data, problem.w,
            comm=comm,
        )

        def fn(state, i_t, hp_run):
            return raw(
                state, i_t,
                hp={"alpha": hp_run["alpha"], "lam": hp_run["lam"]},
            )

        return fn

    def z_of(problem, hp, comm):
        """Iterates live directly on the state."""
        return lambda state, hp_run: state.z

    def sparse_run(problem, hp, steps, indices, z0, options):
        """The Section-5.1 delta relay (``core.sparse_comm.run_sparse``)."""
        return _sparse_comm.run_sparse(
            DSBAConfig(
                spec=problem.spec, alpha=hp["alpha"], lam=problem.lam,
                method=method,
            ),
            problem.data,
            problem.graph,
            problem.w,
            steps,
            indices,
            z0=z0,
            **options,
        )

    def sparse_run_many(problem, merged, steps, idx_b, z0, options):
        """Vmapped relay sweep (``run_sparse_many``); declines "reference"."""
        options = dict(options)
        if options.pop("engine", "vectorized") != "vectorized":
            return None  # the oracle loop is per-run by construction
        return _sparse_comm.run_sparse_many(
            DSBAConfig(
                spec=problem.spec, alpha=merged[0]["alpha"],
                lam=problem.lam, method=method,
            ),
            problem.data,
            problem.graph,
            problem.w,
            steps,
            idx_b,
            [hp["alpha"] for hp in merged],
            z0=z0,
            **options,
        )

    return SolverSpec(
        name=method,
        init=init,
        step=step,
        z_of=z_of,
        defaults={"alpha": default_alpha},
        sparse_run=sparse_run,
        sparse_run_many=sparse_run_many,
        # the paper's monotone-operator framing is family-agnostic: the
        # SAGA table stores scalars for any linear-predictor operator,
        # including the bilinear saddle family (resolvent in closed form)
        problem_families=FAMILIES,
        # the fixed point z* = consensus root is W-independent and the
        # state is all leading-N leaves -> schedules, churn and per-node
        # regularization are all sound (docs/algorithm.md, docs/solvers.md)
        supports_schedule=True,
        supports_churn=True,
        supports_per_node_lam=True,
        # after a churn remap, re-enter the t=0 branch: the t>=1
        # difference recursion is stationary at ANY consensus point with
        # settled tables — only the step-0 psi (-alpha*phibar injection)
        # targets the new membership's root. Warm tables and iterates
        # are kept; phibar rows are node-local, so slicing/padding them
        # is exact (docs/algorithm.md).
        reanchor=lambda st: dataclasses.replace(
            st, step=jnp.zeros((), jnp.int32)
        ),
    )


register_solver(_make_dsba_family("dsba", default_alpha=0.5))
register_solver(_make_dsba_family("dsa", default_alpha=0.2))


# ---------------------------------------------------------------------------
# Registry entries: deterministic baselines (EXTRA / DLM / SSDA)
# ---------------------------------------------------------------------------


def _full_operator(spec: OperatorSpec, feats, labels, comm):
    """G(Z, lam): (N, D) -> (N, D), full local operator incl. regularizer.

    ``lam`` is a call-time argument (traced in the compiled runners), not a
    baked constant — a regularization-path sweep reuses one executable.
    The node-indexed data constants are read through ``comm.local`` at
    trace time, so under the sharded backend each device computes only its
    own node's operator (the whole map is node-local — no communication).
    """
    t = spec.tail_dim
    d = feats.shape[-1]

    def G(Z, lam):
        fe = comm.local(feats)
        la = comm.local(labels)
        head, tail = Z[:, :d], Z[:, d:]
        u = jnp.einsum("nqd,nd->nq", fe, head)
        tails = jnp.broadcast_to(tail[:, None, :], u.shape + (t,))
        g, tail_out = spec.coeff_and_tail(u, la, tails)
        out_head = jnp.einsum("nq,nqd->nd", g, fe) / fe.shape[1]
        if t:
            out = jnp.concatenate([out_head, tail_out.mean(1)], axis=1)
        else:
            out = out_head
        return out + lam * Z

    return G


def _dense_setup(problem: Problem):
    """(feats, labels, G-factory inputs) shared by the dense baselines."""
    feats = jnp.asarray(problem.data.dense())
    labels = jnp.asarray(problem.data.y)
    return feats, labels


def _extra_init(problem, hp, z0):
    """EXTRA state: (z, z_prev, g_prev, t) with a scan-compatible counter."""
    zeros = jnp.zeros_like(z0)
    return (z0, zeros, zeros, jnp.zeros((), jnp.int32))


def _extra_step(problem, hp, comm):
    """EXTRA (Shi et al. 2015a), eq. (47) form with first-step special case."""
    feats, labels = _dense_setup(problem)
    G = _full_operator(problem.spec, feats, labels, comm)
    dt = feats.dtype
    w_mix = comm.matvec(problem.w, dt)
    wt_mix = comm.matvec(w_tilde(problem.w), dt)

    def step(carry, i_t, hp_run):
        alpha, lam = hp_run["alpha"], hp_run["lam"]
        z, z_prev, g_prev, t = carry
        g = G(z, lam)
        z1 = jnp.where(
            t == 0,
            w_mix(z) - alpha * g,
            z + w_mix(z) - wt_mix(z_prev) - alpha * (g - g_prev),
        )
        return (z1, z, g, t + 1)

    return step


def _dlm_init(problem, hp, z0):
    """DLM state: (z, dual multipliers)."""
    return (z0, jnp.zeros_like(z0))


def _dlm_step(problem, hp, comm):
    """DLM (Ling et al. 2015): linearized decentralized ADMM."""
    feats, labels = _dense_setup(problem)
    G = _full_operator(problem.spec, feats, labels, comm)
    dt = feats.dtype
    lap_mix = comm.matvec(problem.graph.laplacian, dt)
    deg = jnp.asarray(problem.graph.degrees, dt)[:, None]

    def step(carry, i_t, hp_run):
        c, beta, lam = hp_run["c"], hp_run["beta"], hp_run["lam"]
        z, lam_dual = carry
        deg_l = comm.local(deg)
        grad_aug = G(z, lam) + lam_dual + 2.0 * c * lap_mix(z)
        z1 = z - grad_aug / (2.0 * c * deg_l + beta)
        lam1 = lam_dual + c * lap_mix(z1)
        return (z1, lam1)

    return step


# Single-slot share of the grad f* closure: the runner-cache build invokes
# the step and z_of factories back to back on the same (problem, hp), and
# the build is real work (Gram + N Cholesky factorizations for ridge). The
# slot holds the problem strongly, so the identity check cannot alias a
# recycled id; the value snapshots (data, lam, spec) at build time so
# mutating the problem invalidates the hit. lam is baked here — which is
# why the ssda SolverSpec sets ``bake_lam`` (the runner cache keys on lam).
_SSDA_CG_CACHE: list = []


def _ssda_conj_grad(problem: Problem, inner_newton: int):
    """grad f*_n read-out: Cholesky for ridge, damped Newton otherwise.

    Built once per (problem, inner_newton) — see ``_SSDA_CG_CACHE``.
    """
    for p, data_ref, lam_ref, spec_ref, inner_ref, cg in _SSDA_CG_CACHE:
        if (p is problem and p.data is data_ref and p.lam == lam_ref
                and p.spec == spec_ref and inner_ref == inner_newton):
            return cg
    cg = _build_ssda_conj_grad(problem, inner_newton)
    _SSDA_CG_CACHE[:] = [
        (problem, problem.data, problem.lam, problem.spec, inner_newton, cg)
    ]
    return cg


def _build_ssda_conj_grad(problem: Problem, inner_newton: int):
    """Construct the grad f*_n closure (the cached work behind the cache).

    The returned ``conj_grad(S, local)`` reads its baked per-node constants
    (Cholesky factors / features) through ``local`` — the comm backend's
    node-block view — so one cached closure serves both the dense runner
    (identity) and the sharded runner (this device's rows).
    """
    spec, lam = problem.spec, problem.lam
    if spec.tail_dim:
        raise NotImplementedError(
            "SSDA requires grad f*; the paper notes it does not apply to AUC"
        )
    feats = jnp.asarray(problem.data.dense())  # (N, q, d)
    labels = jnp.asarray(problem.data.y)
    n, q, d = feats.shape
    dt = feats.dtype

    if spec.kind == "ridge":
        # grad f_n(x) = A^T(Ax - y)/q + lam x ; grad f*_n(s) solves it = s
        gram = jnp.einsum("nqd,nqe->nde", feats, feats) / q
        gram = gram + lam * jnp.eye(d, dtype=dt)[None]
        rhs0 = jnp.einsum("nqd,nq->nd", feats, labels) / q
        chol = jax.vmap(jnp.linalg.cholesky)(gram)

        def conj_grad(S, local):  # (N, d) -> (N, d): x_n = grad f*_n(s_n)
            return jax.vmap(
                lambda L, r: jax.scipy.linalg.cho_solve((L, True), r)
            )(local(chol), S + local(rhs0))

    else:

        def conj_grad(S, local):
            # invert grad f_n via damped Newton with explicit per-node jacobians
            def one(fe, la, s):
                def gn(x):
                    u = fe @ x
                    g, _ = spec.coeff_and_tail(u, la, jnp.zeros((q, 0), dt))
                    return fe.T @ g / q + lam * x

                x = jnp.zeros((d,), dt)
                jac = jax.jacfwd(gn)
                for _ in range(inner_newton):
                    x = x - jnp.linalg.solve(jac(x), gn(x) - s)
                return x

            return jax.vmap(one)(local(feats), local(labels), S)

    return conj_grad


def _ssda_init(problem, hp, z0):
    """SSDA state: (momentum iterate, previous momentum iterate) on the dual."""
    n, d = problem.data.n_nodes, problem.data.d
    dt = jnp.asarray(problem.data.val).dtype
    zeros = jnp.zeros((n, d), dt)
    return (zeros, zeros)


def _ssda_step(problem, hp, comm):
    """SSDA (Scaman et al. 2017): accelerated gradient ascent on the dual."""
    conj_grad = _ssda_conj_grad(problem, int(hp["inner_newton"]))
    n = problem.data.n_nodes
    dt = jnp.asarray(problem.data.val).dtype
    imw_mix = comm.matvec(np.eye(n) - np.asarray(problem.w), dt)

    def step(carry, i_t, hp_run):
        eta, momentum = hp_run["eta"], hp_run["momentum"]
        m, m_prev = carry
        v = m + momentum * (m - m_prev)
        x = conj_grad(-v, comm.local)  # primal: grad f*(-(U Lambda)_n)
        m1 = v + eta * imw_mix(x)
        return (m1, m)

    return step


def _ssda_z_of(problem, hp, comm):
    """Primal read-out grad f*(-m): a real computation, not a field access.

    Jitted by the runner cache alongside the step — no inner jit here.
    """
    conj_grad = _ssda_conj_grad(problem, int(hp["inner_newton"]))
    return lambda state, hp_run: conj_grad(-state[0], comm.local)


register_solver(
    SolverSpec(
        name="extra",
        init=_extra_init,
        step=_extra_step,
        z_of=lambda problem, hp, comm: lambda state, hp_run: state[0],
        defaults={"alpha": 0.3},
    )
)
register_solver(
    SolverSpec(
        name="dlm",
        init=_dlm_init,
        step=_dlm_step,
        z_of=lambda problem, hp, comm: lambda state, hp_run: state[0],
        defaults={"c": 0.3, "beta": 1.0},
    )
)
register_solver(
    SolverSpec(
        name="ssda",
        init=_ssda_init,
        step=_ssda_step,
        z_of=_ssda_z_of,
        defaults={"eta": 0.05, "momentum": 0.5, "inner_newton": 8},
        # inner_newton is a Python loop count (structural); lam is baked
        # into the Cholesky / Newton factorization of grad f*.
        static_hp=("inner_newton",),
        bake_lam=True,
        # SSDA needs grad f*; the paper notes it does not apply to the
        # saddle families (AUC) — solve() now reports that as a typed
        # CapabilityError instead of a factory-time NotImplementedError.
        problem_families=MINIMIZATION_FAMILIES,
    )
)


# ---------------------------------------------------------------------------
# Registry entries: accelerated consensus (MUDAG) + communication sliding
# ---------------------------------------------------------------------------


def _fastmix_weight(w: np.ndarray) -> float:
    """The FastMix / Chebyshev momentum weight for mixing matrix ``w``.

    Liu & Morse (2011) accelerated gossip, as used by Mudag (Ye et al.
    2020):  x^{k+1} = (1 + eta_w) W x^k - eta_w x^{k-1}  with

        eta_w = (1 - sqrt(1 - sigma^2)) / (1 + sqrt(1 - sigma^2)),

    sigma the second-largest eigenvalue magnitude of W. Computed from the
    (static, numpy) mixing matrix at factory time — W's content is part of
    the runner cache key, so the baked scalar can never go stale.
    """
    eigs = np.sort(np.abs(np.linalg.eigvalsh(np.asarray(w, dtype=np.float64))))
    sigma = float(eigs[-2]) if eigs.size > 1 else 0.0
    sigma = min(max(sigma, 0.0), 1.0 - 1e-12)
    root = float(np.sqrt(1.0 - sigma * sigma))
    return (1.0 - root) / (1.0 + root)


def _make_fastmix(comm, w, dt):
    """K-round accelerated gossip through ``comm.matvec`` (K is traced).

    The Chebyshev combination (1 + eta_w) W x - eta_w x_prev has W's graph
    support plus the diagonal, so each inner round is exactly one
    ``comm.matvec`` application (one edge-colored ppermute sweep under the
    sharded backend) plus local arithmetic. ``lax.fori_loop`` with a
    traced trip count lowers to a while loop — K never triggers a
    retrace, which is what makes no-retrace K-sweeps possible.
    """
    w_mix = comm.matvec(w, dt)
    eta_w = _fastmix_weight(w)

    def fastmix(x, k):
        def body(_, carry):
            cur, prev = carry
            nxt = (1.0 + eta_w) * w_mix(cur) - eta_w * prev
            return (nxt, cur)

        cur, _ = jax.lax.fori_loop(0, k, body, (x, x))
        return cur

    return fastmix


def _mudag_init(problem, hp, z0):
    """MUDAG state: (x, y, tracked s, previous gradient, step counter)."""
    zeros = jnp.zeros_like(z0)
    return (z0, z0, zeros, zeros, jnp.zeros((), jnp.int32))


def _mudag_step(problem, hp, comm):
    """Mudag (Ye et al. 2020): Nesterov descent + K-round FastMix gossip.

    Gradient tracking keeps mean(s) = mean(G(y)) (both the tracking update
    and FastMix preserve the node mean), Nesterov momentum gives the
    sqrt(kappa) iteration rate, and each iteration spends 2K gossip rounds
    (one FastMix for the tracked gradient, one for the iterate) — reported
    by the ``comm_rounds`` hook as 2K dense exchanges per iteration.
    ``gossip_rounds`` arrives runtime-traced (cast to int32 here), so a
    K-sweep reuses one compiled runner.
    """
    feats, labels = _dense_setup(problem)
    G = _full_operator(problem.spec, feats, labels, comm)
    fastmix = _make_fastmix(comm, problem.w, feats.dtype)

    def step(carry, i_t, hp_run):
        eta, beta = hp_run["eta"], hp_run["momentum"]
        lam = hp_run["lam"]
        k = jnp.asarray(hp_run["gossip_rounds"]).astype(jnp.int32)
        x, y, s, g_prev, t = carry
        g = G(y, lam)
        s1 = fastmix(jnp.where(t == 0, g, s + g - g_prev), k)
        x1 = fastmix(y - eta * s1, k)
        y1 = x1 + beta * (x1 - x)
        return (x1, y1, s1, g, t + 1)

    return step


def _sliding_init(problem, hp, z0):
    """Sliding state: (z, tracked s, previous gradient, step counter)."""
    zeros = jnp.zeros_like(z0)
    return (z0, zeros, zeros, jnp.zeros((), jnp.int32))


def _sliding_step(problem, hp, comm):
    """Communication sliding (Lan-Lee-Zhou 2017 style, tracking variant).

    Multiple local primal steps per communication round: the mixing matvec
    is applied only when ``t % comm_period == 0`` (a ``jnp.where`` select,
    so one compiled step serves every phase); between rounds the nodes
    descend on their tracked gradient locally. Gradient tracking makes the
    periodic-mixing sequence B-connected, so the iterates still converge
    to the exact consensus root. The ``comm_rounds`` hook reports only the
    rounds actually taken — 2*ceil(iters/period) — which is the point:
    skipped rounds must show up as savings in ``doubles_received``. (Under
    the sharded backend the ppermute still executes physically every
    iteration and its result is discarded off-round; the *measured* bytes
    therefore reflect the SPMD program, the modeled doubles the algorithm.)
    """
    feats, labels = _dense_setup(problem)
    G = _full_operator(problem.spec, feats, labels, comm)
    w_mix = comm.matvec(problem.w, feats.dtype)

    def step(carry, i_t, hp_run):
        alpha, lam = hp_run["alpha"], hp_run["lam"]
        period = jnp.asarray(hp_run["comm_period"]).astype(jnp.int32)
        z, s, g_prev, t = carry
        g = G(z, lam)
        s1 = jnp.where(t == 0, g, s + g - g_prev)
        on_round = (t % period) == 0
        zc = jnp.where(on_round, w_mix(z), z)
        sc = jnp.where(on_round, w_mix(s1), s1)
        z1 = zc - alpha * sc
        return (z1, sc, g, t + 1)

    return step


def _mudag_rounds(hp, iters):
    """2K dense-exchange rounds per iteration (s-mix and x-mix FastMix)."""
    return 2 * int(round(hp["gossip_rounds"])) * np.asarray(iters)


def _sliding_rounds(hp, iters):
    """2*ceil(iters/period): z and s exchanged on communication rounds only."""
    period = max(1, int(round(hp["comm_period"])))
    return 2 * np.ceil(np.asarray(iters) / period)


register_solver(
    SolverSpec(
        name="mudag",
        init=_mudag_init,
        step=_mudag_step,
        z_of=lambda problem, hp, comm: lambda state, hp_run: state[0],
        # eta ~ 1/L (normalized rows give L <= 1 + lam); momentum ~
        # (sqrt(kappa)-1)/(sqrt(kappa)+1); K ~ O(1/sqrt(1-sigma)) gossip
        # rounds — benchmarks tune per task, these cover the paper's ridge
        defaults={"eta": 1.0, "momentum": 0.9, "gossip_rounds": 4},
        # Nesterov descent needs a convex minimization objective — the
        # saddle families (auc, bilinear) are excluded by capability
        problem_families=MINIMIZATION_FAMILIES,
        comm_rounds=_mudag_rounds,
        # gradient tracking preserves mean(s) = mean(g) under ANY doubly
        # stochastic W, and the FastMix weight is re-baked per segment
        # runner — schedules are sound. Churn needs the tracker RESET:
        # the telescoped tracker state encodes the departed membership's
        # mean gradient, so carrying it pins the survivors to the dead
        # system's root (docs/algorithm.md). The reanchor re-runs the
        # t=0 tracker seed (s = FastMix(g)) on the new membership, with
        # momentum restarted (y = x).
        supports_schedule=True,
        supports_churn=True,
        reanchor=lambda st: (
            st[0], st[0], jnp.zeros_like(st[2]), jnp.zeros_like(st[3]),
            jnp.zeros((), jnp.int32),
        ),
        # FastMix applies the matvec inside a traced-trip-count fori_loop:
        # a straggler buffer write there would escape the loop trace (the
        # link mask is a read-only capture, so link faults are fine)
        supports_stragglers=False,
    )
)
register_solver(
    SolverSpec(
        name="sliding",
        init=_sliding_init,
        step=_sliding_step,
        z_of=lambda problem, hp, comm: lambda state, hp_run: state[0],
        defaults={"alpha": 0.1, "comm_period": 4},
        problem_families=MINIMIZATION_FAMILIES,
        comm_rounds=_sliding_rounds,
        supports_schedule=True,  # tracking is W-agnostic (see mudag)
        supports_churn=True,
        # tracker reset on churn (see mudag); z itself carries over
        reanchor=lambda st: (
            st[0], jnp.zeros_like(st[1]), jnp.zeros_like(st[2]),
            jnp.zeros((), jnp.int32),
        ),
        # off-round iterations exchange nothing physically — a
        # last-delivered buffer updated by the where-gated matvec would
        # record "deliveries" on rounds that never happened
        supports_stragglers=False,
    )
)


# ---------------------------------------------------------------------------
# Registry entry: DSGDA — decentralized stochastic gradient descent ascent
# ---------------------------------------------------------------------------


def _dsgda_init(problem, hp, z0):
    """DSGDA state: (z, SAGA tables, table mean, tracker, v_prev, counter).

    Same warm start as Algorithm 1 line 1: the scalar tables hold the
    coefficient form of every component operator at z0, phibar their
    assembled mean — so the first variance-reduced estimate is exact. The
    gradient tracker and previous estimate start at zero; the step's
    ``t == 0`` branch seeds the tracker with the first estimate.
    """
    spec = problem.spec
    feats = jnp.asarray(problem.data.dense())  # (N, q, d)
    labels = jnp.asarray(problem.data.y)  # (N, q)
    t = spec.tail_dim
    d = feats.shape[-1]
    z0 = jnp.asarray(z0)
    head, tail = z0[:, :d], z0[:, d:]
    u = jnp.einsum("nqd,nd->nq", feats, head)
    tails = jnp.broadcast_to(tail[:, None, :], u.shape + (t,))
    g, tail_out = spec.coeff_and_tail(u, labels, tails)  # (N,q), (N,q,t)
    phibar_head = jnp.einsum("nq,nqd->nd", g, feats) / feats.shape[1]
    phibar = jnp.concatenate([phibar_head, tail_out.mean(1)], axis=1)
    zeros = jnp.zeros_like(z0)
    return (z0, g, tail_out, phibar, zeros, zeros, jnp.zeros((), jnp.int32))


def _dsgda_step(problem, hp, comm):
    """SAGA-variance-reduced decentralized SGDA with gradient tracking.

    One sampled component per node per iteration; the scalar-table
    estimator v = (g_i - table_i) x_i (+) tail delta + phibar + lam z is
    unbiased with variance shrinking as the tables fill in. The tracker
    y absorbs the node-local heterogeneity (plain mixed descent on v
    stalls at an O(alpha) bias because phibar_n is nonzero at the saddle
    — only the network mean vanishes); with tracking the fixed point is
    the exact regularized saddle and convergence is linear (the operator
    is strongly monotone once lam > 0). Descent on the primal block (step
    ``alpha``) and ascent on the dual block (step ``eta``) happen in one
    update because the tail carries -dL/dtheta.
    """
    spec = problem.spec
    feats, labels = _dense_setup(problem)  # (N, q, d), (N, q)
    t = spec.tail_dim
    q = feats.shape[1]
    d = feats.shape[-1]
    dt = feats.dtype
    w_mix = comm.matvec(problem.w, dt)
    head_mask = jnp.concatenate(
        [jnp.ones((d,), dt), jnp.zeros((t,), dt)]
    )

    def step(carry, i_t, hp_run):
        alpha, eta, lam = hp_run["alpha"], hp_run["eta"], hp_run["lam"]
        z, tab_g, tab_tail, phibar, y, v_prev, step_t = carry
        fe = comm.local(feats)
        la = comm.local(labels)
        n_loc = fe.shape[0]
        rows = jnp.take_along_axis(fe, i_t[:, None, None], axis=1)[:, 0, :]
        ys = jnp.take_along_axis(la, i_t[:, None], axis=1)[:, 0]
        head, tail = z[:, :d], z[:, d:]
        u = jnp.sum(rows * head, axis=-1)
        g, tail_out = spec.coeff_and_tail(u, ys, tail)  # (n,), (n,t)
        old_g = jnp.take_along_axis(tab_g, i_t[:, None], axis=1)[:, 0]
        old_tail = jnp.take_along_axis(
            tab_tail, i_t[:, None, None], axis=1
        )[:, 0, :]
        dg = g - old_g
        dtail = tail_out - old_tail
        delta = jnp.concatenate([dg[:, None] * rows, dtail], axis=1)
        v = delta + phibar + lam * z
        y1 = jnp.where(step_t == 0, v, w_mix(y) + v - v_prev)
        scale = alpha * head_mask + eta * (1.0 - head_mask)
        z1 = w_mix(z) - scale[None, :] * y1
        node = jnp.arange(n_loc)
        tab_g1 = tab_g.at[node, i_t].set(g)
        tab_tail1 = tab_tail.at[node, i_t].set(tail_out)
        return (
            z1, tab_g1, tab_tail1, phibar + delta / q, y1, v,
            step_t + 1,
        )

    return step


register_solver(
    SolverSpec(
        name="dsgda",
        init=_dsgda_init,
        step=_dsgda_step,
        z_of=lambda problem, hp, comm: lambda state, hp_run: state[0],
        defaults={"alpha": 0.3, "eta": 0.3},
        # descent-ascent targets the saddle families; the convex tasks
        # already have the full stochastic family (dsba/dsa)
        problem_families=("auc", "bilinear"),
        supports_schedule=True,  # tracking is W-agnostic (see mudag)
        supports_churn=True,
        # tracker reset on churn: keep the iterate and SAGA tables
        # (ElasticGossip remaps their node axes), zero the dual tracker
        # y and v_prev, and rewind t so the step re-seeds y = v on the
        # new membership (see mudag)
        reanchor=lambda st: (
            st[0], st[1], st[2], st[3],
            jnp.zeros_like(st[4]), jnp.zeros_like(st[5]),
            jnp.zeros((), jnp.int32),
        ),
    )
)


# ---------------------------------------------------------------------------
# Registry entry: personalized consensus-regularized descent
# ---------------------------------------------------------------------------


def _personal_init(problem, hp, z0):
    """Personalized-descent state: just the iterate block."""
    return (jnp.asarray(z0),)


def _personal_step(problem, hp, comm):
    """Consensus-regularized personalization (per-node lam, mu-coupling).

    Each node keeps its OWN solution of its locally regularized problem,
    coupled to its neighbors only through the graph-Laplacian penalty
    (mu/2) <Z, L Z>: the fixed point solves

        G_n(z_n) + lam_n z_n + mu (L Z)_n = 0      for every node n,

    the consensus-regularized personalization system (mu -> inf recovers
    exact consensus, mu = 0 fully local models). Plain forward descent on
    this monotone map — the point here is the problem geometry (per-node
    lam on non-iid shards), not acceleration. ``lam`` arrives traced and
    may be an (N,) array; the column reshape makes both shapes broadcast
    against the (N, D) iterate block.
    """
    feats, labels = _dense_setup(problem)
    G = _full_operator(problem.spec, feats, labels, comm)
    dt = feats.dtype
    lap_mix = comm.matvec(problem.graph.laplacian, dt)

    def step(carry, i_t, hp_run):
        alpha, mu, lam = hp_run["alpha"], hp_run["mu"], hp_run["lam"]
        (z,) = carry
        lam_col = lam[:, None] if jnp.ndim(lam) > 0 else lam
        g = G(z, 0.0) + lam_col * z
        return (z - alpha * (g + mu * lap_mix(z)),)

    return step


def personalized_root(
    problem: Problem, mu: float = 1.0, iters: int = 100, tol: float = 1e-12
) -> np.ndarray:
    """(N, D) root of the consensus-regularized personalization system.

    Damped Newton on the stacked map F(Z) = G(Z) + lam .* Z + mu L Z —
    the per-node-lam counterpart of ``Problem.solve_star()`` (which has
    no single centralized root to offer when lam varies per node). Use
    the SAME ``mu`` as the ``personal`` solver run being measured.
    """
    n, D = problem.graph.n, problem.dim
    comm = DenseComm(problem.graph)
    feats = jnp.asarray(problem.data.dense())
    labels = jnp.asarray(problem.data.y)
    dt = feats.dtype
    G = _full_operator(problem.spec, feats, labels, comm)
    lap = jnp.asarray(problem.graph.laplacian, dt)
    lam = problem.lam
    lam_col = (
        jnp.asarray(np.asarray(lam)[:, None], dt)
        if np.ndim(lam) > 0 else float(lam)
    )

    def F(zf):
        Z = zf.reshape(n, D)
        out = G(Z, 0.0) + lam_col * Z + mu * (lap @ Z)
        return out.reshape(-1)

    jacF = jax.jacfwd(F)
    z = jnp.zeros((n * D,), dt)
    eye = jnp.eye(n * D, dtype=dt)
    for _ in range(iters):
        f = F(z)
        nf = float(jnp.linalg.norm(f))
        if nf < tol:
            break
        delta = jnp.linalg.solve(jacF(z) + 1e-12 * eye, f)
        t = 1.0
        z_try = z - delta
        for _ in range(30):  # backtracking damping
            z_try = z - t * delta
            if float(jnp.linalg.norm(F(z_try))) <= (1.0 - 0.25 * t) * nf:
                break
            t *= 0.5
        z = z_try
    return np.asarray(z).reshape(n, D)


register_solver(
    SolverSpec(
        name="personal",
        init=_personal_init,
        step=_personal_step,
        z_of=lambda problem, hp, comm: lambda state, hp_run: state[0],
        defaults={"alpha": 0.2, "mu": 1.0},
        # forward descent needs a monotone minimization operator; the
        # saddle families couple blocks the Laplacian penalty ignores
        problem_families=MINIMIZATION_FAMILIES,
        # dense-only: an (N,) lam under shard_map would broadcast the
        # whole vector to every device block instead of its own entry
        supports_sharded=False,
        supports_schedule=True,
        supports_per_node_lam=True,
    )
)
