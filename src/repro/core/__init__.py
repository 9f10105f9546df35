"""The paper's primary contribution and its substrate.

DSBA (Decentralized Stochastic Backward Aggregation) plus monotone
operators, mixing matrices, deterministic baselines, the sparse
communication relay, and the pod-axis gossip generalization. The public
run entrypoint is ``core.solvers.solve`` (Problem + SolverSpec registry);
``dsba.run`` and the ``baselines.run_*`` wrappers are deprecated shims.
"""
from repro.launch.compile_cache import enable_persistent_cache

# Persistent XLA compile cache: every entrypoint that imports repro.core
# (tests, benchmarks, notebooks) shares on-disk compiled executables across
# processes. Opt out with REPRO_NO_COMPILE_CACHE=1; JAX's own
# JAX_COMPILATION_CACHE_DIR, where set, is where it goes. See
# launch/compile_cache.py for policy.
enable_persistent_cache()

from repro.core.operators import OperatorSpec  # noqa: F401,E402
from repro.core.dsba import (  # noqa: F401,E402
    DSBAConfig, DSBAState, dsba_step, init_state,
)
from repro.core.solvers import (  # noqa: F401,E402
    CapabilityError, Problem, SolveResult, SolverCapabilities, SolverSpec,
    available_solvers, clear_runner_caches, get_solver, make_problem,
    register_solver, runner_cache_stats, solve, solve_many,
)
from repro.core import mixing, baselines, reference, solvers  # noqa: F401,E402
