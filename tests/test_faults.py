"""Unified fault injection & recovery (ISSUE 10 acceptance).

Four claims:
  1. fault plans are validated up front — illegal combinations raise a
     typed error BEFORE any factory or compile runs, and p=0 plans are
     BIT-equal to plan-free runs (by routing: an all-delivered mask
     collapses to the plain compiled runner);
  2. link faults and stragglers degrade gracefully on the dense backend
     (finite, biased-not-divergent, delivered-only accounting) and the
     staleness bound genuinely bounds every node's delivery gap;
  3. churn recovery: a kill under ``comm="sparse"`` re-derives the relay
     per membership segment, parity-matches dense churn, and reaches the
     survivor root; mudag's tracker reanchor reconverges geometrically
     where the no-reanchor run plateaus (regression-pinned);
  4. ``solve(..., checkpoint=...)`` + ``solve(..., resume=...)`` is
     bit-equal to an uninterrupted run for dsba/dsa on dense and sparse.

The sharded legs of the same claims run under the forced-8-device tier
(``tests/multidevice/test_faults_inner.py``). Exhaustive drop-rate x
method sweeps are ``slow``-marked.
"""
import functools

import numpy as np
import pytest

from repro.ckpt import CheckpointSpec, committed_steps
from repro.core import mixing
from repro.core.solvers import (
    ChurnEvent,
    ChurnPlan,
    FaultPlan,
    LinkFault,
    StragglerSpec,
    get_solver,
    make_problem,
    solve,
)
from repro.data.synthetic import make_regression
from repro.ft.faults import straggler_delivered_mask

N, Q, D, K = 8, 12, 6, 3


@functools.lru_cache(maxsize=None)
def _problem(n=N):
    data = make_regression(n, Q, D, k=K, seed=0)
    p = make_problem("ridge", data, mixing.ring_graph(n), lam=1e-2)
    p.solve_star()
    return p


def _solve(p, method="dsba", comm="dense", plan=None, **kw):
    kw.setdefault("steps", 120)
    kw.setdefault("record_every", 30)
    kw.setdefault("seed", 1)
    opts = {"fault_plan": plan} if plan is not None else None
    return solve(p, method, comm=comm, comm_options=opts, **kw)


# ---------------------------------------------------------------------------
# 1. up-front validation + p=0 routing bit-equality
# ---------------------------------------------------------------------------


def test_illegal_fault_combinations_raise_up_front():
    import dataclasses

    p = _problem()
    kill = ChurnPlan((ChurnEvent(at=10, kind="kill", nodes=(7,)),))
    # schedule x fault_plan
    ps = dataclasses.replace(p, schedule=((0, p.graph),))
    with pytest.raises(ValueError, match="schedule and a fault_plan"):
        _solve(ps, plan=FaultPlan(link=LinkFault(p=0.1)))
    # churn x node/edge-targeted families (ids relabel across segments)
    with pytest.raises(ValueError, match="scheduled link faults"):
        _solve(p, plan=FaultPlan(
            churn=kill, link=LinkFault(edges=((0, 1),), at=(5,))))
    with pytest.raises(ValueError, match="straggler node subset"):
        _solve(p, plan=FaultPlan(
            churn=kill, straggler=StragglerSpec(p=0.5, nodes=(0,))))
    with pytest.raises(ValueError, match="keep_snapshots"):
        _solve(p, plan=FaultPlan(churn=kill), keep_snapshots=True)
    # checkpoint/resume exclusions
    ck = CheckpointSpec("/tmp/nonexistent-ck", every=30)
    with pytest.raises(ValueError, match="not checkpointable"):
        solve(p, "dsba", comm="sharded", steps=60, checkpoint=ck)
    with pytest.raises(ValueError, match="fault_plan"):
        _solve(p, plan=FaultPlan(link=LinkFault(p=0.1)), checkpoint=ck)
    with pytest.raises(ValueError, match="multiple of"):
        solve(p, "dsba", steps=60, record_every=25,
              checkpoint=CheckpointSpec("/tmp/nonexistent-ck", every=30))
    # the plan itself validates its fields
    with pytest.raises(ValueError, match="at least one fault family"):
        FaultPlan()
    with pytest.raises(ValueError, match="not in \\[0, 1\\]"):
        LinkFault(p=1.5)
    with pytest.raises(ValueError, match="max_staleness"):
        StragglerSpec(p=0.5, max_staleness=0)
    with pytest.raises(ValueError, match="edges without"):
        LinkFault(edges=((0, 1),))


@pytest.mark.parametrize("comm", ["dense", "sparse"])
def test_p0_plan_bit_equal_to_plan_free(comm):
    """An all-delivered plan routes through the SAME compiled runner as a
    plan-free run — bit-equality by routing, not by masked arithmetic."""
    p = _problem()
    base = _solve(p, comm=comm)
    plan = FaultPlan(link=LinkFault(p=0.0),
                     straggler=(None if comm == "sparse"
                                else StragglerSpec(p=0.0)))
    res = _solve(p, comm=comm, plan=plan)
    assert np.array_equal(np.asarray(base.z), np.asarray(res.z))
    assert np.array_equal(np.asarray(base.dist2), np.asarray(res.dist2))
    np.testing.assert_array_equal(base.doubles_received, res.doubles_received)
    # the p=0 record still reports the accounting, with zero drop rate
    f = res.extras["faults"]
    assert f["drop_rate"] == 0.0
    inj = f.get("injected_messages", f.get("injected_broadcasts"))
    dlv = f.get("delivered_messages", f.get("delivered_broadcasts"))
    assert inj == dlv > 0


# ---------------------------------------------------------------------------
# 2. graceful degradation + delivered-only accounting
# ---------------------------------------------------------------------------


def test_dense_link_faults_degrade_gracefully():
    """p=0.2 drops: the run stays finite and converges to a biased
    neighborhood (row-renormalization keeps the masked W stochastic),
    and the doubles accounting counts only delivered messages."""
    p = _problem()
    base = _solve(p, steps=400, record_every=100)
    res = _solve(p, steps=400, record_every=100,
                 plan=FaultPlan(link=LinkFault(p=0.2, seed=7)))
    assert np.isfinite(res.z).all() and np.isfinite(res.dist2).all()
    assert base.dist2[-1] < 1e-12          # fault-free converges hard
    assert 1e-12 < res.dist2[-1] < 1.0     # faulted: biased, not divergent
    f = res.extras["faults"]
    assert 0 < f["delivered_messages"] < f["injected_messages"]
    assert 0.1 < f["drop_rate"] < 0.3
    assert res.doubles_received[-1].sum() < base.doubles_received[-1].sum()


def test_dense_stragglers_and_composition():
    """Stragglers alone and composed with link faults: finite runs,
    composed delivery is the AND of the two masks (strictly fewer
    messages than either family alone)."""
    p = _problem()
    link = LinkFault(p=0.2, seed=3)
    strag = StragglerSpec(p=0.4, max_staleness=3, seed=5)
    r_s = _solve(p, steps=200, record_every=50, plan=FaultPlan(straggler=strag))
    r_l = _solve(p, steps=200, record_every=50, plan=FaultPlan(link=link))
    r_b = _solve(p, steps=200, record_every=50,
                 plan=FaultPlan(link=link, straggler=strag))
    for r in (r_s, r_l, r_b):
        assert np.isfinite(r.z).all() and np.isfinite(r.dist2).all()
    both = r_b.extras["faults"]["delivered_messages"]
    assert both < r_s.extras["faults"]["delivered_messages"]
    assert both < r_l.extras["faults"]["delivered_messages"]


@pytest.mark.parametrize("link,straggler",
                         [(True, False), (False, True), (True, True)])
def test_fault_mix_runs_at_highest_precision(link, straggler):
    """Every product of the faulty dense mix runs at full precision, as
    the plain mix does: the TPU's default would round f32 operands to
    bf16 (the CPU ignores the setting, so only the program shows it)."""
    import jax
    from jax import lax

    from repro.core.comm import FaultyDenseComm

    g = mixing.ring_graph(4)
    comm = FaultyDenseComm(g, link, straggler)
    mix = comm.matvec(mixing.laplacian_mixing(g), np.float32)

    def run(x, mask, deliv, buf):
        comm.begin_step(mask, deliv, (buf,))
        out = mix(x)
        comm.end_step()
        return out

    x = np.ones((4, 3), np.float32)
    jaxpr = jax.make_jaxpr(run)(x, np.ones((4, 4), bool), np.ones(4, bool), x)
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert dots
    for e in dots:
        assert e.params["precision"] == (lax.Precision.HIGHEST,) * 2


def test_staleness_bound_is_enforced():
    """Even at p=0.95 no node goes more than max_staleness iterations
    without a delivery (the forced catch-up), and the first iteration
    always delivers (no uninitialized buffer reads)."""
    for bound in (1, 2, 4):
        m = straggler_delivered_mask(
            StragglerSpec(p=0.95, max_staleness=bound, seed=9), 6, 300
        )
        assert m[0].all()
        gaps = np.zeros(6, dtype=int)
        for t in range(1, 300):
            gaps = np.where(m[t], 0, gaps + 1)
            assert (gaps <= bound).all()
        assert not m.all()  # the fault actually fired


# ---------------------------------------------------------------------------
# 3. churn recovery: sparse kill parity + tracker reanchor
# ---------------------------------------------------------------------------


def test_sparse_kill_parity_with_dense_and_survivor_root():
    """ISSUE 10 acceptance: a kill under comm="sparse" re-derives the
    relay per membership segment, chains via state0 with the step-0
    reanchor, parity-matches the dense churn run, and reaches the
    survivor system's root to <= 1e-9."""
    p = _problem()
    plan = ChurnPlan((ChurnEvent(at=150, kind="kill", nodes=(5,)),))
    kw = dict(steps=600, record_every=50, seed=1,
              comm_options={"fault_plan": plan})
    rd = solve(p, "dsba", comm="dense", **kw)
    rs = solve(p, "dsba", comm="sparse", **kw)
    assert rs.z.shape == (N - 1, rd.z.shape[1])
    np.testing.assert_allclose(np.asarray(rs.z), np.asarray(rd.z),
                               atol=1e-11, rtol=0)
    np.testing.assert_allclose(np.asarray(rs.dist2), np.asarray(rd.dist2),
                               atol=1e-11, rtol=1e-6)
    assert rs.dist2[-1] <= 1e-9  # the survivor root (per-phase z_star)
    assert rs.extras["churn_rows"] == N
    # the relay's modeled traffic is still the closed-form count
    assert np.isfinite(rs.doubles_received).all()
    assert (np.diff(rs.doubles_received.sum(axis=1)) > 0).all()


def test_sparse_join_parity_with_dense():
    p = _problem()
    plan = ChurnPlan((ChurnEvent(
        at=100, kind="join", n_new=2, seed_from=0,
        graph=mixing.ring_graph(N + 2)),))
    kw = dict(steps=300, record_every=50, seed=1,
              comm_options={"fault_plan": plan})
    rd = solve(p, "dsba", comm="dense", **kw)
    rs = solve(p, "dsba", comm="sparse", **kw)
    assert rs.z.shape == (N + 2, rd.z.shape[1])
    np.testing.assert_allclose(np.asarray(rs.z), np.asarray(rd.z),
                               atol=1e-11, rtol=0)


def test_mudag_kill_reanchor_reconverges_geometrically():
    """The tracking family's churn gap (ROADMAP item 2): with the tracker
    reanchor (s, g_prev zeroed, t rewound so the step re-seeds the
    tracker from the survivors' gradients) the kill run reconverges to
    the survivor root; without it, the telescoped tracker still encodes
    the departed node's gradients and the run PLATEAUS (regression-pinned
    by temporarily nulling the spec's reanchor hook)."""
    import jax.numpy as jnp  # noqa: F401  (reanchor lambdas use jnp)

    p = _problem()
    plan = ChurnPlan((ChurnEvent(at=150, kind="kill", nodes=(5,)),))
    kw = dict(steps=600, record_every=50, seed=1, eta=0.5, momentum=0.5,
              comm_options={"fault_plan": plan})
    res = solve(p, "mudag", comm="dense", **kw)
    assert res.dist2[-1] < 1e-12  # geometric reconvergence

    spec = get_solver("mudag")
    orig = spec.reanchor
    object.__setattr__(spec, "reanchor", None)
    try:
        res_no = solve(p, "mudag", comm="dense", **kw)
    finally:
        object.__setattr__(spec, "reanchor", orig)
    # plateau: orders of magnitude off the root, and flat at the tail
    assert res_no.dist2[-1] > 1e-6
    assert abs(res_no.dist2[-1] - res_no.dist2[-2]) < 0.1 * res_no.dist2[-1]


@pytest.mark.parametrize("method,hp", [
    ("sliding", dict(alpha=0.1, comm_period=4)),
    ("dsgda", dict()),
])
def test_tracking_family_churn_stays_finite_and_improves(method, hp):
    """sliding/dsgda share the reanchor contract: the kill run keeps
    descending after the event instead of locking onto the dead
    system's root."""
    if method == "dsgda":
        data = make_regression(6, 10, 5, k=3, seed=2)
        p = make_problem("auc", data, mixing.ring_graph(6), lam=1e-2)
        p.solve_star()
        plan = ChurnPlan((ChurnEvent(at=200, kind="kill", nodes=(4,)),))
        res = solve(p, method, steps=800, record_every=100, seed=3,
                    comm_options={"fault_plan": plan}, **hp)
    else:
        p = _problem()
        plan = ChurnPlan((ChurnEvent(at=150, kind="kill", nodes=(5,)),))
        res = solve(p, method, steps=600, record_every=50, seed=1,
                    comm_options={"fault_plan": plan}, **hp)
    assert np.isfinite(res.dist2).all()
    assert res.dist2[-1] < 1e-3 and res.dist2[-1] < res.dist2[-3]


def test_churn_composes_with_link_faults():
    """Churn + probabilistic link faults in ONE plan: each membership
    segment re-derives its masks deterministically; accounting reports
    both the relabeled rows and the delivered totals."""
    p = _problem()
    plan = FaultPlan(
        churn=ChurnPlan((ChurnEvent(at=60, kind="kill", nodes=(7,)),)),
        link=LinkFault(p=0.15, seed=11),
    )
    res = _solve(p, steps=160, record_every=40, plan=plan)
    assert res.z.shape[0] == N - 1
    assert np.isfinite(res.z).all()
    assert res.extras["churn_rows"] == N
    f = res.extras["faults"]
    assert 0 < f["delivered_messages"] < f["injected_messages"]


# ---------------------------------------------------------------------------
# 4. checkpoint / resume bit-equality
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["dsba", "dsa"])
@pytest.mark.parametrize("comm", ["dense", "sparse"])
def test_checkpoint_resume_bit_equal(tmp_path, method, comm):
    """Interrupt at step 40 of 60, resume from the newest committed
    checkpoint: iterate trace, recorder arrays, AND accounting are
    bit-equal to the uninterrupted run (the sample streams are
    prefix-stable in steps, so the restored position lines up exactly)."""
    p = _problem()
    kw = dict(record_every=10, seed=3)
    full = solve(p, method, comm=comm, steps=60, **kw)

    ck = tmp_path / f"{method}_{comm}"
    solve(p, method, comm=comm, steps=40,
          checkpoint=CheckpointSpec(ck, every=20), **kw)
    assert committed_steps(ck) == [20, 40]
    res = solve(p, method, comm=comm, steps=60, resume=str(ck), **kw)

    assert np.array_equal(np.asarray(full.z), np.asarray(res.z))
    assert np.array_equal(np.asarray(full.dist2), np.asarray(res.dist2))
    np.testing.assert_array_equal(full.iters, res.iters)
    np.testing.assert_array_equal(full.doubles_received, res.doubles_received)
    np.testing.assert_array_equal(full.ints_received, res.ints_received)


def test_resume_validates_method_and_comm(tmp_path):
    p = _problem()
    ck = tmp_path / "ck"
    solve(p, "dsba", steps=40, record_every=10, seed=3,
          checkpoint=CheckpointSpec(ck, every=20))
    with pytest.raises(ValueError, match="method"):
        solve(p, "dsa", steps=60, record_every=10, seed=3, resume=str(ck))
    with pytest.raises(ValueError, match="comm"):
        solve(p, "dsba", comm="sparse", steps=60, record_every=10, seed=3,
              resume=str(ck))
    with pytest.raises(ValueError, match="beyond steps"):
        solve(p, "dsba", steps=30, record_every=10, seed=3, resume=str(ck))
    with pytest.raises(ValueError, match="no committed checkpoint"):
        solve(p, "dsba", steps=60, record_every=10, seed=3,
              resume=str(tmp_path / "empty"))


def test_resume_at_completed_run_returns_final_state(tmp_path):
    """Resuming a run whose newest checkpoint IS the final step performs
    zero further iterations and still returns the full result."""
    p = _problem()
    ck = tmp_path / "done"
    kw = dict(record_every=10, seed=3)
    full = solve(p, "dsba", steps=40, **kw)
    solve(p, "dsba", steps=40, checkpoint=CheckpointSpec(ck, every=20), **kw)
    res = solve(p, "dsba", steps=40, resume=str(ck), **kw)
    assert np.array_equal(np.asarray(full.z), np.asarray(res.z))
    assert np.array_equal(np.asarray(full.dist2), np.asarray(res.dist2))


# ---------------------------------------------------------------------------
# slow: exhaustive drop-rate x method sweeps
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("method,hp", [
    ("dsba", dict()), ("dsa", dict()), ("mudag", dict(eta=0.5, momentum=0.5)),
])
@pytest.mark.parametrize("pdrop", [0.1, 0.2, 0.4])
def test_degradation_sweep_dense(method, hp, pdrop):
    """Dense degradation is monotone-ish in p and never divergent: the
    bias neighborhood grows with the drop rate but every run stays
    finite with delivered-only accounting below the no-fault count."""
    p = _problem()
    base = solve(p, method, steps=400, record_every=100, seed=1, **hp)
    res = solve(p, method, steps=400, record_every=100, seed=1,
                comm_options={"fault_plan": FaultPlan(
                    link=LinkFault(p=pdrop, seed=7))}, **hp)
    assert np.isfinite(res.dist2).all()
    assert res.dist2[-1] < 10.0
    assert res.doubles_received[-1].sum() < base.doubles_received[-1].sum()


@pytest.mark.slow
@pytest.mark.parametrize("method", ["dsba", "dsa"])
def test_sparse_short_horizon_link_faults(method):
    """Short-horizon sparse link faults: the suppressed-broadcast model
    runs finite and its modeled traffic stays below the fault-free relay
    (docs/solvers.md documents the long-horizon drift caveat)."""
    p = _problem()
    base = solve(p, method, comm="sparse", steps=80, record_every=20, seed=1)
    res = solve(p, method, comm="sparse", steps=80, record_every=20, seed=1,
                comm_options={"fault_plan": FaultPlan(
                    link=LinkFault(p=0.1, seed=7))})
    assert np.isfinite(res.z).all()
    assert res.doubles_received[-1].sum() <= base.doubles_received[-1].sum()
    f = res.extras["faults"]
    assert 0 < f["delivered_broadcasts"] < f["injected_broadcasts"]
