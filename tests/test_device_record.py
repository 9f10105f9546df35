"""The device-side recorder of ``solve()`` on the dense and sharded paths.

A dense or sharded solve runs its record points as one compiled program
(the runner's ``record``: a scan over record points, each a chunk of
``record_every`` iterations and the ``dist2``/consensus reductions) and
reads its results to the host once; a schedule or churn run does so once
a phase, against the phase's root. The oracle here is the per-point loop
it replaced, built from the same runner: its ``chunk`` program, ``z_read``
after every chunk, and ``_Recorder.push`` reducing the iterates on the
host in NumPy. Plain solves, fault plans (link drops, stragglers, both),
a two-segment schedule and a kill are checked against it.

The sharded cases need four devices, which only exist if
``--xla_force_host_platform_device_count`` was set before jax
initialized: the ``sharded`` fixture runs this file as a script in a
fresh interpreter with four forced host devices, and each sharded case
asserts on what that child printed.
"""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.core import mixing
from repro.core import solvers as S
from repro.data.synthetic import make_regression

REPO = Path(__file__).resolve().parents[1]
N, Q, D = 4, 6, 12
REC = 6
HP = {"dsba": {"alpha": 0.05}, "extra": {"alpha": 0.05}}
RTOL = {"float64": 1e-12, "float32": 1e-5}
CASES = [
    (method, steps, keep, root, dtype)
    for method in ("dsba", "extra")
    for steps in (24, 26)  # steps % record_every zero and non-zero
    for keep in (False, True)
    for root in (False, True)
    for dtype in ("float64", "float32")
]


def _case_id(case):
    method, steps, keep, root, dtype = case
    return (f"{method}-tail{steps % REC}-{'snap' if keep else 'nosnap'}-"
            f"{'root' if root else 'noroot'}-{dtype}")


def _problems():
    """{dtype: (problem with its root, the same without)}."""
    out = {}
    for dtype in ("float64", "float32"):
        data = make_regression(N, Q, D, k=4, seed=0, dtype=np.dtype(dtype))
        p = S.make_problem("ridge", data, mixing.ring_graph(N), lam=1e-2)
        p.solve_star()
        out[dtype] = (p, dataclasses.replace(p, z_star=None))
    return out


def _host_loop(problem, method, steps, indices, mesh=None, plan=None,
               keep=True):
    """The per-point loop, phase by phase: the runner's chunk, z_read after
    every chunk and a host push at every record point (against the phase's
    root). Returns (iters, dist2, consensus, zs, final z)."""
    spec = S.get_solver(method)
    hp = {**spec.defaults, **HP[method]}
    churn = None if plan is None else plan.churn
    if problem.schedule is None and churn is None:
        rows = np.arange(problem.graph.n)
        phases = [S._Phase(0, steps, problem, None, None, rows, rows)]
    else:
        phases = S._resolve_phases(problem, steps, churn)
    pts = S._record_points(steps, REC)
    rec = S._Recorder(problem.z_star, keep_snapshots=keep)
    state = z = None
    for ph in phases:
        p = ph.problem
        link, strag = S._static_fault_masks(plan, p.graph, ph.end - ph.start,
                                            start=ph.start)
        runner = S._get_runner(spec, p, hp, mesh=mesh,
                               link=link is not None,
                               straggler=strag is not None)
        hp_dyn = S._dynamic_hp(spec, p, hp)
        if state is None:
            carry = runner.init(np.zeros((p.graph.n, p.dim),
                                         p.data.val.dtype))
        else:
            carry = runner.carry_of(S._elastic_remap(state, ph, n_prev, spec))
        xs = S._scan_inputs(indices[ph.start:ph.end][:, ph.cols], link, strag)
        prev = ph.start
        for mk in sorted({pt for pt in pts if ph.start < pt <= ph.end}
                         | {ph.end}):
            lo, hi = prev - ph.start, mk - ph.start
            carry = runner.chunk(carry,
                                 jax.tree.map(lambda a: a[lo:hi], xs), hp_dyn)
            prev = mk
            z = runner.z_read(carry, hp_dyn)
            if mk in pts:
                rec.push(mk, z, z_star=p.z_star)
        state, n_prev = runner.state_of(carry), p.graph.n
    return (*rec.arrays(), np.asarray(z))


def _gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    if not b.size:
        return 0.0
    scale = np.maximum(np.abs(b), np.abs(b).max() * 1e-3)
    return float(np.max(np.abs(a - b) / scale))


def _run_gaps(problem, method, steps, keep, mesh=None, plan=None):
    """Relative gaps of solve() against the host loop, and host_reads."""
    indices = S.draw_indices(steps, problem.graph.n, Q, 7)
    opts = {}
    if mesh is not None:
        opts["mesh"] = mesh
    if plan is not None:
        opts["fault_plan"] = plan
    res = S.solve(problem, method,
                  comm="dense" if mesh is None else "sharded", steps=steps,
                  record_every=REC, indices=indices, keep_snapshots=keep,
                  comm_options=opts or None, **HP[method])
    iters, dist2, cons, zs, z = _host_loop(problem, method, steps, indices,
                                           mesh, plan, keep)
    return {
        "iters": [int(i) for i in res.iters] == [int(i) for i in iters],
        "dist2": _gap(res.dist2, dist2),
        "dist2_len": len(res.dist2),
        "consensus": _gap(res.consensus, cons),
        "z": _gap(res.z, z),
        "zs": None if res.zs is None else _gap(res.zs, zs),
        "host_reads": res.extras["host_reads"],
    }


def _gaps(case, problems, mesh=None):
    method, steps, keep, root, dtype = case
    problem = problems[dtype][0 if root else 1]
    return _run_gaps(problem, method, steps, keep, mesh)


def _check(case, g):
    method, steps, keep, root, dtype = case
    rtol = RTOL[dtype]
    assert g["iters"]
    assert g["dist2_len"] == (len(S._record_points(steps, REC)) if root
                              else 0)
    assert g["dist2"] <= rtol
    assert g["consensus"] <= rtol
    assert g["z"] <= rtol
    assert (g["zs"] is not None) == keep
    if keep:
        assert g["zs"] <= rtol
    assert g["host_reads"] == 1


@pytest.fixture(scope="module")
def problems():
    return _problems()


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_dense_record_matches_host_loop(case, problems):
    _check(case, _gaps(case, problems))


@pytest.fixture(scope="module")
def sharded():
    """This file run as a script on four forced host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    proc = subprocess.run([sys.executable, __file__], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_sharded_record_matches_host_loop(case, sharded):
    _check(case, sharded[_case_id(case)])


@pytest.mark.parametrize("steps", [40, 50])
def test_checkpoint_segments_read_once_each(tmp_path, problems, steps):
    """Under a checkpoint a solve reads once a segment (a segment ends at
    each multiple of ``checkpoint.every``) and records what an
    uninterrupted solve records; a resumed solve reads once."""
    from repro.ckpt import CheckpointSpec

    problem = problems["float64"][0]
    kw = dict(steps=steps, record_every=10, seed=3, alpha=0.05)
    full = S.solve(problem, "dsba", **kw)
    assert full.extras["host_reads"] == 1
    ck = tmp_path / "ck"
    seg = S.solve(problem, "dsba", checkpoint=CheckpointSpec(ck, every=20),
                  **kw)
    assert seg.extras["host_reads"] == -(-steps // 20)
    for field in ("iters", "dist2", "consensus", "z"):
        np.testing.assert_array_equal(getattr(seg, field),
                                      getattr(full, field))
    res = S.solve(problem, "dsba", resume=str(ck), **kw)
    assert res.extras["host_reads"] == 1
    np.testing.assert_array_equal(res.dist2, full.dist2)
    np.testing.assert_array_equal(res.z, full.z)


LINK = S.LinkFault(p=0.2, seed=7)
STRAG = S.StragglerSpec(p=0.4, max_staleness=3, seed=5)
SWITCH = 10  # a schedule switch between the record points 6 and 12
PHASED = {  # name: (fault plan, scheduled, host reads)
    "link": (S.FaultPlan(link=LINK), False, 1),
    "straggler": (S.FaultPlan(straggler=STRAG), False, 1),
    "link+straggler": (S.FaultPlan(link=LINK, straggler=STRAG), False, 1),
    "schedule": (None, True, 2),
    "kill": (S.FaultPlan(churn=S.ChurnPlan(
        (S.ChurnEvent(at=15, kind="kill", nodes=(3,)),))), False, 2),
}


def _phased_gaps(name, problems, mesh=None):
    """A dsba solve of 26 iterations under a fault plan or a two-segment
    schedule (the ring with edge (0, 1) moved to (0, 2) from SWITCH on),
    against the host loop."""
    plan, scheduled, _ = PHASED[name]
    problem = problems["float64"][0]
    if scheduled:
        g2 = mixing.Graph(N, ((0, 2), (0, 3), (1, 2), (2, 3)))
        problem = dataclasses.replace(
            problem, schedule=((0, problem.graph), (SWITCH, g2)))
    return _run_gaps(problem, "dsba", 26, name != "kill", mesh, plan)


def _check_phased(name, g):
    rtol = RTOL["float64"]
    assert g["iters"]
    assert g["dist2_len"] == len(S._record_points(26, REC))
    assert g["dist2"] <= rtol
    assert g["consensus"] <= rtol
    assert g["z"] <= rtol
    assert (g["zs"] is not None) == (name != "kill")
    if g["zs"] is not None:
        assert g["zs"] <= rtol
    assert g["host_reads"] == PHASED[name][2]


@pytest.mark.parametrize("name", list(PHASED))
def test_dense_fault_and_phase_record_matches_host_loop(name, problems):
    """Fault runs read once; phased runs once a phase, each phase's
    records against its own root, a phase end off the record points
    dropped."""
    _check_phased(name, _phased_gaps(name, problems))


@pytest.mark.parametrize("name", ["link", "schedule"])
def test_sharded_fault_and_phase_record_matches_host_loop(name, sharded):
    _check_phased(name, sharded[name])


def _sharded_main():
    from repro.launch.mesh import make_node_mesh

    jax.config.update("jax_enable_x64", True)
    problems = _problems()
    mesh = make_node_mesh(N)
    out = {_case_id(c): _gaps(c, problems, mesh) for c in CASES}
    for name in ("link", "schedule"):
        out[name] = _phased_gaps(name, problems, mesh)
    print(json.dumps(out))


if __name__ == "__main__":
    _sharded_main()
