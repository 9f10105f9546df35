"""The device-side recorder of ``solve()`` on the dense and sharded paths.

A plain dense or sharded solve runs its record points as one compiled
program (the runner's ``record``: a scan over record points, each a chunk
of ``record_every`` iterations and the ``dist2``/consensus reductions) and
reads its results to the host once. The oracle here is the per-point loop
it replaced: the runner's ``chunk`` program, ``z_read`` after every chunk,
and ``_Recorder.push`` reducing the iterates on the host in NumPy.

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


def _host_loop(problem, method, comm, steps, indices, mesh=None):
    """The per-point loop: chunk, z_read and a host push at every point."""
    spec = S.get_solver(method)
    hp = {**spec.defaults, **HP[method]}
    if comm == "dense":
        runner = S._get_dense_runner(spec, problem, hp)
    else:
        runner = S._get_sharded_runner(spec, problem, hp, mesh)
    hp_dyn = S._dynamic_hp(spec, problem, hp)
    idx = np.asarray(indices[:steps], np.int32)
    state = runner.init(np.zeros((N, problem.dim), problem.data.val.dtype))
    rec = S._Recorder(problem.z_star, keep_snapshots=True)
    prev = 0
    for pt in S._record_points(steps, REC):
        state = runner.chunk(state, idx[prev:pt], hp_dyn)
        prev = pt
        rec.push(pt, runner.z_read(state, hp_dyn))
    return rec.arrays()


def _gaps(case, problems, comm, mesh=None):
    """Relative gaps of solve() against the host loop, and host_reads."""
    method, steps, keep, root, dtype = case
    problem = problems[dtype][0 if root else 1]
    indices = S.draw_indices(steps, N, Q, 7)
    opts = {} if mesh is None else {"comm_options": {"mesh": mesh}}
    res = S.solve(problem, method, comm=comm, steps=steps, record_every=REC,
                  indices=indices, keep_snapshots=keep, **HP[method], **opts)
    iters, dist2, cons, zs = _host_loop(problem, method, comm, steps,
                                        indices, mesh)

    def gap(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        if a.shape != b.shape:
            return float("inf")
        if not b.size:
            return 0.0
        scale = np.maximum(np.abs(b), np.abs(b).max() * 1e-3)
        return float(np.max(np.abs(a - b) / scale))

    return {
        "iters": [int(i) for i in res.iters] == [int(i) for i in iters],
        "dist2": gap(res.dist2, dist2),
        "dist2_len": len(res.dist2),
        "consensus": gap(res.consensus, cons),
        "z": gap(res.z, zs[-1]),
        "zs": None if res.zs is None else gap(res.zs, zs),
        "host_reads": res.extras["host_reads"],
    }


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
    _check(case, _gaps(case, problems, "dense"))


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


def _sharded_main():
    from repro.launch.mesh import make_node_mesh

    jax.config.update("jax_enable_x64", True)
    problems = _problems()
    mesh = make_node_mesh(N)
    out = {_case_id(c): _gaps(c, problems, "sharded", mesh) for c in CASES}
    print(json.dumps(out))


if __name__ == "__main__":
    _sharded_main()
