"""The four-chip cell ``ridge_ring4.sharded`` at a tiny size on four host
devices (in a child process, ``sharded_child.py``): the sound program
reads correct, every solver fault of ``test_faults.py`` reads not correct,
and the window's solves reuse one compiled runner."""
import json
import os
import subprocess
import sys

import pytest

from chipbench.tests.conftest import ROOT
from chipbench.tests.test_faults import SOLVER_FAULTS


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.tests.sharded_child"], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    return {c["fault"]: c for c in lines}


def test_sharded_cell_sound_run_is_correct(cases):
    sound = cases[None]
    assert sound["correct"], sound["checks"]
    assert sound["metrics"] == ["setup_s", "solve_s"]


@pytest.mark.parametrize("fault", sorted(SOLVER_FAULTS))
def test_sharded_cell_fault_is_caught(cases, fault):
    assert not cases[fault]["correct"], cases[fault]["checks"]


def test_sharded_cell_solves_reuse_one_runner(cases):
    """One runner is built, by the warm-up solve; every solve of the
    window finds it."""
    stats = cases[None]["sharded_cache"]
    assert stats["misses"] == 1 and stats["size"] == 1
    assert stats["hits"] == cases[None]["attempted"] >= 1
