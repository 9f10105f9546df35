"""Drive the ``ridge_ring4.sharded`` cell at a tiny size on four host
devices and print one JSON line per case: the sound program and the
program with its step broken. Run by ``test_sharded_cell.py`` in a child
process, since the host device count is fixed before JAX starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python -m chipbench.tests.sharded_child
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

from chipbench import run  # noqa: E402
from chipbench.tests import test_faults as tf  # noqa: E402

WORKLOAD = "ridge_ring4.sharded"


def tiny_cell():
    cell, cfg, traffic, limits = run.cell_files(tf.BENCH, WORKLOAD)
    cfg = dict(cfg, q=20, d=300, k=8, steps=600, record_every=20,
               lam=1.0 / 800)
    return cell, cfg, traffic, limits


def case(fault):
    import pytest

    from repro.core import runner_cache

    runner_cache.clear()
    with pytest.MonkeyPatch.context() as mp:
        if fault is not None:
            tf.patch(mp, *tf.SOLVER_FAULTS[fault])
        e2e, layer = run.reported(tf.BENCH, WORKLOAD)
        res = run.run_cell(WORKLOAD, *tiny_cell(), e2e, layer,
                           seed=2**31 + 29, seconds=0.5, trace=False,
                           devices=jax.devices()[:4],
                           t_start=time.perf_counter())
    return {"fault": fault, "correct": res["correct"],
            "checks": res["checks"], "metrics": sorted(res["metrics"]),
            "attempted": res["attempted"],
            "sharded_cache": runner_cache.SHARDED.stats()}


def main():
    jax.config.update("jax_enable_x64", True)
    assert len(jax.devices()) >= 4, jax.devices()
    for fault in [None, *sorted(tf.SOLVER_FAULTS)]:
        print(json.dumps(case(fault)), flush=True)


if __name__ == "__main__":
    main()
