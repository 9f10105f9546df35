"""Small-mesh dry-run smoke: lower + compile the REAL step functions on an
8-device host mesh in a SUBPROCESS (so the 1-device default of the rest of
the test suite is untouched — the spec forbids setting the device-count flag
globally)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json, sys
    import jax
    import numpy as np
    from repro.configs import get_reduced
    from repro.launch.dryrun import build_cell
    from repro.launch import hlo_analysis as H
    from repro.models.layers import use_constraint_mesh

    arch, shape, multi = sys.argv[1], sys.argv[2], sys.argv[3] == "multi"
    mesh_shape = (2, 2, 2) if multi else (2, 4)
    axes = ("pod", "data", "model") if multi else ("data", "model")
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh(mesh_shape, axes)

    cfg = get_reduced(arch)
    # shrink the shape grid to smoke scale
    from repro.launch import shapes as S
    S.SHAPES = {
        "train_4k": S.ShapeSpec("train_4k", "train", 64, 8),
        "prefill_32k": S.ShapeSpec("prefill_32k", "prefill", 128, 4),
        "decode_32k": S.ShapeSpec("decode_32k", "decode", 128, 8),
        "long_500k": S.ShapeSpec("long_500k", "decode", 256, 1),
    }
    with mesh, use_constraint_mesh(mesh):
        fn, sds = build_cell(cfg, shape, mesh, multi)
        compiled = fn.lower(*sds).compile()
        cost = compiled.cost_analysis()
        colls = H.collective_stats(compiled.as_text())
    print(json.dumps({
        "flops": float(cost.get("flops", 0)),
        "collective_bytes": colls.total_bytes,
        "collective_ops": sorted(colls.count_by_op),
    }))
    """
)


def run_cell(arch, shape, mesh="single"):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, arch, shape, mesh],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("arch,shape", [
    ("minitron_8b", "train_4k"),
    ("gemma2_2b", "train_4k"),
    ("kimi_k2", "train_4k"),
    ("mamba2_1p3b", "train_4k"),
    ("whisper_small", "train_4k"),
    ("zamba2_1p2b", "decode_32k"),
    ("qwen2_moe", "prefill_32k"),
])
def test_single_pod_cells_compile(arch, shape):
    rec = run_cell(arch, shape, "single")
    assert rec["flops"] > 0


def test_multi_pod_gossip_train_compiles_with_collective_permute():
    rec = run_cell("minitron_8b", "train_4k", "multi")
    assert rec["flops"] > 0
    # the pod axis must communicate via neighbor permutes (the paper's
    # pattern), which XLA emits as collective-permute
    assert "collective-permute" in rec["collective_ops"], rec["collective_ops"]


def test_multi_pod_serve_compiles():
    rec = run_cell("mamba2_1p3b", "decode_32k", "multi")
    assert rec["flops"] > 0
