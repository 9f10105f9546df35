"""The main path's Pallas kernels compile for a TPU v5e chip.

Nothing runs: each kernel is lowered at real widths against a *described*
v5e chip (``jax.experimental.topologies``) and compiled by the TPU's own
compiler, which refuses what interpret mode accepts — block shapes off the
(8, 128) tiling, dtypes Mosaic lacks, dot forms it cannot lower. Each test
asserts the compiled program holds the kernel as a ``tpu_custom_call``
named as the kernel's ``pallas_call`` names it: a profile of the chip shows
that name, whichever function wraps the kernel.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file. Where it cannot be described, the fixture skips.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.decode_attention import decode_attention, mla_decode_attention
from repro.kernels.expert_matmul import expert_matmul
from repro.kernels.flash_attention import (
    flash_attention_bwd,
    flash_attention_fwd,
)
from repro.kernels.sparse_saga import sparse_axpy

KERNEL_MARK = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_names(text: str) -> set[str]:
    """Instruction names, without their instance numbers, of the kernel
    custom calls in compiled HLO text."""
    return {
        line.split(" = ", 1)[0].split()[-1].lstrip("%").rsplit(".", 1)[0]
        for line in text.splitlines()
        if " custom-call(" in line and KERNEL_MARK in line
    }


def test_decode_attention_compiles_at_minitron_8b_widths(one_chip):
    # minitron_8b: 32 query heads over 8 kv heads, head_dim 128; the paged
    # pool at the serving smoke's page size, 8 slots of 33 pages each
    B, Hq, Hkv, Dh, bs, pages = 8, 32, 8, 128, 16, 33
    n_blocks = B * pages + 1
    text = _compiled_text(
        decode_attention, one_chip,
        ((B, Hq, Dh), jnp.bfloat16),
        ((n_blocks, bs, Hkv, Dh), jnp.bfloat16),
        ((n_blocks, bs, Hkv, Dh), jnp.bfloat16),
        ((B, pages), jnp.int32),
        ((B,), jnp.int32),
    )
    assert KERNEL_MARK in text
    assert _kernel_names(text) == {"decode_attention"}


def test_mla_decode_attention_compiles_at_kimi_k2_widths(one_chip):
    # kimi_k2.reasoning: 64 slots of 320 pages of 16 latent rows, each row
    # 576 wide (kv_lora_rank 512 + rope 64) padded to 640 lanes, 64 heads,
    # 8 layers in one pool
    B, H, R, C, pages, layers = 64, 64, 640, 512, 320, 8
    n_blocks = B * pages + 1
    fn = functools.partial(mla_decode_attention, scale=0.1, value_dim=C)
    text = _compiled_text(
        fn, one_chip,
        ((B, H, R), jnp.bfloat16),
        ((layers, n_blocks, 16, R), jnp.bfloat16),
        ((), jnp.int32),
        ((B, pages), jnp.int32),
        ((B,), jnp.int32),
    )
    assert _kernel_names(text) == {"mla_decode_attention"}


@pytest.mark.parametrize("tm", [16, 128])
@pytest.mark.parametrize("proj", ["gate", "down"])
def test_expert_matmul_compiles_at_kimi_k2_widths(one_chip, tm, proj):
    # 7 MoE layers x 8 held experts of 7168 x 2048 (gate, up) or 2048 x 7168
    # (down); the decode step's 64 tokens (tm 16) and a 2,048-token
    # prefill (tm 128), top-8
    K, N = (7168, 2048) if proj == "gate" else (2048, 7168)
    T = 64 if tm == 16 else 2048
    M = (-(-T * 8 // tm) + 8) * tm
    text = _compiled_text(
        functools.partial(expert_matmul, tm=tm), one_chip,
        ((M, K), jnp.bfloat16),
        ((56, K, N), jnp.bfloat16),
        ((M // tm,), jnp.int32),
        ((), jnp.int32),
    )
    assert _kernel_names(text) == {"expert_matmul"}


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles_at_seq_2048(one_chip, direction):
    q = ((1, 32, 2048, 128), jnp.bfloat16)
    kv = ((1, 8, 2048, 128), jnp.bfloat16)
    if direction == "fwd":
        text = _compiled_text(flash_attention_fwd, one_chip, q, kv, kv)
        names = {"flash_attention"}
    else:
        lse = ((1, 32, 2048), jnp.float32)
        text = _compiled_text(
            flash_attention_bwd, one_chip, q, kv, kv, q, lse, q
        )
        names = {"flash_bwd_dq", "flash_bwd_dkv"}
    assert KERNEL_MARK in text
    assert _kernel_names(text) == names


def test_sparse_axpy_compiles_at_rcv1_shape(one_chip):
    # the DSBA-s relay's densification: N=10 nodes, rcv1's d=47,236 and
    # 74 nonzeros per row, float32 (Mosaic has no float64)
    N, D, k = 10, 47236, 74
    text = _compiled_text(
        sparse_axpy, one_chip,
        ((N, D), jnp.float32),
        ((N, k), jnp.int32),
        ((N, k), jnp.float32),
        ((N,), jnp.float32),
        ((N,), jnp.float32),
    )
    assert KERNEL_MARK in text
    assert _kernel_names(text) == {"saga_sparse_axpy"}


_INSTR = re.compile(
    r"^\s*(ROOT\s+)?%(\S+) = \w+\[([\d,]*)\](?:\{[^}]*\})?\s+([\w-]+)"
    r"\(([^)]*)\)(?:.*calls=%([\w.-]+))?"
)


def _hlo_ops(text: str) -> dict[str, tuple[bool, list[dict]]]:
    """Compiled HLO text -> {computation: (is_entry, instructions)}, each
    instruction with its opcode, result elements, root flag, the largest
    operand's elements and the computation a fusion calls. Loops are kept
    too (their result is the carry tuple)."""
    comps, ops, size = {}, None, {}
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            head = line.split(" (", 1)[0].split()
            ops, size = [], {}
            comps[head[-1].lstrip("%")] = (head[0] == "ENTRY", ops)
        elif ops is None:
            continue
        elif m := _INSTR.match(line):
            root, name, shape, op, args, called = m.groups()
            size[name] = int(np.prod([int(x) for x in shape.split(",") if x]))
            operand = max((size.get(a.strip().lstrip("%"), 0)
                           for a in args.split(",")), default=0)
            ops.append({"op": op, "elems": size[name], "root": bool(root),
                        "operand": operand, "calls": called})
        elif " while(%" in line:
            ops.append({"op": "while", "elems": 0, "root": False,
                        "operand": 0, "calls": None})
    return comps


def test_relay_scan_keeps_its_ring_in_place_at_rcv1_shape(one_chip):
    """The DSBA-s relay scan at ridge_rcv1's shape (N=10 on ER(0.4) with
    graph seed 0, d=47,236, k=74, float32, the kernel compiled). Outside
    the entry computation (which seeds the scan once a call) no
    instruction copies, slices or re-lays-out the reconstruction ring and
    no loop runs inside the scan: the only results as large as the ring
    are its block writes, each a dynamic-update-slice in place."""
    from repro.core import mixing
    from repro.core import sparse_comm as sc
    from repro.core.dsba import DSBAConfig
    from repro.core.solvers import make_problem
    from repro.data.synthetic import make_regression

    n, d, k, q, steps = 10, 47236, 74, 100, 16
    data = make_regression(n, q, d, k, seed=0, dtype=np.float32)
    graph = mixing.erdos_renyi_graph(n, 0.4, seed=0)
    w = mixing.laplacian_mixing(graph)
    problem = make_problem("ridge", data, graph, lam=1e-4)
    cfg = DSBAConfig(spec=problem.spec, alpha=0.5, lam=1e-4)
    scan, tb = sc._build_sparse_scan(
        cfg, data, graph, w, verify=False, kernel_mode="on"
    )
    carry = jax.eval_shape(lambda: sc._relay_carry0(
        cfg, data, np.zeros((n, d), np.float32), tb, False
    ))
    ring = int(np.prod(carry[2].shape))

    def sds(shape, dtype, weak=False):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip,
                                    weak_type=weak)

    text = scan.lower(
        jax.tree.map(lambda x: sds(x.shape, x.dtype), carry),
        (sds((steps,), jnp.int32), sds((steps, n), jnp.int32)),
        sds((n, d), jnp.float32),
        {"alpha": sds((), jnp.float32, True),
         "lam": sds((), jnp.float32, True)},
    ).compile().as_text()
    assert _kernel_names(text) == {"saga_sparse_axpy"}

    comps = _hlo_ops(text)
    root_op = {c: next((i["op"] for i in ops if i["root"]), None)
               for c, (_, ops) in comps.items()}
    loops = [c for c, (_, ops) in comps.items() for i in ops
             if i["op"] == "while"]
    assert len(loops) == 1 and comps[loops[0]][0], loops  # the scan's own
    bad = []
    for c, (entry, ops) in comps.items():
        if entry:
            continue
        for i in ops:
            op = i["op"]
            in_place = op == "dynamic-update-slice" or (
                op == "fusion"
                and root_op.get(i["calls"]) == "dynamic-update-slice"
            )
            if op in ("copy", "slice", "transpose") and i["operand"] == ring:
                bad.append((c, op, i["elems"]))  # the ring copied or staged
            elif i["elems"] == ring and not in_place and op not in (
                "parameter", "get-tuple-element", "bitcast"
            ):
                bad.append((c, op, i["elems"]))  # the ring rebuilt
    assert not bad, bad


def _placed_dsba(runner_of, problem, sharding_of):
    """(runner, state, hp_dyn, placed) for a DSBA runner (alpha 0.5) on
    described devices: shapes only, each placed by ``sharding_of`` a
    partition spec."""
    from repro.core import solvers as S

    spec = S.get_solver("dsba")
    hp = {**spec.defaults, "alpha": 0.5}
    runner = runner_of(spec, problem, hp)
    n, d = problem.graph.n, problem.dim
    proto = jax.eval_shape(
        runner.init, jax.ShapeDtypeStruct((n, d), jnp.float32)
    )

    def placed(shape, dtype, pspec, weak=False):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=sharding_of(pspec), weak_type=weak
        )

    state = jax.tree.map(
        lambda s, sp: placed(s.shape, s.dtype, sp),
        proto, S._node_partition_specs(proto, n),
    )
    hp_dyn = {
        k: placed(np.shape(v), jnp.asarray(v).dtype, P(),
                  weak=isinstance(v, float))
        for k, v in S._dynamic_hp(spec, problem, hp).items()
    }
    return runner, state, hp_dyn, placed


def _ring4(topo):
    """The ridge_ring4 deployment's DSBA runner on a v5e:2x2 node mesh."""
    from repro.core import mixing, solvers as S
    from repro.data.synthetic import make_regression

    n, d = 4, 47236
    mesh = Mesh(np.array(topo.devices), ("node",),
                axis_types=(AxisType.Auto,))
    problem = S.make_problem(
        "ridge", make_regression(n, 8, d, 74, seed=0, dtype=np.float32),
        mixing.ring_graph(n),
    )
    return _placed_dsba(
        lambda spec, pr, hp: S._get_sharded_runner(spec, pr, hp, mesh),
        problem, lambda pspec: NamedSharding(mesh, pspec),
    )


def test_sharded_solver_chunk_measures_permutes_on_four_chips(topo):
    """comm="sharded" on a v5e:2x2 node mesh: a 4-node ring's DSBA chunk
    compiles, and the collective bytes measured from the TPU's HLO (whose
    tiled layouts the parser must read) are 4 permutes of one iterate row
    per iteration, as on the CPU."""
    from repro.launch.hlo_analysis import compiled_collective_costs

    n, d, iters = 4, 47236, 10
    runner, state, hp_dyn, placed = _ring4(topo)
    idx = placed((iters, n), jnp.int32, P(None, "node"))
    compiled = runner.chunk.lower(state, idx, hp_dyn).compile()
    costs = compiled_collective_costs(compiled, iterations=iters)
    assert costs["count_by_op"] == {"collective-permute": 4.0}
    assert costs["bytes_per_iter"] == 4 * d * 4


def test_sharded_record_program_on_four_chips(topo):
    """ridge_ring4's whole solve as one record program (47 record points
    of 6 iterations) on a v5e:2x2 node mesh: the chunk's 4 permutes an
    iteration, and besides them only the record reductions, all-reduces
    of at most one iterate row and two scalars a record point."""
    from repro.launch.hlo_analysis import collective_stats

    n, d, rec, every = 4, 47236, 47, 6
    runner, state, hp_dyn, placed = _ring4(topo)
    idx = placed((rec, every, n), jnp.int32, P(None, None, "node"))
    ref = (placed((d,), jnp.float32, P()), placed((d,), jnp.float32, P()))
    lowered = runner.record.lower(state, idx, hp_dyn, ref,
                                  keep_snapshots=False)
    _, dist2, cons, zs = lowered.out_info
    assert dist2.shape == cons.shape == (rec,) and zs is None
    stats = collective_stats(lowered.compile().as_text())
    assert set(stats.count_by_op) == {"collective-permute", "all-reduce"}
    assert stats.count_by_op["collective-permute"] == 4 * rec * every
    assert stats.bytes_by_op["collective-permute"] == 4 * rec * every * d * 4
    assert 1 <= stats.count_by_op["all-reduce"] / rec <= 3
    assert stats.bytes_by_op["all-reduce"] / rec <= (d + 2) * 4


def test_dense_record_program_on_one_chip(one_chip):
    """ridge_rcv1's whole dense solve as one record program (120 record
    points of 6 iterations, N = 10 on ER(0.4)) compiles for one v5e chip
    and holds no collective."""
    from repro.core import mixing, solvers as S
    from repro.data.synthetic import make_regression
    from repro.launch.hlo_analysis import collective_stats

    n, d, rec, every = 10, 47236, 120, 6
    problem = S.make_problem(
        "ridge", make_regression(n, 100, d, 74, seed=0, dtype=np.float32),
        mixing.erdos_renyi_graph(n, 0.4, seed=0), lam=1e-4,
    )
    runner, state, hp_dyn, placed = _placed_dsba(
        S._get_dense_runner, problem, lambda pspec: one_chip
    )
    idx = placed((rec, every, n), jnp.int32, P())
    ref = (placed((d,), jnp.float32, P()), placed((d,), jnp.float32, P()))
    lowered = runner.record.lower(state, idx, hp_dyn, ref,
                                  keep_snapshots=False)
    _, dist2, cons, zs = lowered.out_info
    assert dist2.shape == cons.shape == (rec,) and zs is None
    assert dist2.dtype == cons.dtype == jnp.float32
    assert not collective_stats(lowered.compile().as_text()).count_by_op
