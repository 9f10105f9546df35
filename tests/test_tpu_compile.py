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
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.decode_attention import decode_attention
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


def test_sharded_solver_chunk_measures_permutes_on_four_chips(topo):
    """comm="sharded" on a v5e:2x2 node mesh: a 4-node ring's DSBA chunk
    compiles, and the collective bytes measured from the TPU's HLO (whose
    tiled layouts the parser must read) are 4 permutes of one iterate row
    per iteration, as on the CPU."""
    from repro.core import mixing, solvers as S
    from repro.data.synthetic import make_regression
    from repro.launch.hlo_analysis import compiled_collective_costs

    n, d, iters = 4, 47236, 10
    mesh = Mesh(np.array(topo.devices), ("node",),
                axis_types=(AxisType.Auto,))
    problem = S.make_problem(
        "ridge", make_regression(n, 8, d, 74, seed=0, dtype=np.float32),
        mixing.ring_graph(n),
    )
    spec = S.get_solver("dsba")
    hp = {**spec.defaults, "alpha": 0.5}
    runner = S._get_sharded_runner(spec, problem, hp, mesh)
    proto = jax.eval_shape(
        runner.init, jax.ShapeDtypeStruct((n, d), jnp.float32)
    )

    def placed(shape, dtype, spec, weak=False):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec), weak_type=weak
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
    idx = placed((iters, n), jnp.int32, P(None, "node"))
    compiled = runner.chunk.lower(state, idx, hp_dyn).compile()
    costs = compiled_collective_costs(compiled, iterations=iters)
    assert costs["count_by_op"] == {"collective-permute": 4.0}
    assert costs["bytes_per_iter"] == 4 * d * 4
