"""Trip-count-aware HLO cost analysis vs XLA cost_analysis + manual math."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_analysis as H


def _compile(fn, *sds):
    return jax.jit(fn).lower(*sds).compile()


def test_dot_flops_match_cost_analysis_no_loops():
    """On a loop-free program our counter matches XLA's flops closely."""
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 512), jnp.float32)

    comp = _compile(lambda a, b: a @ b, x, w)
    want = comp.cost_analysis()["flops"]
    got = H.program_costs(comp.as_text()).flops
    assert abs(got - want) / want < 0.05, (got, want)


def test_scan_flops_multiplied_by_trip_count():
    """XLA counts a scan body once; program_costs multiplies by trips."""
    L, M = 16, 128
    x = jax.ShapeDtypeStruct((M, M), jnp.float32)
    ws = jax.ShapeDtypeStruct((L, M, M), jnp.float32)

    def scanned(x, ws):
        def body(c, w):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    comp = _compile(scanned, x, ws)
    xla_flops = comp.cost_analysis()["flops"]
    ours = H.program_costs(comp.as_text()).flops
    one_matmul = 2 * M * M * M
    # XLA reports ~1 matmul; we must report ~L matmuls
    assert xla_flops < 2 * one_matmul
    assert ours == pytest.approx(L * one_matmul, rel=0.1), (
        ours / one_matmul, L
    )


def test_nested_scan_multiplicities_compose():
    L1, L2, M = 4, 8, 64
    x = jax.ShapeDtypeStruct((M, M), jnp.float32)
    ws = jax.ShapeDtypeStruct((L1, L2, M, M), jnp.float32)

    def nested(x, ws):
        def outer(c, wrow):
            def inner(ci, w):
                return ci @ w, None
            c2, _ = jax.lax.scan(inner, c, wrow)
            return c2, None
        y, _ = jax.lax.scan(outer, x, ws)
        return y

    comp = _compile(nested, x, ws)
    ours = H.program_costs(comp.as_text()).flops
    want = L1 * L2 * 2 * M**3
    assert ours == pytest.approx(want, rel=0.15), ours / (2 * M**3)


def test_shape_bytes_tuple_types():
    assert H._shape_bytes("f32[2,3]") == 24
    assert H._shape_bytes("(f32[4], bf16[8])") == 16 + 16
    assert H._shape_bytes("s32[]") == 4
    assert H._shape_bytes("pred[10]") == 10


def test_collective_bytes_inside_loops_are_multiplied():
    """all-reduce inside a scan counts trip_count times."""
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch import hlo_analysis as H

        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh((4,), ("data",))
        L, M = 8, 64

        def f(x, ws):
            def body(c, w):
                y = c @ w
                return jax.lax.with_sharding_constraint(
                    y, NamedSharding(mesh, P())), None
            out, _ = jax.lax.scan(body, x, ws)
            return out

        xs = jax.ShapeDtypeStruct((M, M), jnp.float32)
        ws = jax.ShapeDtypeStruct((L, M, M), jnp.float32)
        with mesh:
            comp = jax.jit(
                f,
                in_shardings=(NamedSharding(mesh, P("data", None)),
                              NamedSharding(mesh, P(None, "data", None))),
            ).lower(xs, ws).compile()
        pc = H.program_costs(comp.as_text())
        ops = set(pc.coll_count_by_op)
        counts = {k: int(v) for k, v in pc.coll_count_by_op.items()}
        # some collective must appear with multiplicity ~L
        print("OK", max(counts.values()) >= L / 2, counts)
    """)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK True" in out.stdout, out.stdout


def test_collectives_parse_through_tpu_tiled_layouts():
    """TPU HLO writes tiled layouts (``{1,0:T(1,128)S(1)}``) after shapes;
    their parentheses must not hide the instruction from the parser."""
    text = "\n".join([
        "HloModule m",
        "",
        "ENTRY %main (p: f32[1,512]) -> f32[1,512] {",
        "  %p = f32[1,512]{1,0:T(1,128)} parameter(0)",
        "  %cp = (f32[1,512]{1,0:T(1,128)S(1)}, f32[1,512]{1,0:T(1,128)S(1)},"
        " u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%p),"
        " channel_id=1, source_target_pairs={{0,1},{1,0}}",
        "  ROOT %d = f32[1,512]{1,0:T(1,128)} collective-permute-done(%cp)",
        "}",
    ])
    stats = H.collective_stats(text)
    assert stats.count_by_op == {"collective-permute": 1}
    assert stats.bytes_by_op == {"collective-permute": 512 * 4}
