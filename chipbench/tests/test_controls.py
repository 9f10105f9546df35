"""Each cell's control, at a size a test run can hold, comes out as not
correct under the cell's limits, while the program reads correct."""
import jax

from chipbench import run, tracing
from chipbench.systems import dsba_solve, serve_lm
from chipbench.tests.test_faults import tiny_chat_cell, tiny_solver_cell


def passes(checks):
    return all(c["value"] <= c["limit"] for c in checks.values())


def solver_checks(workload, **kw):
    _, cfg, traffic, limits = tiny_solver_cell(workload)
    s = dsba_solve.System(cfg, traffic, 2**31 + 5, jax.devices()[:1],
                          tracing.Spans(False), **kw)
    s.warm()
    s.run_window(0.3)
    s.release()
    return s, limits


def test_dense_cell_control_is_the_programs_bf16_path():
    sound, limits = solver_checks("ridge_rcv1.dense")
    assert passes(sound.check(limits)[0])
    control, limits = solver_checks("ridge_rcv1.dense", dtype="bfloat16")
    assert not passes(control.check(limits)[0])


def test_sparse_cell_control_is_the_bf16_reference():
    sound, limits = solver_checks("ridge_rcv1.sparse")
    assert passes(sound.check(limits)[0])
    assert not passes(sound.reference_control(limits, "bfloat16"))


def test_chat_cell_control_is_the_fp8_model():
    _, cfg, traffic, limits = tiny_chat_cell()
    s = serve_lm.System(cfg, traffic, 2**31 + 6, jax.devices()[:1],
                        tracing.Spans(False))
    s.warm()
    s.run_window(1.0)
    s.finish()
    s.release()
    checks, _, failed = s.check(limits)
    assert run.verdict(checks, failed), checks
    checks, _, failed = s.check(limits, quant="fp8")
    assert not run.verdict(checks, failed), checks
