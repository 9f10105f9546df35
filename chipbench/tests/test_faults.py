"""The harness, its look for a chip skipped, driving a whole run at a
tiny size with the timed path broken underneath: ``correct`` must come
out false for every fault the cell can have, and true without one."""
import dataclasses
import json
import time
from pathlib import Path

import jax
import pytest

from chipbench import run

HERE = Path(__file__).resolve().parents[1]
BENCH = json.loads((HERE.parent / "BENCHMARK.json").read_text())


def tiny_solver_cell(workload):
    cell, cfg, traffic, limits = run.cell_files(BENCH, workload)
    cfg = dict(cfg, n_nodes=4, q=20, d=300, k=8, steps=600, record_every=20,
               lam=1.0 / 800)
    return cell, cfg, traffic, limits


def tiny_chat_cell():
    cell, cfg, traffic, limits = run.cell_files(BENCH, "minitron_8b.chat")
    cfg = dict(cfg, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
               head_dim=16, d_ff=128, vocab_size=256,
               serving={"max_batch": 4, "block_size": 16, "prompt_pad": 64,
                        "max_len": 96})
    traffic = dict(
        traffic, arrivals={"rate_rps": 20.0, "gap_cv": 2.0},
        prompt_tokens={"median": 30, "sigma": 0.8, "min": 8, "max": 64},
        output_tokens={"median": 8, "sigma": 0.8, "min": 2, "max": 32},
        preroll_s=1.0, drain_limit_s=20, check_tokens=40)
    return cell, cfg, traffic, limits


def drive(workload, cell, cfg, traffic, limits, seconds):
    from repro.core import runner_cache

    for cache in (runner_cache.DENSE, runner_cache.SPARSE):
        cache.clear()  # the next solve traces the (broken) step anew
    e2e, layer = run.reported(BENCH, workload)
    return run.run_cell(workload, cell, cfg, traffic, limits, e2e, layer,
                        seed=2**31 + 17, seconds=seconds, trace=False,
                        devices=jax.devices()[:1], t_start=time.perf_counter())


# -- solver cells -------------------------------------------------------------


def _state_unchanged(orig):
    return lambda cfg, w, wt, idx, val, y, state, i_t, mix=None, **kw: state


def _half_the_rows(orig):
    def step(cfg, w, wt, idx, val, y, state, i_t, mix=None, **kw):
        return orig(cfg, w, wt, idx, val, y, state, i_t % (idx.shape[1] // 2),
                    mix, **kw)
    return step


def _no_exchange(orig):
    def step(cfg, w, wt, idx, val, y, state, i_t, mix=None, **kw):
        own = (state.z, 2.0 * state.z - state.z_prev)
        return orig(cfg, w, wt, idx, val, y, state, i_t, None, mix_pair=own)
    return step


SOLVER_FAULTS = {
    "state_unchanged": ("repro.core.dsba.dsba_step", _state_unchanged),
    "half_the_rows": ("repro.core.dsba.dsba_step", _half_the_rows),
    "no_exchange": ("repro.core.dsba.dsba_step", _no_exchange),
    "answer_altered": ("repro.core.solvers.solve",
                       lambda orig: lambda *a, **k: dataclasses.replace(
                           orig(*a, **k), z=-orig(*a, **k).z)),
}


def patch(monkeypatch, target, make):
    import importlib

    parts = target.split(".")
    for i in range(len(parts) - 1, 0, -1):
        try:
            owner = importlib.import_module(".".join(parts[:i]))
            break
        except ModuleNotFoundError:
            continue
    for name in parts[i:-1]:
        owner = getattr(owner, name)
    monkeypatch.setattr(owner, parts[-1], make(getattr(owner, parts[-1])))


@pytest.mark.parametrize("workload", ["ridge_rcv1.sparse", "ridge_rcv1.dense"])
def test_solver_cell_sound_run_is_correct(workload):
    res = drive(workload, *tiny_solver_cell(workload), seconds=0.5)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"solve_s", "setup_s"}


# neither cell spans chips; the neighbour exchange that the dense backend
# runs as a matmul is the nearest thing, and the relay mixes its own
# reconstructed copies, which the fault below does not reach
CASES = [(w, f) for w in ("ridge_rcv1.sparse", "ridge_rcv1.dense")
         for f in sorted(SOLVER_FAULTS)
         if not (f == "no_exchange" and w.endswith(".sparse"))]


@pytest.mark.parametrize("workload,fault", CASES)
def test_solver_cell_fault_is_caught(monkeypatch, workload, fault):
    patch(monkeypatch, *SOLVER_FAULTS[fault])
    res = drive(workload, *tiny_solver_cell(workload), seconds=0.5)
    assert not res["correct"], res["checks"]


# -- serving cell -------------------------------------------------------------


def _pools_unchanged(orig):
    def step(cfg, params, tokens, pools, table, lengths):
        _, logits = orig(cfg, params, tokens, pools, table, lengths)
        return pools, logits
    return step


def _half_the_slots(orig):
    """The later half (rounded up) of the slots in use get token 0 in
    place of their own: whatever the load, every decode step has some."""
    def step(cfg, params, tokens, pools, table, lengths):
        import jax.numpy as jnp

        used = lengths > 0
        rank = jnp.cumsum(used) - 1
        drop = used & (rank >= used.sum() // 2)
        return orig(cfg, params, jnp.where(drop[:, None], 0, tokens), pools,
                    table, lengths)
    return step


def _token_altered(orig):
    def sample(self, row):
        return (orig(self, row) + 1) % row.shape[-1]
    return sample


SERVE_FAULTS = {
    "state_unchanged": ("repro.models.transformer.decode_step_paged",
                        _pools_unchanged),
    "half_the_batch": ("repro.models.transformer.decode_step_paged",
                       _half_the_slots),
    "token_altered": ("repro.serve.scheduler.Scheduler._sample",
                      _token_altered),
}


def test_chat_cell_sound_run_is_correct():
    res = drive("minitron_8b.chat", *tiny_chat_cell(), seconds=1.5)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"ttft_p50_ms", "itl_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault", sorted(SERVE_FAULTS))
def test_chat_cell_fault_is_caught(monkeypatch, fault):
    patch(monkeypatch, *SERVE_FAULTS[fault])
    res = drive("minitron_8b.chat", *tiny_chat_cell(), seconds=1.5)
    assert not res["correct"], res["checks"]


def test_refuses_without_a_chip(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert run.main(["--workload", "ridge_rcv1.dense", "--seed", "1",
                     "--seconds", "1"]) == 2
    assert "refusing" in capsys.readouterr().err
