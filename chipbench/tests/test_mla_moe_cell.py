"""The kimi_k2.reasoning cell at a tiny size on the CPU: the plain
reference against the program, the fp8 control failing the cell's limit,
whole runs with the timed path broken coming out not correct, the refusal
of a program without latent attention, and the new metrics' readers."""
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import costs_mla_moe, mla_moe_weights, run, tracing
from chipbench.references import mla_moe_lm
from chipbench.systems import serve_mla_moe
from chipbench.tests.test_faults import SERVE_FAULTS, patch

HERE = Path(__file__).resolve().parents[1]
BENCH = json.loads((HERE.parent / "BENCHMARK.json").read_text())
CELL = "kimi_k2.reasoning"


def tiny_kimi_cell():
    cell, cfg, traffic, limits = run.cell_files(BENCH, CELL)
    cfg = dict(cfg, n_layers=3, hidden_size=64, num_attention_heads=4,
               q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
               moe_intermediate_size=32, n_routed_experts=8,
               num_experts_per_tok=2, n_experts_held=4, expert_offset=2,
               vocab_size=256, dtype="float32",
               rope_scaling=dict(cfg["rope_scaling"],
                                 original_max_position_embeddings=16),
               serving={"max_batch": 4, "block_size": 16, "prompt_pad": 64,
                        "max_len": 512})
    traffic = dict(
        traffic, arrivals={"rate_rps": 20.0, "gap_cv": 2.0},
        prompt_tokens={"median": 30, "sigma": 0.8, "min": 8, "max": 64},
        output_tokens={"median": 8, "sigma": 0.8, "min": 2, "max": 32},
        preroll_s=1.0, drain_limit_s=20, check_tokens=40)
    return cell, cfg, traffic, limits


def drive(seconds=1.5):
    e2e, layer = run.reported(BENCH, CELL)
    return run.run_cell(CELL, *tiny_kimi_cell(), e2e, layer,
                        seed=2**31 + 23, seconds=seconds, trace=False,
                        devices=jax.devices()[:1], t_start=time.perf_counter())


def test_reference_matches_the_programs_prefill():
    from repro.models import transformer as T

    _, cfg, _, _ = tiny_kimi_cell()
    mcfg = serve_mla_moe.model_config(cfg)
    params = mla_moe_weights.make(cfg, 11, "float32")
    tokens = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    padded = np.zeros((1, 512), np.int32)
    padded[0, :40] = tokens
    with jax.default_matmul_precision("highest"):
        _, logits = T.prefill(mcfg, params, jnp.asarray(padded),
                              T.init_cache(mcfg, 1, 512),
                              valid_len=jnp.asarray([40], jnp.int32))
        model = mla_moe_lm.LM.of(mla_moe_weights.dims(cfg))
        x = mla_moe_lm.hidden(params, jnp.asarray(padded[0]), model)
        h = mla_moe_lm._rms(x[39], params["final_norm"], 1e-6)
        ref = np.asarray(h @ params["lm_head"])
    np.testing.assert_allclose(np.asarray(logits[0]), ref, rtol=1e-4,
                               atol=1e-4 * np.abs(ref).max())


def test_sound_run_is_correct_and_the_fp8_control_is_not():
    _, cfg, traffic, limits = tiny_kimi_cell()
    s = serve_mla_moe.System(cfg, traffic, 2**31 + 6, jax.devices()[:1],
                             tracing.Spans(False))
    s.warm()
    s.run_window(1.5)
    s.finish()
    c = s.counters()
    s.release()
    checks, _, failed = s.check(limits)
    assert run.verdict(checks, failed), checks
    assert checks["served_gap_max"]["value"] <= 1e-4  # float32 throughout
    # a token altered by one id reads far above every sound token
    assert s.gap_report["altered"]["min"] > 100 * 1e-4
    checks, _, failed = s.check(limits, quant="fp8")
    assert not run.verdict(checks, failed), checks
    assert c["routes_decode"] > 0 and c["routes_prefill"] > 0
    assert 0 < c["experts_hit_decode"] <= 2 * 4 * c["decode_steps"]


def test_selection_bias_leaves_the_held_experts_their_even_share():
    _, cfg, _, _ = tiny_kimi_cell()
    cfg = dict(cfg, n_routed_experts=384, num_experts_per_tok=8,
               n_experts_held=8, expert_offset=0)
    k = mla_moe_weights.dims(cfg)
    bias = np.asarray(mla_moe_weights.make(cfg, 2**31 + 5)["blocks"]["moe"]
                      ["router_bias"], np.float64)
    assert bias.shape == (k["n_layers"] - k["n_dense"], 384)
    assert np.all(bias[:, :8] == 0)
    np.testing.assert_allclose(bias.mean(-1), 0, atol=1e-4)
    assert 0.5 * mla_moe_weights.BIAS_STD < bias[:, 8:].std() \
        < 2 * mla_moe_weights.BIAS_STD
    # unit-scale router scores: the held experts get top_k / n_experts of
    # the routes, and the bias changes most tokens' choice
    z = np.random.default_rng(0).standard_normal((4000, 384))
    s = 1 / (1 + np.exp(-z))
    top = np.argsort(-(s + bias[0]), -1)[:, :8]
    plain = np.argsort(-s, -1)[:, :8]
    share = np.mean(top < 8) * 384 / 8
    assert 0.9 < share < 1.1, share
    assert np.mean(np.any(np.sort(top, -1) != np.sort(plain, -1), -1)) > 0.3


def test_cell_reports_its_metrics():
    res = drive()
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"ttft_p50_ms", "setup_s"}


@pytest.mark.parametrize("fault", ["half_the_batch", "token_altered"])
def test_broken_timed_path_is_caught(monkeypatch, fault):
    patch(monkeypatch, *SERVE_FAULTS[fault])
    res = drive()
    assert not res["correct"], res["checks"]


def test_a_program_without_latent_attention_is_refused(monkeypatch):
    import dataclasses

    from repro.models import config

    @dataclasses.dataclass(frozen=True)
    class Older:
        name: str
        n_experts: int = 0

    monkeypatch.setattr(config, "ModelConfig", Older)
    monkeypatch.setattr(mla_moe_weights, "make", lambda *a, **k: 1 / 0)
    _, cfg, traffic, _ = tiny_kimi_cell()
    with pytest.raises(RuntimeError, match="cannot serve"):
        serve_mla_moe.System(cfg, traffic, 1, jax.devices()[:1],
                             tracing.Spans(False))


def _trace(n_steps, layers, moe_layers):
    """A normal-form trace: per decode step one decode program holding
    ``layers`` latent-attention calls and 3 expert_matmul calls per MoE
    layer, each 1 us, the program 1 ms; and one prefill program."""
    ops, mods, t = [], [], 1000
    for _ in range(n_steps):
        mods.append([t, 10**6, "jit_decode_step_paged"])
        for j in range(layers):
            ops.append([t + 10 * j, 1000, "%mla_decode_attention.1 = "
                        "bf16[64,64,512]{2,1,0} custom-call(...)"])
        for j in range(3 * moe_layers):
            ops.append([t + 500 + 10 * j, 1000, "%expert_matmul.4 = "
                        "bf16[640,2048]{1,0} custom-call(...)"])
        t += 2 * 10**6
    mods.append([t, 5 * 10**6, "jit_prefill"])
    ops.append([t, 1000, "%flash_attention.2 = bf16[1,64,2048,192]{3,2,1,0} "
                "custom-call(...)"])
    return {"window": [0, t + 10**7], "ops": {"TPU:0": ops},
            "modules": {"TPU:0": mods}, "host": []}


def test_readers_use_the_route_counter():
    _, cfg, _, _ = run.cell_files(BENCH, CELL)
    k = mla_moe_weights.dims(cfg)
    from chipbench.peaks import peaks_for

    c = {"mla_moe": k, "decode_steps": 2, "kv_tokens": 1000, "max_batch": 64,
         "decode_tokens": 100, "routes_decode": 40, "experts_hit_decode": 30,
         "routes_prefill": 300, "prefills": 1, "prompt_tokens": 1800,
         "prompt_sq": 1800**2}
    obs = run.Observation(_trace(2, 8, 7), c, peaks_for("TPU v5 lite"), 1)
    read = {n: run.load_reader(n) for n in (
        "mla_decode_roofline.serve", "expert_matmul_roofline.serve",
        "moe_decode_mfu.serve", "moe_prefill_mfu.serve")}
    got = {n: f(obs) for n, f in read.items()}
    flops, nbytes = costs_mla_moe.expert_matmul(40, 30, 7168, 2048, 2)
    want = 100 * max(flops / 197e12, nbytes / 819e9) / (2 * 21 * 1e-6)
    assert got["expert_matmul_roofline.serve"] == pytest.approx(want)
    assert all(v is not None and v > 0 for v in got.values()), got
    # twice the routes in the same device time: more useful FLOPs
    more = run.Observation(obs.trace, dict(c, routes_decode=80), obs.peaks, 1)
    assert read["moe_decode_mfu.serve"](more) > got["moe_decode_mfu.serve"]
    # a trace of another program (the parent's, or a dense cell's): nothing
    empty = run.Observation({"window": [0, 1], "ops": {}, "modules": {},
                             "host": []}, {**c, "mla_moe": None},
                            obs.peaks, 1)
    assert all(f(empty) is None for f in read.values())
