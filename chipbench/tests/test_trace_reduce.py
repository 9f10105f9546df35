"""The trace-to-metrics reduction, on hand-made traces and on small
traces recorded on a TPU v5e (``data/``)."""
import pytest

from chipbench import kernels, tracing


def synthetic():
    ms = 1_000_000  # ns
    return {
        "window": [0, 100 * ms],
        "ops": {"TPU:0": [
            [10 * ms, 20 * ms, "%while.1 = (f32[2]) while(f32[2] %a)"],
            [10 * ms, 5 * ms, "%fusion.3 = f32[8]{0} fusion(f32[8] %x)"],
            [20 * ms, 10 * ms,
             "%saga_sparse_axpy.2 = f32[10,47236]{1,0} custom-call(f32 %p)"],
            [60 * ms, 10 * ms, "%fusion.3 = f32[8]{0} fusion(f32[8] %x)"],
            [95 * ms, 10 * ms, "%copy.1 = f32[8]{0} copy(f32[8] %x)"],
        ]},
        "modules": {"TPU:0": [[10 * ms, 20 * ms, "jit_run(1)"],
                              [60 * ms, 10 * ms, "jit_read(2)"]]},
        "host": [[0, 50 * ms, "bench.solve"],
                 [40 * ms, 5 * ms, "bench.observe"]],
    }


def test_union_merges_overlaps():
    assert tracing.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]


def test_busy_and_idle_on_a_synthetic_trace():
    tr = synthetic()
    # busy: [10,30] + [60,70] + [95,100] (clipped at the window's end)
    assert tracing.busy_s(tr) == pytest.approx(0.035)
    assert tracing.window_s(tr) == pytest.approx(0.1)
    assert tracing.idle_share(tr) == pytest.approx(65.0)


def test_idle_gaps_go_to_the_innermost_host_span():
    gaps = dict(tracing.idle_gaps(synthetic()))
    # [0,10] + [30,40] + [45,50] under solve, [40,45] under observe,
    # [50,60] + [70,95] under no span
    assert gaps["solve"] == pytest.approx(0.025)
    assert gaps["observe"] == pytest.approx(0.005)
    assert gaps["host.other"] == pytest.approx(0.035)


def test_top_ops_leave_out_loops_and_name_instructions():
    top = dict(tracing.top_ops(synthetic()))
    assert "while.1 (f32[2])" not in top
    assert top["fusion.3 f32[8]"] == pytest.approx(0.015)
    assert tracing.op_name("%fusion.3 = f32[8]{0} fusion()") == "fusion.3"
    assert tracing.op_base("%fusion.3 = f32[8]{0} fusion()") == "fusion"


def test_kernel_events_are_custom_calls_by_name():
    tr = synthetic()
    evs = kernels.events(tr, "sparse_axpy")
    assert len(evs) == 1 and tracing.seconds(evs) == pytest.approx(0.01)
    assert kernels.events(tr, "decode_attention") == []
    progs = kernels.programs(tr)
    assert sorted(progs) == ["jit_read(2)", "jit_run(1)"]
    assert len(kernels.events_in(tr, progs["jit_run(1)"], "sparse_axpy")) == 1
    assert kernels.events_in(tr, progs["jit_read(2)"], "sparse_axpy") == []


def test_recorded_decode_steps(data_dir):
    """Four paged decode steps of minitron_8b (4 layers, 64 slots) as a
    v5e traced them: one decode program a step, four decode-attention
    kernel calls in each."""
    tr = tracing.load(data_dir / "v5e_decode_steps.json.gz")
    progs = kernels.programs(tr)
    decode = [evs for evs in progs.values()
              if kernels.events_in(tr, evs, "decode_attention")]
    assert len(decode) == 1 and len(decode[0]) == 4
    attn = kernels.events(tr, "decode_attention")
    assert len(attn) == 16
    assert 0 < tracing.seconds(attn) < tracing.busy_s(tr) < tracing.window_s(tr)
    idle = tracing.idle_share(tr)
    assert 0 < idle < 100
    gaps = dict(tracing.idle_gaps(tr))
    assert sum(gaps.values()) == pytest.approx(
        tracing.window_s(tr) - tracing.busy_s(tr), rel=1e-9)
    assert max(gaps, key=gaps.get) == "scheduler.step"
    top = tracing.top_ops(tr, 3)
    assert top[0][0].startswith("decode_attention")


def test_recorded_dense_chunks(data_dir):
    """Dense-backend solver chunks as a v5e traced them: the runner is the
    program with most device time, 30 iterations a chunk."""
    tr = tracing.load(data_dir / "v5e_dense_chunks.json.gz")
    progs = kernels.programs(tr)
    runner = max(progs.values(), key=tracing.seconds)
    assert len(runner) == 4 and all("run_chunk" in ev[2] for ev in runner)
    assert kernels.events(tr, "sparse_axpy") == []
    assert tracing.busy_s(tr) <= tracing.window_s(tr)


def test_recorded_decode_steps_feed_the_decode_readers(data_dir):
    """The decode readers find the one decode program of the recorded
    trace and give shares of the peak under 100%; the host's part of a
    step comes from the idle gaps under the scheduler's span."""
    from chipbench import peaks, run

    tr = tracing.load(data_dir / "v5e_decode_steps.json.gz")
    m = {"n_layers": 4, "d_model": 4096, "n_heads": 32, "n_kv_heads": 8,
         "head_dim": 128, "d_ff": 16384, "vocab_size": 256000}
    counters = {"model": m, "steps": 4, "decode_steps": 4,
                "decode_tokens": 4 * 64, "kv_tokens": 4 * 64 * 512,
                "max_batch": 64}
    obs = run.Observation(tr, counters, peaks.peaks_for("TPU v5 lite"), 1)
    step_ms = run.load_reader("decode_step_ms.serve")(obs)
    prog = kernels.program_with(tr, "decode_attention")
    assert step_ms == pytest.approx(1e3 * tracing.seconds(prog) / 4)
    for name in ("decode_mfu.serve", "decode_attention_roofline.serve"):
        assert 0 < run.load_reader(name)(obs) < 100
    # the host's part of a step: the idle time under scheduler.step
    gaps = dict(tracing.idle_gaps(tr))
    assert run.load_reader("step_host_ms.serve")(obs) == pytest.approx(
        1e3 * gaps["scheduler.step"] / 4)
    # no prefill ran in the recorded steps: its readers return nothing
    counters["prefills"] = 0
    for name in ("prefill_ms.serve", "prefill_mfu.serve"):
        assert run.load_reader(name)(obs) is None


def test_every_per_layer_metric_has_a_reader_and_moves_a_reported_metric():
    import json

    from chipbench import run

    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    for cell in bench["workloads"]:
        e2e, layer = run.reported(bench, cell["name"])
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2 and layer
        for m in layer:
            assert m["moves"] in names, (cell["name"], m["name"])
            assert callable(run.load_reader(m["name"]))
