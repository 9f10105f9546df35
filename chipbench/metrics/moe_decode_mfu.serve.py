"""Model FLOP/s utilization of Kimi-K2's paged decode program, in %.

Model FLOPs of one token per active slot in each of the window's decode
steps (every layer, routed experts counted by the program's route
counter only, the output head, attention over its context in the model's
expanded form) over the decode program's device time x the bf16 peak: the
whole decode step's share of the chip's peak. Moves ``ttft_p50_ms``, the
cell's judged metric: a first token is stamped after the decode step that
follows its prefill."""
from chipbench import costs_mla_moe, kernels, tracing


def read(obs):
    c = obs.counters
    k = c.get("mla_moe")
    prog = kernels.program_with(obs.trace, "mla_decode_attention")
    if k is None or prog is None or len(prog) != c["decode_steps"]:
        return None
    flops = costs_mla_moe.decode_flops(k, c["decode_tokens"], c["kv_tokens"],
                                       c["routes_decode"])
    return 100.0 * flops / (tracing.seconds(prog) * obs.peaks.bf16_flops)
