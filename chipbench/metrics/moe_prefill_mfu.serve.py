"""Model FLOP/s utilization of Kimi-K2's prefill program, in %.

Model FLOPs of the window's prompts (their own tokens, not the padding to
``prompt_pad``; routed experts counted by the program's route counter
only) over the prefill program's device time x the bf16 peak: the whole
prefill step's share of the chip's peak. The prefill program is the one
in which the flash-attention kernel runs as often as the scheduler ran
prefills. Moves ``ttft_p50_ms``."""
from chipbench import costs_mla_moe, kernels, tracing


def read(obs):
    c = obs.counters
    k = c.get("mla_moe")
    n = c.get("prefills")
    prog = kernels.program_with(obs.trace, "flash_attention", n) if n else None
    if k is None or prog is None:
        return None
    flops = costs_mla_moe.prefill_flops(k, c["prompt_tokens"], c["prompt_sq"],
                                        n, c["routes_prefill"])
    return 100.0 * flops / (tracing.seconds(prog) * obs.peaks.bf16_flops)
