"""Model FLOP/s utilization of the prefill program, in %.

Model FLOPs of the window's prompts (their own tokens, not the padding to
``prompt_pad``) over the prefill program's device time x the bf16 peak:
the whole prefill step's share of the chip's peak, which bounds the flash
kernel's roofline share from above. Moves ``ttft_p50_ms``."""
from chipbench import costs, kernels, tracing


def read(obs):
    c = obs.counters
    n = c.get("prefills")
    prog = kernels.program_with(obs.trace, "flash_attention", n) if n else None
    if prog is None:
        return None
    flops = costs.prefill_flops(c["model"], c["prompt_tokens"],
                                c["prompt_sq"], n)
    return 100.0 * flops / (tracing.seconds(prog) * obs.peaks.bf16_flops)
