"""Model FLOP/s utilization of the paged decode program, in %.

Model FLOPs of one token per active slot in each of the window's decode
steps (every layer, the output head, attention over its context) over the
decode program's device time x the bf16 peak: the whole decode step's
share of the chip's peak, which bounds the decode-attention kernel's from
above. Moves ``itl_p95_ms``."""
from chipbench import costs, kernels, tracing


def read(obs):
    c = obs.counters
    prog = kernels.program_with(obs.trace, "decode_attention")
    if prog is None or len(prog) != c["decode_steps"]:
        return None
    flops = costs.decode_flops(c["model"], c["decode_tokens"], c["kv_tokens"])
    return 100.0 * flops / (tracing.seconds(prog) * obs.peaks.bf16_flops)
