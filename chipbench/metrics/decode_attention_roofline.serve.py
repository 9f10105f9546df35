"""Roofline share of the paged decode-attention kernel, in %.

Least time over the window's calls (one per layer per decode step): K and
V of every context token of every slot still active after the step read
once, bf16, over the kernel's measured time (slots that finish in a step
are not counted, so the share reads a little low). Moves
``itl_p95_ms``."""
from chipbench import costs, kernels, peaks, tracing


def read(obs):
    evs = kernels.events(obs.trace, "decode_attention")
    c = obs.counters
    m = c["model"]
    if not evs or len(evs) != m["n_layers"] * c["decode_steps"]:
        return None
    flops, nbytes = costs.decode_attention(
        c["kv_tokens"], c["max_batch"] * c["decode_steps"], m["n_heads"],
        m["n_kv_heads"], m["head_dim"], 2)
    return peaks.roofline_share(flops * m["n_layers"], nbytes * m["n_layers"],
                                tracing.seconds(evs), obs.peaks)
