"""Roofline share of the prefill flash-attention kernel, in %: the least
time of each call at its padded shape (bf16, causal) over the kernel's
measured time. Moves ``ttft_p50_ms``: a request's first token comes from
its prefill."""
from chipbench import costs, kernels, peaks, tracing


def read(obs):
    evs = kernels.events(obs.trace, "flash_attention")
    if not evs:
        return None
    m = obs.counters["model"]
    flops, nbytes = costs.flash_attention(
        1, m["n_heads"], m["n_kv_heads"], obs.counters["prompt_pad"],
        m["head_dim"], 2)
    return peaks.roofline_share(flops * len(evs), nbytes * len(evs),
                                tracing.seconds(evs), obs.peaks)
