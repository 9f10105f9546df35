"""Roofline share of the latent (MLA) decode-attention kernel, in %.

Least time over the window's calls (one per layer per decode step): each
cached latent row (kv_lora_rank + rope values, bf16) of every slot still
active after the step read once, the absorbed queries read and the
latent outputs written, over the kernel's measured time (slots that
finish in a step are not counted, so the share reads a little low).
Moves ``ttft_p50_ms``, the cell's judged metric: a first token is
stamped after the decode step that follows its prefill."""
from chipbench import costs_mla_moe, kernels, peaks, tracing


def read(obs):
    c = obs.counters
    k = c.get("mla_moe")
    evs = kernels.events(obs.trace, "mla_decode_attention")
    if k is None or not evs or len(evs) != k["n_layers"] * c["decode_steps"]:
        return None
    flops, nbytes = costs_mla_moe.mla_decode(
        c["kv_tokens"], c["max_batch"] * c["decode_steps"], k["heads"],
        k["kv_lora"] + k["rope"], k["kv_lora"], 2)
    n = k["n_layers"]
    return peaks.roofline_share(flops * n, nbytes * n, tracing.seconds(evs),
                                obs.peaks)
