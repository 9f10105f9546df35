"""Roofline share of the held experts' grouped matmul in decode, in %.

Over the decode program's expert_matmul calls (gate, up and down in each
MoE layer of each decode step): least time from the program's route
counter (``ServeStats``), the weights of each held expert with at least
one route in a step read once and the routed rows' activations read and
written, 6 d f FLOPs a route, over the kernel's measured time. Moves
``ttft_p50_ms``, the cell's judged metric: a first token is stamped after
the decode step that follows its prefill."""
from chipbench import costs_mla_moe, kernels, peaks, tracing


def read(obs):
    c = obs.counters
    k = c.get("mla_moe")
    prog = kernels.program_with(obs.trace, "mla_decode_attention")
    if k is None or prog is None or not c.get("routes_decode"):
        return None
    evs = kernels.events_in(obs.trace, prog, "expert_matmul")
    if len(evs) != 3 * (k["n_layers"] - k["n_dense"]) * c["decode_steps"]:
        return None
    flops, nbytes = costs_mla_moe.expert_matmul(
        c["routes_decode"], c["experts_hit_decode"], k["d"], k["f"], 2)
    return peaks.roofline_share(flops, nbytes, tracing.seconds(evs),
                                obs.peaks)
