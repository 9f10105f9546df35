"""Roofline share of the relay's compiled ``sparse_axpy`` kernel, in %.

Least time of each call (by HBM bytes: the kernel works in float32, for
which the v5e has no published peak) over the kernel's measured time.
Moves ``solve_s``."""
from chipbench import costs, kernels, peaks, tracing


def read(obs):
    evs = kernels.events(obs.trace, "sparse_axpy")
    if not evs:
        return None
    c = obs.counters
    flops, nbytes = costs.sparse_axpy(c["n_nodes"], c["d"], c["k"],
                                      c["itemsize"])
    return peaks.roofline_share(flops, nbytes * len(evs),
                                tracing.seconds(evs), obs.peaks)
