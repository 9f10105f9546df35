"""Device time per prefill program, in ms.

The prefill program is the one in which the flash-attention kernel runs
and whose event count in the window matches the prefills the scheduler
ran there. Moves ``ttft_p50_ms``: a request's first token comes from its
prefill, and a burst's prefills run one after another."""
from chipbench import kernels, tracing


def read(obs):
    n = obs.counters.get("prefills")
    prog = kernels.program_with(obs.trace, "flash_attention", n) if n else None
    if prog is None:
        return None
    return 1e3 * tracing.seconds(prog) / n
