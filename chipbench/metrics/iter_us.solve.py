"""Device time of the compiled runner per solver iteration, in us.

The runner is the program that took most device time in the window (the
dense backend's chunked scan, the relay's whole-run scan); its events'
summed duration over the iterations run in the window. Moves
``solve_s``."""
from chipbench import kernels, tracing


def read(obs):
    progs = kernels.programs(obs.trace)
    iters = obs.counters.get("iterations")
    if not progs or not iters:
        return None
    runner = max(progs.values(), key=tracing.seconds)
    return 1e6 * tracing.seconds(runner) / iters / obs.chips
