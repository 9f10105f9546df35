"""Share of the traced window in which no op ran on the device, in %.
Moves ``solve_s``."""
from chipbench import tracing


def read(obs):
    return tracing.idle_share(obs.trace)
