"""Device idle time inside the scheduler's step, per step, in ms.

The host's own work in ``Scheduler.step`` while no op runs on the device
(the logits fetched to the host, sampling in NumPy, admission and
bookkeeping): the trace's idle gaps under the benchmark's
``scheduler.step`` span over the window's steps. Moves ``itl_p95_ms``."""
from chipbench import tracing


def read(obs):
    steps = obs.counters.get("steps")
    gaps = dict(tracing.idle_gaps(obs.trace, n=len(obs.trace["host"]) + 1))
    if not steps or "scheduler.step" not in gaps:
        return None
    return 1e3 * gaps["scheduler.step"] / steps
