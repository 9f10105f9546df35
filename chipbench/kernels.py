"""How a kernel and a program show in the device trace.

A compiled Pallas kernel is an HLO custom call; the profiler names its
event by the HLO instruction, which carries the name of the JAX function
that made the call: ``%saga_sparse_axpy.12 = f32[10,47236]{...}
custom-call(...)``, ``%decode_attention.9 = bf16[64,32,128]{...}
custom-call(...)`` (names read from a v5e trace). A kernel is found by
that name in a custom call.
"""
from __future__ import annotations

from chipbench import tracing


def is_kernel(text: str, kernel: str) -> bool:
    """True for an op event of ``kernel``'s custom call (``kernel`` is one
    of ``sparse_axpy``, ``flash_attention``, ``decode_attention``)."""
    return kernel in tracing.op_name(text) and " custom-call(" in text


def events(trace: dict, kernel: str) -> list:
    """The window's events of one kernel, over every device."""
    return tracing.matching(trace, lambda name: is_kernel(name, kernel))


def events_in(trace: dict, program_events: list, kernel: str) -> list:
    """The events of ``kernel`` that ran inside the given program events."""
    spans = sorted((ev[0], ev[0] + ev[1]) for ev in program_events)
    import bisect

    starts = [s for s, _ in spans]
    out = []
    for ev in events(trace, kernel):
        i = bisect.bisect_right(starts, ev[0]) - 1
        if i >= 0 and ev[0] + ev[1] <= spans[i][1]:
            out.append(ev)
    return out


def programs(trace: dict) -> dict[str, list]:
    """The window's program (XLA module) events, grouped by program name."""
    out: dict[str, list] = {}
    for ev in tracing.module_events(trace, lambda name: True):
        out.setdefault(ev[2], []).append(ev)
    return out


def program_with(trace: dict, kernel: str, count: int | None = None):
    """The events of the one program in which ``kernel`` runs (and that
    ran ``count`` times in the window, where given), or None."""
    progs = [evs for evs in programs(trace).values()
             if (count is None or len(evs) == count)
             and events_in(trace, evs, kernel)]
    return progs[0] if len(progs) == 1 else None
