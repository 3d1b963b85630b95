"""Host spans of the program read from a reduced trace.

The stream server writes one profiler span per host phase of its serving
loop (``repro.runtime.tracing``: ``stream.admit``, ``stream.enqueue``,
``stream.retire``, ``stream.drain``, ``stream.stage``).  A reader of such
a phase sums the durations of the host events of that exact name that
start inside the window, per window step.
"""
from __future__ import annotations

from typing import Optional


def host_ms_per_step(ctx: dict, name: str) -> Optional[float]:
    """Summed duration of the host events named ``name`` that start in
    the window, over the window's steps; ms.  None where the window holds
    no such event (or no trace, or no step)."""
    tr = ctx.get("trace")
    if tr is None or not ctx.get("steps"):
        return None
    lo, hi = tr.window
    secs = [e - s for s, e, n in tr.host if n == name and lo <= s < hi]
    if not secs:
        return None
    return 1e3 * sum(secs) / ctx["steps"]
