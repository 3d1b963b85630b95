"""The profiler trace of a window and its reduction.

A traced run records the measured window with ``jax.profiler`` (device
activity, and the host's own annotations; no Python call tracing), then
reduces the ``.xplane.pb`` to:

* the window: the span of the host annotation ``bench.window``;
* per device: the union of the intervals in which an operation ran (busy
  time, so idle share = 1 - busy / window), clipped to the window;
* per device: total time by executable (the ``XLA Modules`` line, the jit
  module names such as ``jit__stream_step_pool_impl``) with the number of
  executions, and by operation (the ``XLA Ops`` line, HLO instruction
  names such as ``vmap_jit_streaming_logits__.2``, the serving kernel's
  custom call), each under its module;
* the device's idle gaps, each named after the innermost host event that
  spans its middle.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
import shutil
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
#: idle gaps shorter than this lie between back-to-back device operations
LAUNCH_GAP_S = 20e-6
#: host events searched backwards from a gap's middle for the innermost one
HOST_LOOKBACK = 400
_SUFFIX = re.compile(r"\(\d+\)$")


@contextlib.contextmanager
def capture(log_dir: Path):
    """Record a profiler trace into ``log_dir`` (emptied first)."""
    import jax

    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def xplane_file(log_dir: Path) -> str:
    found = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class OpTotal:
    seconds: float = 0.0
    count: int = 0


@dataclasses.dataclass
class DeviceTrace:
    name: str
    busy: List[Tuple[float, float]]            # merged, in seconds
    modules: Dict[str, OpTotal]
    ops: Dict[str, OpTotal]

    def busy_s(self) -> float:
        return sum(e - s for s, e in self.busy)


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]                # seconds
    devices: List[DeviceTrace]
    host: List[Tuple[float, float, str]]       # host events (s, e, name)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return sum(d.busy_s() for d in self.devices) / len(self.devices)

    def idle_share(self) -> float:
        """Worst device's idle share of the window, in %."""
        return max(100.0 * (1.0 - d.busy_s() / self.window_s)
                   for d in self.devices)

    def module_time(self, pattern: str) -> Tuple[float, int]:
        """(seconds averaged over devices, executions on the first device)
        of executables whose name contains ``pattern``."""
        secs = [sum(t.seconds for n, t in d.modules.items() if pattern in n)
                for d in self.devices]
        count = sum(t.count for n, t in self.devices[0].modules.items()
                    if pattern in n)
        return sum(secs) / len(secs), count

    def op_time(self, *patterns: str) -> Tuple[float, int]:
        """(seconds averaged over devices, events on the first device) of
        operations whose HLO name holds one of ``patterns``."""
        def hit(n):
            return any(p in n.rsplit("/", 1)[-1] for p in patterns)
        secs = [sum(t.seconds for n, t in d.ops.items() if hit(n))
                for d in self.devices]
        count = sum(t.count for n, t in self.devices[0].ops.items() if hit(n))
        return sum(secs) / len(secs), count

    def top_ops(self, n: int = 10) -> List[list]:
        d = self.devices[0]
        top = sorted(d.ops.items(), key=lambda kv: -kv[1].seconds)[:n]
        return [[name, t.seconds] for name, t in top]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Idle seconds of the first device summed by what the host was
        doing: each gap of at least ``LAUNCH_GAP_S`` is named after the
        innermost host event spanning its middle ("idle" where none
        does); shorter gaps, between back-to-back operations, are summed
        as "between ops"."""
        lo, hi = self.window
        gaps, cur = [], lo
        for s, e in self.devices[0].busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < hi:
            gaps.append((cur, hi))
        host = sorted(self.host)
        starts = [h[0] for h in host]
        by_name: Dict[str, float] = defaultdict(float)
        for s, e in gaps:
            if e - s < LAUNCH_GAP_S:
                by_name["between ops"] += e - s
                continue
            mid = 0.5 * (s + e)
            best = None
            i = bisect.bisect_right(starts, mid)
            for hs, he, name in reversed(host[max(0, i - HOST_LOOKBACK):i]):
                if he >= mid and (best is None or he - hs < best[1] - best[0]):
                    best = (hs, he, name)
            by_name[best[2] if best else "idle"] += e - s
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in top]


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except Exception:  # stats of an event the reader cannot decode
        return {}


def reduce(path: str) -> Trace:
    """Reduce one ``.xplane.pb`` (see the module docstring)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    window: Optional[Tuple[float, float]] = None
    host: List[Tuple[float, float, str]] = []
    devices: List[DeviceTrace] = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    if ev.name == WINDOW:
                        window = (s, e)
                    elif ev.duration_ns > 0:
                        host.append((s, e, _SUFFIX.sub("", ev.name)))
        elif re.match(r"/device:(TPU|GPU):\d+$", plane.name):
            ops: Dict[str, OpTotal] = defaultdict(OpTotal)
            modules: Dict[str, OpTotal] = defaultdict(OpTotal)
            op_iv, mod_iv = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    target, ivs = ops, op_iv
                elif line.name == "XLA Modules":
                    target, ivs = modules, mod_iv
                else:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    ivs.append((s, e))
                    name = _SUFFIX.sub("", ev.name)
                    if target is ops:
                        module = _stats(ev).get("hlo_module")
                        if module:
                            name = f"{_SUFFIX.sub('', str(module))}/{name}"
                    t = target[name]
                    t.count += 1
                    t.seconds += e - s
            devices.append(DeviceTrace(plane.name, union(op_iv or mod_iv),
                                       dict(modules), dict(ops)))
    if not devices:
        raise ValueError(f"{path}: no device plane")
    if window is None:
        lo = min(s for d in devices for s, _ in d.busy)
        hi = max(e for d in devices for _, e in d.busy)
        window = (lo, hi)
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    for d in devices:
        d.busy = clip(d.busy, *window)
    return Trace(window=window, devices=devices, host=host)
