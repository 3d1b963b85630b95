"""The readers of the stream server's host spans (``harness.spans`` and
``bench/metrics/<phase>_host_ms.fleet.py``): by hand on a made trace, and
on the spans a CPU run of the fleet cell (`drivers.fleet`) records."""
import glob
import math
import time

import jax
import pytest

from conftest import tiny_cell
from harness import trace
from harness.common import load_reader

READERS = {"admit_host_ms.fleet": "stream.admit",
           "enqueue_host_ms.fleet": "stream.enqueue",
           "retire_host_ms.fleet": "stream.retire",
           "drain_host_ms.fleet": "stream.drain",
           "stage_host_ms.fleet": "stream.stage"}


def _trace(host):
    dev = trace.DeviceTrace("/device:TPU:0", [(0.0, 1.0)], {}, {})
    return trace.Trace(window=(0.0, 4.0), devices=[dev], host=host)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_sums_its_spans_that_start_in_the_window(metric):
    span = READERS[metric]
    host = [(0.5, 0.6, span), (1.0, 1.25, span),
            (-0.5, 0.1, span),              # starts before the window
            (3.75, 4.5, span),              # starts inside, ends after
            (4.0, 4.1, span),               # starts at the window's end
            (1.0, 3.0, span + "_x"),        # another name
            (0.0, 4.0, "bench.step")]
    got = load_reader(metric)({"trace": _trace(host), "steps": 5})
    assert got == pytest.approx(1e3 * (0.1 + 0.25 + 0.75) / 5)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_reads_none_without_its_span(metric):
    read = load_reader(metric)
    others = [(0.5, 0.6, n) for n in READERS.values() if n != READERS[metric]]
    assert read({"trace": _trace(others), "steps": 4}) is None
    assert read({"trace": _trace([(-1.0, 0.5, READERS[metric])]),
                 "steps": 4}) is None
    assert read({"trace": None, "steps": 4}) is None
    assert read({"trace": _trace([(0.5, 0.6, READERS[metric])]),
                 "steps": 0}) is None


def _host_events(log_dir):
    """(window, [(s, e, name)]) of a recorded trace's host plane."""
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    window, host = None, []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if ev.name == trace.WINDOW:
                    window = (s, e)
                else:
                    host.append((s, e, ev.name))
    return window, host


def test_readers_read_a_recorded_fleet_window(cpu, tmp_path):
    """The fleet cell's window, recorded on the CPU: every reader finds
    its spans, one enqueue per window step and some retirements."""
    from drivers import fleet

    cell = tiny_cell("arab-fleet-saturated")
    jax.clear_caches()
    try:
        res = fleet.run(cell, 20260101, 1.0, cpu, time.perf_counter(),
                        tracer=lambda: trace.capture(tmp_path / "tr"))
    finally:
        jax.clear_caches()
    window, host = _host_events(tmp_path / "tr")
    steps = res["ctx"]["steps"]
    dev = trace.DeviceTrace("/device:TPU:0", [window], {}, {})
    ctx = {"trace": trace.Trace(window=window, devices=[dev], host=host),
           "steps": steps}
    for metric in READERS:
        got = load_reader(metric)(ctx)
        assert got is not None and math.isfinite(got) and got > 0, metric
    lo, hi = window
    n = {name: sum(1 for s, _e, h in host if h == name and lo <= s < hi)
         for name in READERS.values()}
    assert n["stream.enqueue"] == steps
    assert n["stream.retire"] >= steps // 2 > 0
