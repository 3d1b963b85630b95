"""The trace reduction: busy union, idle share, time per executable and per
kernel, idle gaps named by the host; on hand-made intervals and on a small
trace recorded on one v5e (``bench/tests/data/fleet_v5e.xplane.pb``)."""
from pathlib import Path

import pytest

from harness import trace

RECORDED = Path(__file__).parent / "data" / "fleet_v5e.xplane.pb"


def test_union_and_clip():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (4.0, 4.5), (6.0, 7.0)]
    assert trace.union(iv) == [(0.0, 2.0), (3.0, 4.5), (6.0, 7.0)]
    assert trace.clip(trace.union(iv), 1.0, 6.5) == [
        (1.0, 2.0), (3.0, 4.5), (6.0, 6.5)]


def _hand_trace():
    ops = {"jit__stream_step_pool_impl/fusion.1": trace.OpTotal(0.5, 2),
           "jit__stream_step_pool_impl/vmap_jit_streaming_logits__.2":
               trace.OpTotal(1.0, 4)}
    mods = {"jit__stream_step_pool_impl": trace.OpTotal(2.0, 4)}
    dev = trace.DeviceTrace("/device:TPU:0", [(0.0, 1.0), (2.0, 3.0)],
                            mods, ops)
    host = [(1.0, 2.0, "bench.step"), (1.2, 1.8, "_snapshot_slot"),
            (3.0, 4.0, "bench.record")]
    return trace.Trace(window=(0.0, 4.0), devices=[dev], host=host)


def test_shares_and_times_by_hand():
    tr = _hand_trace()
    assert tr.window_s == 4.0
    assert tr.busy_s() == 2.0
    assert tr.idle_share() == pytest.approx(50.0)
    assert tr.module_time("_stream_step_pool") == (2.0, 4)
    assert tr.op_time("streaming_logits") == (1.0, 4)
    assert tr.op_time("absent") == (0.0, 0)
    assert tr.op_time("step_pool") == (0.0, 0)     # a module is no op name
    assert tr.top_ops(1) == [
        ["jit__stream_step_pool_impl/vmap_jit_streaming_logits__.2", 1.0]]


def test_idle_gaps_named_by_innermost_host_event():
    gaps = dict(map(tuple, _hand_trace().idle_gaps()))
    assert gaps == {"_snapshot_slot": pytest.approx(1.0),
                    "bench.record": pytest.approx(1.0)}


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trace():
    tr = trace.reduce(str(RECORDED))
    assert [d.name for d in tr.devices] == ["/device:TPU:0"]
    assert 0.0 < tr.busy_s() <= tr.window_s
    assert 0.0 <= tr.idle_share() < 100.0
    secs, steps = tr.module_time("_stream_step_pool")
    assert steps >= 1 and 0.0 < secs <= tr.busy_s() + 1e-9
    k_secs, k_n = tr.op_time("streaming_logits")
    assert k_n >= steps and 0.0 < k_secs < secs
    assert len(tr.top_ops()) == 10
    assert sum(v for _, v in tr.idle_gaps()) == pytest.approx(
        tr.window_s - tr.busy_s(), rel=1e-6)


def test_every_reader_reads_a_trace():
    """Each per-layer metric of the cell reads a number from a trace and
    the driver's counts, and a share stays within 0-100 %."""
    from harness.common import Cell, read_per_layer

    ctx = {"trace": _hand_trace(), "window_s": 4.0, "steps": 4,
           "dispatch_s": [0.01, 0.03],
           "fleet_ops": 1e9, "kernel_ops": 1e6, "kernel_bytes": 1e6,
           "chips": 1, "device_kind": "TPU v5 lite"}
    cell = Cell("arab-fleet-saturated")
    got = read_per_layer(cell, ctx)
    assert set(got) == {m["name"] for m in cell.per_layer}
    for name, m in got.items():
        assert m["value"] >= 0.0
        if m["unit"] == "%":
            assert m["value"] <= 100.0, name


def test_a_reader_that_reads_nothing_ends_the_run():
    from harness.common import Cell, read_per_layer

    ctx = {"trace": trace.Trace(window=(0.0, 4.0), devices=[
        trace.DeviceTrace("/device:TPU:0", [(0.0, 1.0)], {}, {})], host=[]),
        "window_s": 4.0, "steps": 4, "dispatch_s": [0.01],
        "fleet_ops": 1e9, "kernel_ops": 1e6, "kernel_bytes": 1e6,
        "chips": 1, "device_kind": "TPU v5 lite"}
    with pytest.raises(RuntimeError, match="step_device_ms.fleet"):
        read_per_layer(Cell("arab-fleet-saturated"), ctx)
