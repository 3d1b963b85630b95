"""Device-resident serving pipeline (PR 5): equivalence + safety battery.

The contracts under test (see runtime/stream_server.py module docstring):

  * device staging (pool gather + folded cohort refresh, one dispatch) is
    bit-for-bit the PR-4 host-staged path over a full multi-admission /
    retire episode, in every retirement mode;
  * async pipelining (depth 1/2, donated) is bit-for-bit the synchronous
    depth-0 schedule (the lag only defers metric bookkeeping);
  * buffer donation never changes numerics, and the retirement snapshots
    (``_snapshot_rows``, one or several rows a program) stay valid after
    later donated steps consume the batched state they were read from (no
    use-after-donate);
  * the batched snapshot of the streams that retire in one step is bit
    for bit the single-slot snapshot of each, padded rows dropped;
  * ``cfg.dtype`` is honored end to end (the PR-4 host staging hardcoded
    float32, silently upcasting bf16 configs);
  * ``run_until_drained(max_steps)`` truncation is never silent;
  * latency records ride bounded ring buffers and split dispatch (host
    enqueue) from drain (device sync) honestly.
"""
import dataclasses
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.online import init_state
from repro.core.types import DFRConfig
from repro.runtime import StreamRequest, StreamServer
from repro.runtime import stream_server as ss


CFG = DFRConfig(n_in=2, n_classes=3, n_nodes=8)

# every retirement mode, with the server kwargs it needs
RETIREMENT_MODES = (
    ("none", {}),
    ("none-inc", {"refresh_mode": "incremental"}),
    ("forget", {"refresh_mode": "incremental", "retirement": "forget",
                "forget": 0.9}),
    ("window", {"refresh_mode": "incremental", "retirement": "window",
                "retire_window": 6}),
    ("adaptive", {"refresh_mode": "incremental", "retirement": "adaptive"}),
)


def _make_stream(rid, n, t=16, seed=0, n_in=2, n_classes=3):
    r = np.random.default_rng(seed)
    return StreamRequest(
        rid=rid,
        u=r.normal(size=(n, t, n_in)).astype(np.float32),
        length=r.integers(4, t + 1, n).astype(np.int32),
        label=r.integers(0, n_classes, n).astype(np.int32),
    )


def _episode_streams(seed0=0):
    """More streams than slots and ragged lengths: the episode exercises
    admission, tail windows, retirement and slot refill."""
    return [_make_stream(i, n, seed=seed0 + i)
            for i, n in enumerate([8, 6, 10, 4, 7])]


def _serve(streams=None, cfg=CFG, **kw):
    srv = StreamServer(cfg, t_max=16, max_streams=3, window=2,
                       phase_steps=2, refresh_every=3, **kw)
    for s in (streams if streams is not None else _episode_streams()):
        srv.submit(s)
    done = srv.run_until_drained()
    return {r.rid: list(r.preds) for r in done}, srv


def _assert_states_bitwise_equal(sa, sb):
    for a, b in zip(jax.tree_util.tree_leaves(sa),
                    jax.tree_util.tree_leaves(sb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _assert_states_equal_cross_program(sa, sb):
    """Bitwise on every serving-relevant leaf (params, ridge statistics,
    factor, counters); the ``loss_ema`` *diagnostic* is compared to ~1 ulp
    instead - the host-staged and device-staged executables are different
    XLA programs, and the loss reduction may fuse with a different
    association order in each (observed: 1-ulp drift at fp32).  Predictions
    and the entire model state are still required to match exactly."""
    _assert_states_bitwise_equal(sa.params, sb.params)
    _assert_states_bitwise_equal(sa.ridge, sb.ridge)
    np.testing.assert_array_equal(np.asarray(sa.step), np.asarray(sb.step))
    a = np.asarray(sa.loss_ema, np.float32)
    b = np.asarray(sb.loss_ema, np.float32)
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)


# ---------------------------------------------------------------------------
# Device staging == host staging, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,kw", RETIREMENT_MODES,
                         ids=[m for m, _ in RETIREMENT_MODES])
def test_device_pool_is_bitwise_the_host_path(mode, kw):
    """The cursor-gathered device batch and the folded cohort refresh serve
    a full admission/retire episode bit-for-bit identically to the PR-4
    host-staged build (depth 0; donation exercised on the device side)."""
    preds_h, srv_h = _serve(staging="host", donate=False, **kw)
    preds_d, srv_d = _serve(staging="device", donate=True, **kw)
    assert preds_h == preds_d
    _assert_states_equal_cross_program(srv_h.states, srv_d.states)
    for a, b in zip(sorted(srv_h.completed, key=lambda r: r.rid),
                    sorted(srv_d.completed, key=lambda r: r.rid)):
        _assert_states_equal_cross_program(a.final_state, b.final_state)


def test_device_pool_matches_host_under_staggered_cohorts():
    """Cohort staggering (C=2, uneven cohorts -> padded fixed-shape rows in
    the fused refresh) also matches the host path's row refresh exactly."""
    for kw in ({"refresh_cohorts": 2},
               {"refresh_cohorts": 2, "refresh_mode": "incremental"}):
        preds_h, srv_h = _serve(staging="host", donate=False, **kw)
        preds_d, srv_d = _serve(**kw)
        assert preds_h == preds_d
        _assert_states_equal_cross_program(srv_h.states, srv_d.states)


# ---------------------------------------------------------------------------
# Pipelining: depth D == depth 0, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,kw", RETIREMENT_MODES,
                         ids=[m for m, _ in RETIREMENT_MODES])
@pytest.mark.parametrize("depth", [1, 2])
def test_pipelined_serving_is_bitwise_the_synchronous_path(depth, mode, kw):
    """Depth-1/2 donated pipelining serves the multi-admission episode
    bit-for-bit like synchronous depth 0: the lag-D prediction ring defers
    only bookkeeping, never the serving schedule."""
    preds_0, srv_0 = _serve(pipeline_depth=0, **kw)
    preds_d, srv_d = _serve(pipeline_depth=depth, **kw)
    assert preds_0 == preds_d
    _assert_states_bitwise_equal(srv_0.states, srv_d.states)
    for a, b in zip(sorted(srv_0.completed, key=lambda r: r.rid),
                    sorted(srv_d.completed, key=lambda r: r.rid)):
        assert a.correct == b.correct
        assert b.done
        _assert_states_bitwise_equal(a.final_state, b.final_state)


# ---------------------------------------------------------------------------
# One pool step program per step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kw", [
    {}, {"pipeline_depth": 2}, {"quantize": "int8"},
    {"refresh_mode": "incremental", "retirement": "adaptive"},
], ids=["sync", "pipelined", "int8", "adaptive"])
def test_one_pool_step_program_per_step(monkeypatch, kw):
    """Each ``step()`` dispatches exactly one step program, the donated
    pool step, refresh rounds included: the host-staged step and its
    separate refresh programs are never called.  The count goes through
    the module-level name the server looks up at call time."""
    calls = []
    real = ss._stream_step_pool_donated

    def counted(*a, **k):
        calls.append(k["window"])
        return real(*a, **k)

    def never(*a, **k):
        raise AssertionError("a second step program was dispatched")

    monkeypatch.setattr(ss, "_stream_step_pool_donated", counted)
    for name in ("_stream_step_pool", "_stream_step", "_stream_step_donated",
                 "_stream_refresh_rows", "_stream_refresh_rows_donated",
                 "_stream_refresh_factor_rows",
                 "_stream_refresh_factor_rows_donated"):
        monkeypatch.setattr(ss, name, never)
    srv = StreamServer(CFG, t_max=16, max_streams=3, window=2,
                       phase_steps=2, refresh_every=3, **kw)
    for s in _episode_streams():
        srv.submit(s)
    steps = 0
    while srv.sched.active():
        before = len(calls)
        srv.step()
        steps += 1
        assert len(calls) == before + 1
    srv.drain()
    assert len(calls) == steps == srv.global_step
    # the episode crossed refresh rounds, each folded into its step
    assert srv.global_step > srv.refresh_every
    for r in srv.completed:
        assert len(r.preds) == r.n_samples


# ---------------------------------------------------------------------------
# Donation safety
# ---------------------------------------------------------------------------


def test_donation_preserves_numerics_and_snapshots():
    """donate=True vs donate=False: identical predictions and identical
    retirement snapshots - and every snapshot gathered on the ``_snapshot_
    row`` path stays finite and readable after many later donated steps
    consumed the batched state it came from (no use-after-donate)."""
    preds_n, srv_n = _serve(donate=False, pipeline_depth=2)
    preds_y, srv_y = _serve(donate=True, pipeline_depth=2)
    assert preds_n == preds_y
    _assert_states_bitwise_equal(srv_n.states, srv_y.states)
    for a, b in zip(sorted(srv_n.completed, key=lambda r: r.rid),
                    sorted(srv_y.completed, key=lambda r: r.rid)):
        # snapshots of early-retired streams were taken many donated
        # dispatches ago; they must still be materializable and equal
        _assert_states_bitwise_equal(a.final_state, b.final_state)
        for leaf in jax.tree_util.tree_leaves(b.final_state):
            assert np.all(np.isfinite(np.asarray(leaf, np.float64)))


@pytest.mark.parametrize("slots", [[0], [1, 0]], ids=["one", "batched"])
def test_snapshot_survives_interleaved_donated_steps(slots):
    """Direct use-after-donate probe: snapshot live slots mid-episode (one
    row or two, one batched program either way), run more donated steps,
    then read the snapshots - their buffers must be independent of the
    donated state tree."""
    srv = StreamServer(CFG, t_max=16, max_streams=2, window=2,
                       phase_steps=1, refresh_every=2, donate=True)
    for s in _episode_streams():
        srv.submit(s)
    for _ in range(3):
        srv.step()
    snaps, programs = srv._snapshot_rows(slots)
    assert programs == 1 and len(snaps) == len(slots)
    ref = [[np.asarray(leaf).copy() for leaf in jax.tree_util.tree_leaves(sn)]
           for sn in snaps]
    for _ in range(4):
        srv.step()           # donated dispatches consume srv.states
    srv.drain()
    for sn, want in zip(snaps, ref):
        for leaf, r in zip(jax.tree_util.tree_leaves(sn), want):
            np.testing.assert_array_equal(np.asarray(leaf), r)


# ---------------------------------------------------------------------------
# Batched retirement snapshots == single-slot snapshots, bit for bit
# ---------------------------------------------------------------------------


def _bits(tree):
    """Each leaf's raw bytes: -0.0, NaN payloads and subnormals count."""
    return [np.asarray(leaf).reshape(-1).view(np.uint8)
            for leaf in jax.tree_util.tree_leaves(tree)]


def _assert_same_bits(sa, sb):
    for a, b in zip(_bits(sa), _bits(sb)):
        np.testing.assert_array_equal(a, b)


class _CheckedServer(StreamServer):
    """Records, beside every batch of retirement snapshots, the slots and
    the single-slot snapshot of each taken from the same batched state."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.snapshot_log = []

    def _snapshot_rows(self, slots):
        want = [ss._snapshot_slot(self.states, np.int32(i)) for i in slots]
        snaps, programs = super()._snapshot_rows(slots)
        self.snapshot_log.append((list(slots), snaps, want, programs))
        return snaps, programs


@pytest.mark.parametrize("kind,kw", [
    ("device", {}), ("host", {"staging": "host"}),
    ("int8", {"quantize": "int8"}),
], ids=["device", "host", "int8"])
@pytest.mark.parametrize("k", [1, 3, 8])
def test_batched_snapshots_are_bitwise_the_single_slot(k, kind, kw):
    """K streams that complete in the same step retire through one
    snapshot program, and each final model is bit for bit the single-slot
    snapshot of its row at that point; the other streams retire in steps
    of their own."""
    lengths = [4] * k + [6, 8, 10][:max(0, 8 - k)]
    streams = [_make_stream(i, n, seed=20 + i) for i, n in enumerate(lengths)]
    srv = _CheckedServer(CFG, t_max=16, max_streams=8, window=2,
                         phase_steps=1, refresh_every=2, **kw)
    for s in streams:
        srv.submit(s)
    done = srv.run_until_drained()
    assert len(done) == len(streams)
    assert [len(slots) for slots, *_ in srv.snapshot_log][0] == k
    for slots, snaps, want, programs in srv.snapshot_log:
        assert programs == 1
        assert len(snaps) == len(want) == len(slots)
        for got, ref in zip(snaps, want):
            _assert_same_bits(got, ref)
    # every final model is one of those snapshots, each stream its own
    snapped = [id(sn) for _, snaps, _, _ in srv.snapshot_log for sn in snaps]
    assert sorted(id(r.final_state) for r in done) == sorted(snapped)


def test_padded_bucket_rows_never_reach_a_final_state():
    """Three rows gather as a bucket of four (the first row repeated);
    only the three requested snapshots come back, each its own row."""
    srv = StreamServer(CFG, t_max=16, max_streams=4, window=2,
                       phase_steps=1, refresh_every=2)
    for s in [_make_stream(i, 12, seed=40 + i) for i in range(4)]:
        srv.submit(s)
    for _ in range(3):
        srv.step()
    assert ss._snapshot_bucket(3, 4) == 4
    slots = [2, 0, 3]
    snaps, programs = srv._snapshot_rows(slots)
    assert programs == 1 and len(snaps) == 3
    for i, got in zip(slots, snaps):
        _assert_same_bits(got, ss._snapshot_slot(srv.states, np.int32(i)))
    # slot 2 (the pad) differs from the others, so a leaked pad would show
    for got in snaps[1:]:
        assert any(not np.array_equal(a, b) for a, b in
                   zip(_bits(got), _bits(snaps[0])))
    ids = [id(leaf) for sn in snaps for leaf in jax.tree_util.tree_leaves(sn)]
    assert len(set(ids)) == len(ids)


def test_snapshot_programs_read_a_bounded_number_of_rows(monkeypatch):
    """A step that retires more rows than one program reads splits them
    into programs of at most ``SNAPSHOT_MAX_ROWS`` (each padded to its own
    bucket), so no larger bucket ever compiles."""
    monkeypatch.setattr(ss, "SNAPSHOT_MAX_ROWS", 2)
    buckets = []
    real = ss._snapshot_slots

    def counted(states, idx):
        buckets.append(idx.shape[0])
        return real(states, idx)

    monkeypatch.setattr(ss, "_snapshot_slots", counted)
    srv = StreamServer(CFG, t_max=16, max_streams=8, window=2,
                       phase_steps=1, refresh_every=2)
    for s in [_make_stream(i, 12, seed=60 + i) for i in range(8)]:
        srv.submit(s)
    for _ in range(3):
        srv.step()
    slots = [5, 1, 7, 2, 6]
    snaps, programs = srv._snapshot_rows(slots)
    assert programs == 3 and buckets == [2, 2, 1]
    for i, got in zip(slots, snaps):
        _assert_same_bits(got, ss._snapshot_slot(srv.states, np.int32(i)))


@pytest.mark.parametrize("k", [1, 3, 8])
def test_batched_snapshot_keeps_every_bit(k):
    """On arbitrary bit patterns - -0.0, subnormals, infinities, NaNs with
    payloads, planted in every row - the batched program copies the rows
    exactly: it is a selection, not arithmetic."""
    rng = np.random.default_rng(k)
    single = jax.eval_shape(lambda: init_state(CFG, factor_beta=0.01))

    def leaf(sd):
        raw = rng.integers(0, 2**32, size=(8, sd.size), dtype=np.uint64)
        raw = raw.astype(np.uint32)
        raw[:, :6] = [0x80000000, 0x00000001, 0x807FFFFF, 0x7FC00123,
                      0xFF800000, 0xFFFFFFFF][:sd.size]
        dtype = np.dtype(sd.dtype)
        if dtype.itemsize == 4:
            return raw.view(dtype).reshape((8, *sd.shape))
        return raw.astype(dtype).reshape((8, *sd.shape))

    host = jax.tree_util.tree_map(leaf, single)
    states = jax.tree_util.tree_map(jnp.asarray, host)
    rows = rng.choice(8, size=k, replace=False).astype(np.int32)
    width = ss._snapshot_bucket(k, 8)
    idx = np.full((width,), rows[0], np.int32)
    idx[:k] = rows
    got = ss._snapshot_slots(states, idx)
    assert len(got) == width
    for i, sn in zip(rows, got):
        _assert_same_bits(sn, jax.tree_util.tree_map(lambda h: h[i], host))


# ---------------------------------------------------------------------------
# dtype honored (PR-4 host staging hardcoded float32)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("staging", ["host", "device"])
def test_bf16_config_is_not_silently_upcast(staging):
    """A bf16 config must serve in bf16: the staged batch and the state
    leaves carry cfg.dtype on both staging paths (regression for the PR-4
    float32 hardcode), and both paths agree exactly."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)

    def fresh_streams():
        return [_make_stream(0, 6, seed=3), _make_stream(1, 4, seed=4)]

    preds, srv = _serve(fresh_streams(), cfg=cfg, staging=staging,
                        refresh_mode="incremental")
    assert srv.states.ridge.B.dtype == jnp.bfloat16
    assert srv.states.ridge.Lt.dtype == jnp.bfloat16
    assert srv.states.params.W.dtype == jnp.bfloat16
    if staging == "device":
        assert srv.pool.u.dtype == jnp.bfloat16
        # both staging paths quantize identically -> identical service
        preds_h, _ = _serve(fresh_streams(), cfg=cfg, staging="host",
                            refresh_mode="incremental")
        assert preds == preds_h
    for r in srv.completed:
        assert len(r.preds) == r.n_samples


# ---------------------------------------------------------------------------
# Pool capacity, truncation signaling, latency accounting
# ---------------------------------------------------------------------------


def test_pool_grows_for_longer_streams_submitted_later():
    """A stream longer than the current pool capacity grows the pool (and
    re-stages queued payloads); service stays exact for every stream."""
    srv = StreamServer(CFG, t_max=16, max_streams=2, window=2,
                       phase_steps=2, refresh_every=3)
    srv.submit(_make_stream(0, 4, seed=0))
    assert srv.pool.capacity == 4
    srv.submit(_make_stream(1, 9, seed=1))   # rounds up to window multiple
    assert srv.pool.capacity == 10
    for _ in range(2):
        srv.step()
    srv.submit(_make_stream(2, 13, seed=2))  # grows mid-service
    assert srv.pool.capacity == 14
    done = srv.run_until_drained()
    assert sorted(r.rid for r in done) == [0, 1, 2]
    for r in done:
        assert len(r.preds) == r.n_samples
    # exactness across the growth: same episode on the host path
    preds_d = {r.rid: list(r.preds) for r in done}
    preds_h, _ = _serve([_make_stream(0, 4, seed=0),
                         _make_stream(1, 9, seed=1),
                         _make_stream(2, 13, seed=2)],
                        staging="host", donate=False)
    # NOTE: submission timing differs (stream 2 arrives mid-episode above),
    # so only the first two streams see identical schedules
    assert preds_d[0] == preds_h[0]


@pytest.mark.parametrize("mode,kw", RETIREMENT_MODES,
                         ids=[m for m, _ in RETIREMENT_MODES])
def test_fused_infer_slots_dispatch_serves_through_the_pool(mode, kw):
    """The slot-axis fused-infer dispatch (`ops.streaming_logits_slots`,
    the TPU latency path exercised here through its XLA ref) serves the
    device-staged episode end to end in every retirement mode and agrees
    with the shared-forward inference on (nearly) every sample - the two
    compute the same math through different op orders, so borderline
    argmaxes may flip."""
    preds_f, srv = _serve(fused_infer=True, **kw)
    preds_s, _ = _serve(fused_infer=False, **kw)
    assert sorted(preds_f) == sorted(preds_s)
    total = agree = 0
    for rid in preds_f:
        assert len(preds_f[rid]) == len(preds_s[rid])
        total += len(preds_f[rid])
        agree += sum(int(a == b)
                     for a, b in zip(preds_f[rid], preds_s[rid]))
    assert agree / total >= 0.97
    for r in srv.completed:
        assert len(r.preds) == r.n_samples


def test_run_until_drained_truncation_is_not_silent():
    """Hitting max_steps with live streams warns with the undrained count;
    strict=True raises instead.  A full drain stays warning-free."""
    def build():
        srv = StreamServer(CFG, t_max=16, max_streams=1, window=2,
                           phase_steps=1, refresh_every=3)
        for s in _episode_streams():
            srv.submit(s)
        return srv

    srv = build()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        srv.run_until_drained(max_steps=2)
    assert any("still live or queued" in str(x.message) for x in w)

    with pytest.raises(RuntimeError, match="still live or queued"):
        build().run_until_drained(max_steps=2, strict=True)

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        done = build().run_until_drained()
    assert not [x for x in w if issubclass(x.category, RuntimeWarning)]
    assert len(done) == len(_episode_streams())


def test_latency_records_are_bounded_and_split():
    """step/dispatch/drain records ride a bounded ring and the percentile
    report carries the honest dispatch-vs-drain split."""
    srv = StreamServer(CFG, t_max=16, max_streams=2, window=2,
                       phase_steps=1, refresh_every=3, pipeline_depth=1,
                       latency_window=8)
    for s in _episode_streams():
        srv.submit(s)
    srv.run_until_drained()
    assert srv.global_step > 8          # the episode outran the ring
    assert len(srv.step_times_s) == 8   # ... which stayed bounded
    assert len(srv.dispatch_times_s) == 8
    assert 0 < len(srv.drain_times_s) <= 8
    lat = srv.latency_percentiles_ms()
    for key in ("p50_ms", "p99_ms", "dispatch_p50_ms", "dispatch_p99_ms",
                "drain_p50_ms", "drain_p99_ms"):
        assert key in lat and lat[key] >= 0.0
    # dispatch never includes the blocking read: it is bounded by the total
    assert lat["dispatch_p50_ms"] <= lat["p50_ms"] + 1e-6


def test_latency_empty_rings_report_nan_not_zero():
    """A server that never stepped has NO latency measurement - the report
    must say NaN, never a fake (and impossible) 0.0 ms percentile."""
    srv = StreamServer(CFG, t_max=16, max_streams=2, window=2,
                       phase_steps=1, refresh_every=3)
    lat = srv.latency_percentiles_ms()
    for key in ("p50_ms", "p99_ms", "dispatch_p50_ms", "dispatch_p99_ms",
                "drain_p50_ms", "drain_p99_ms"):
        assert np.isnan(lat[key]), key
    # one served episode populates every ring with real (finite) readings
    srv.submit(_make_stream(0, 4))
    srv.run_until_drained()
    lat = srv.latency_percentiles_ms()
    assert all(np.isfinite(v) for v in lat.values())


def test_truncation_warning_counts_live_and_queued():
    """The undrained count in the truncation warning must be live + queued
    - 5 streams through 1 slot stopped at step 2 leaves all 5 undrained
    (none of the episode's streams finishes in 2 windows)."""
    srv = StreamServer(CFG, t_max=16, max_streams=1, window=2,
                       phase_steps=1, refresh_every=3)
    streams = _episode_streams()
    for s in streams:
        srv.submit(s)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        srv.run_until_drained(max_steps=2)
    msgs = [str(x.message) for x in w
            if issubclass(x.category, RuntimeWarning)]
    assert msgs and f"{len(streams)} stream(s)" in msgs[0]
    # and the count is self-consistent with the scheduler's own view
    assert len(srv.sched.live()) + len(srv.sched.queue) == len(streams)


def test_drain_after_truncation_is_idempotent_and_resumable():
    """After a truncated run, drain() is a no-op on repeat (no in-flight
    entries left, no double bookkeeping) and the episode can resume to a
    clean finish with every prediction intact."""
    srv = StreamServer(CFG, t_max=16, max_streams=2, window=2,
                       phase_steps=1, refresh_every=3, pipeline_depth=2)
    streams = _episode_streams()
    for s in streams:
        srv.submit(s)
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        srv.run_until_drained(max_steps=3)
    assert not srv._inflight                 # run_until_drained flushed
    counts = {r.rid: len(r.preds) for r in streams}
    srv.drain()                              # idempotent: nothing in flight
    srv.drain()
    assert {r.rid: len(r.preds) for r in streams} == counts
    # the truncated server resumes where it stopped and finishes clean
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        done = srv.run_until_drained()
    assert not [x for x in w if issubclass(x.category, RuntimeWarning)]
    assert sorted(r.rid for r in done) == sorted(r.rid for r in streams)
    for r in done:
        assert r.done and len(r.preds) == r.n_samples
