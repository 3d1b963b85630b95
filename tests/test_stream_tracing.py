"""Host spans and device scopes of the stream server (``runtime.tracing``).

A short episode is recorded with ``jax.profiler`` and its ``.xplane.pb``
read back with ``ProfileData``: the spans' counts, nesting and stats are
held against what the server did, and the refresh eligibility the host
reports is held against the states the device produced.  Episodes run on
the CPU at tiny sizes, with more streams than slots so that admissions
and retirements are staggered over the steps.
"""
import glob
from collections import Counter, defaultdict

import jax
import numpy as np
import pytest

from repro.core.types import DFRConfig
from repro.runtime import StreamRequest, StreamServer, tracing
from repro.runtime import stream_server as ss

CFG = DFRConfig(n_in=3, n_classes=4, n_nodes=4, nonlinearity="tanh")
T_MAX, SLOTS, WINDOW, PHASE = 7, 4, 2, 2
LENGTHS = (5, 9, 3, 12, 7, 6, 4)        # samples per stream
KINDS = {
    # device staging at pipeline_depth=0: what the fleet cell runs
    "sync": dict(),
    # device staging, predictions drained one step late
    "pipelined": dict(pipeline_depth=1),
    # the host-staged batch build and its separate refresh dispatch
    "host": dict(staging="host"),
    # int8 serving: the scale fold rides the refresh branch
    "int8": dict(quantize="int8"),
    # the in-step drift detector over live factors
    "adaptive": dict(retirement="adaptive", refresh_mode="incremental"),
}
SCOPES = ("stream.admit_reset", "stream.serve", "stream.kernel",
          "stream.live_select", "stream.factor_fold", "stream.gather",
          "stream.refresh")


def _requests(seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for rid, n in enumerate(LENGTHS):
        length = rng.integers(1, T_MAX + 1, n).astype(np.int32)
        u = rng.normal(size=(n, T_MAX, CFG.n_in)).astype(np.float32)
        for k in range(n):
            u[k, length[k]:] = 0.0
        label = rng.integers(0, CFG.n_classes, n).astype(np.int32)
        out.append(StreamRequest(rid=100 + rid, u=u, length=length,
                                 label=label))
    return out


def _server(**kw):
    return StreamServer(CFG, t_max=T_MAX, max_streams=SLOTS, window=WINDOW,
                        phase_steps=PHASE, refresh_every=2,
                        pool_capacity=max(LENGTHS), **kw)


def _episode(server, reqs, after_step=None):
    for r in reqs:
        server.submit(r)
    while server.sched.active():
        server.step()
        if after_step is not None:
            after_step(server)
    server.drain()
    return {r.rid: r for r in server.completed}


def _spans(log_dir):
    """[(name, start_ns, end_ns, stats)] of the stream.* host events."""
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    assert len(path) == 1, path
    data = jax.profiler.ProfileData.from_file(path[0])
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("stream."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: s[1])


_RECORDED = {}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """kind -> (spans, server, finished requests, per-step snapshots).
    Each snapshot is taken after a step returns: the live slots and the
    slots' step and sample counters as the device left them."""
    def get(kind):
        if kind not in _RECORDED:
            server = _server(**KINDS[kind])
            snaps = []

            def snap(srv):
                snaps.append((
                    {i for i, _ in srv.sched.live()},
                    np.asarray(srv.states.step),
                    np.asarray(srv.states.ridge.count),
                ))

            log_dir = tmp_path_factory.mktemp(kind)
            with jax.profiler.trace(str(log_dir)):
                assert tracing.recording()
                done = _episode(server, _requests(), snap)
            _RECORDED[kind] = (_spans(log_dir), server, done, snaps)
        return _RECORDED[kind]
    return get


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(outer, inner):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _per_step(spans, name):
    """The ``name`` spans inside each stream.step span, in step order."""
    steps = _named(spans, "stream.step")
    return [[s for s in _named(spans, name) if _inside(st, s)]
            for st in steps]


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_span_counts(recorded, kind):
    spans, server, done, snaps = recorded(kind)
    assert len(done) == len(LENGTHS)
    n = Counter(s[0] for s in spans)
    assert n["stream.step"] == len(snaps) > len(LENGTHS) // SLOTS
    assert n["stream.admit"] == len(LENGTHS)
    assert n["stream.retire"] == len(LENGTHS)
    # the payload is staged once per submit() on the device-staged paths
    staged = KINDS[kind].get("staging", "device") == "device"
    assert n["stream.stage"] == (len(LENGTHS) if staged else 0)
    # each step enqueues once; every step's predictions are drained once
    assert n["stream.enqueue"] == n["stream.step"]
    assert n["stream.drain"] == n["stream.step"]


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_admit_enqueue_retire_lie_inside_their_step(recorded, kind):
    spans = recorded(kind)[0]
    steps = _named(spans, "stream.step")
    for name in ("stream.admit", "stream.enqueue", "stream.snapshot",
                 "stream.retire"):
        for s in _named(spans, name):
            assert sum(_inside(st, s) for st in steps) == 1, (name, s)
    # stage spans come from submit(), outside every step
    for s in _named(spans, "stream.stage"):
        assert not any(_inside(st, s) for st in steps)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_rid_links_a_streams_spans(recorded, kind):
    spans, server, done, _ = recorded(kind)
    by_rid = defaultdict(list)
    for name, _s, _e, stats in spans:
        if "rid" in stats:
            by_rid[stats["rid"]].append((name, stats))
    assert set(by_rid) == set(done)
    staged = KINDS[kind].get("staging", "device") == "device"
    for rid, got in by_rid.items():
        names = sorted(name for name, _ in got)
        want = ["stream.admit", "stream.retire"]
        assert names == (sorted(want + ["stream.stage"]) if staged else want)
        slot = {st["slot"] for name, st in got if "slot" in st}
        assert len(slot) == 1 and 0 <= slot.pop() < SLOTS
        retire = next(st for name, st in got if name == "stream.retire")
        assert retire["samples"] == done[rid].n_samples
        if staged:
            stage = next(st for name, st in got if name == "stream.stage")
            cap = server.pool.capacity
            assert stage["bytes"] == cap * (T_MAX * CFG.n_in * 4 + 4 + 4)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_step_counters(recorded, kind):
    spans, server, done, snaps = recorded(kind)
    stats = [s[3] for s in _named(spans, "stream.step")]
    admits = _per_step(spans, "stream.admit")
    retires = _per_step(spans, "stream.retire")
    snapshots = _per_step(spans, "stream.snapshot")
    for k, st in enumerate(stats):
        assert st["admitted"] == len(admits[k])
        assert st["retired"] == len(retires[k])
        # one device: a step that retires dispatches one snapshot program
        assert st["snapshot_programs"] == len(snapshots[k]) == (
            1 if retires[k] else 0)
        assert st["live"] == len(snaps[k][0]) + st["retired"]
        assert 0 < st["real_timesteps"] <= st["slot_timesteps"]
        assert st["slot_timesteps"] == SLOTS * WINDOW * T_MAX
    # the global step numbers run on, one a step
    assert [st["step"] for st in stats] == list(range(1, len(stats) + 1))
    # every sample is served once: the real timesteps are its lengths
    assert sum(st["real_timesteps"] for st in stats) == sum(
        int(r.length.sum()) for r in done.values())
    # drains name the step they read, each step once
    assert sorted(s[3]["step"] for s in _named(spans, "stream.drain")) == [
        st["step"] for st in stats]


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_snapshot_span_batches_the_steps_retirements(recorded, kind):
    """Once per step that retires: ``rows`` is that step's retirements,
    ``bucket`` the rows read (padding included, a power of two up to the
    slots).  The snapshot precedes the step's per-stream ``stream.retire``
    bookkeeping."""
    spans = recorded(kind)[0]
    retires = _per_step(spans, "stream.retire")
    snapshots = _per_step(spans, "stream.snapshot")
    n_steps = 0
    for rets, snaps in zip(retires, snapshots):
        if not rets:
            assert snaps == []
            continue
        n_steps += 1
        (snap,) = snaps
        rows, bucket = snap[3]["rows"], snap[3]["bucket"]
        assert rows == len(rets)
        assert bucket == ss._snapshot_bucket(rows, SLOTS) >= rows
        assert all(snap[2] <= r[1] for r in rets)
    assert n_steps == len(_named(spans, "stream.snapshot")) > 0
    # the episode retires one stream alone and several together
    rows = [s[3]["rows"] for s in _named(spans, "stream.snapshot")]
    assert min(rows) == 1 and max(rows) > 1


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_refresh_eligibility_matches_the_device(recorded, kind):
    """On each refresh step, the eligible rows the host counts are those
    the program refreshes: live in the step, at least ``phase_steps``
    windows served, samples accumulated - read back from the states."""
    spans, server, _done, snaps = recorded(kind)
    stats = [s[3] for s in _named(spans, "stream.step")]
    retires = _per_step(spans, "stream.retire")
    enqueues = [s[3] for s in _named(spans, "stream.enqueue")]
    seen = Counter()
    for k, st in enumerate(stats):
        due = server.cohorts.due_slots(st["step"])
        assert bool(enqueues[k]["refresh"]) == (due is not None)
        if due is None:
            assert st["refresh_rows"] == st["refresh_eligible"] == 0
            continue
        assert st["refresh_rows"] == SLOTS
        live_after, step_ctr, count = snaps[k]
        live = live_after | {r[3]["slot"] for r in retires[k]}
        want = sum(1 for i in due if i in live and step_ctr[i] >= PHASE
                   and count[i] > 0)
        assert st["refresh_eligible"] == want
        seen["none" if want == 0 else
              "all" if want == len(live) else "some"] += 1
    # the episode refreshes with no row eligible and with some rows not
    assert seen["none"] and (seen["some"] or seen["all"])


def test_step_program_carries_every_scope():
    server = _server(fused_infer=True, refresh_mode="incremental")
    text = server.step_program_text()
    for scope in SCOPES:
        assert f"{scope}/" in text, scope


def test_nothing_recorded_or_computed_when_off(recorded, monkeypatch):
    """Unrecorded, an episode serves what the recorded one served, and the
    step's counters are never computed."""
    traced = recorded("pipelined")[2]

    def boom(*a, **k):
        raise AssertionError("span stats computed with no trace recording")

    monkeypatch.setattr(ss.StreamServer, "_step_stats", boom)
    assert not tracing.recording()
    plain = _episode(_server(**KINDS["pipelined"]), _requests())
    assert not tracing.recording()
    assert set(plain) == set(traced)
    for rid, r in plain.items():
        assert r.preds == traced[rid].preds
        for a, b in zip(jax.tree_util.tree_leaves(r.final_state),
                        jax.tree_util.tree_leaves(traced[rid].final_state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
