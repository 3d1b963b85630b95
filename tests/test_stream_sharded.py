"""Slot-sharded stream serving (PR 6): parity + placement battery.

The contract under test (see runtime/stream_server.py, "Slot-sharded
serving"): ``StreamServer(devices=n)`` shards the slot axis over a 1-D
("slot",) mesh and serves episodes identical to the single-device server
under the parity rule of ``repro.runtime.parity`` - predictions exact,
float state leaves within a few ulps of each leaf's largest magnitude -
across every retirement mode (none/forget/window), pipeline depths 0/1/2,
staggered refresh cohorts, mid-service pool growth and continuous
admission/retire churn.  The tests also pin the device-local
invariant structurally: state trees stay P("slot")-sharded across steps, a
live slot never migrates between devices, and the per-device refresh work
is bounded by the cohort size.

Multi-device tests need >= 8 XLA devices.  The conftest honors
``REPRO_FORCE_DEVICES=8`` (forcing ``--xla_force_host_platform_device_
count`` before jax initializes), which the CI sharded lane sets; a plain
single-device tier-1 run still executes the battery through the slow
subprocess fallback at the bottom, and the scheduler/placement property
tests are host-only and always run.
"""
import json
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # deterministic grid variants below still run
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS,
    reason="hypothesis not installed (CI property lane installs it); the "
           "deterministic grid variants cover the same invariants",
)

from repro.core.types import DFRConfig
from repro.launch.hlo_cost import collectives_in
from repro.runtime import StreamRequest, StreamServer
from repro.runtime.parity import assert_parity
from repro.runtime.scheduler import RefreshCohorts, SlotScheduler

NDEV = jax.device_count()
needs_devices = pytest.mark.skipif(
    NDEV < 8, reason="needs 8 XLA devices (REPRO_FORCE_DEVICES=8); the "
                     "subprocess fallback covers the single-device run"
)

CFG = DFRConfig(n_in=2, n_classes=3, n_nodes=6)

RETIREMENT_MODES = (
    ("none", {"refresh_mode": "incremental"}),
    ("forget", {"refresh_mode": "incremental", "retirement": "forget",
                "forget": 0.9}),
    ("window", {"refresh_mode": "incremental", "retirement": "window",
                "retire_window": 6}),
)


def _make_stream(rid, n, t=10, seed=0):
    r = np.random.default_rng(seed)
    return StreamRequest(
        rid=rid,
        u=r.normal(size=(n, t, CFG.n_in)).astype(np.float32),
        length=r.integers(3, t + 1, n).astype(np.int32),
        label=r.integers(0, CFG.n_classes, n).astype(np.int32),
    )


def _episode_streams(seed0=0):
    """More streams than slots, ragged lengths: admission, tail windows,
    retirement and refill all fire."""
    return [_make_stream(i, n, seed=seed0 + i)
            for i, n in enumerate([7, 5, 9, 4, 6, 8, 5, 4, 7, 6, 5, 9])]


def _serve(devices, depth=0, cohorts=1, streams=None, **kw):
    srv = StreamServer(CFG, t_max=10, max_streams=8, window=2,
                      phase_steps=3, refresh_every=4,
                      refresh_cohorts=cohorts, pipeline_depth=depth,
                      devices=devices, **kw)
    for s in (streams if streams is not None else _episode_streams()):
        srv.submit(s)
    done = srv.run_until_drained()
    return {r.rid: list(r.preds) for r in done}, srv


_BASELINES = {}


def _baseline(mode, kw):
    """devices=1 depth-0 episode, computed once per retirement mode."""
    if mode not in _BASELINES:
        _BASELINES[mode] = _serve(1, **kw)
    return _BASELINES[mode]


# ---------------------------------------------------------------------------
# Bitwise parity: device counts x retirement modes x pipeline depths
# ---------------------------------------------------------------------------


@needs_devices
@pytest.mark.parametrize("mode,kw", RETIREMENT_MODES,
                         ids=[m for m, _ in RETIREMENT_MODES])
@pytest.mark.parametrize("devices", [2, 4, 8])
def test_sharded_episode_is_bitwise_single_device(devices, mode, kw):
    """The shard_map'd fused step serves the full admission/retire episode
    like devices=1: predictions match exactly, the final batched state AND
    every retirement snapshot under the parity rule (the per-device cond
    gates are exact identities when untaken)."""
    preds_1, srv_1 = _baseline(mode, kw)
    preds_n, srv_n = _serve(devices, **kw)
    assert preds_1 == preds_n
    assert_parity(srv_1.states, srv_n.states)
    if srv_1.win is not None:
        assert_parity(srv_1.win, srv_n.win)
    for a, b in zip(sorted(srv_1.completed, key=lambda r: r.rid),
                    sorted(srv_n.completed, key=lambda r: r.rid)):
        assert a.correct == b.correct and b.done
        assert_parity(a.final_state, b.final_state)
        for leaf in jax.tree_util.tree_leaves(b.final_state):
            assert np.all(np.isfinite(np.asarray(leaf, np.float64)))


@needs_devices
@pytest.mark.parametrize("mode,kw", RETIREMENT_MODES,
                         ids=[m for m, _ in RETIREMENT_MODES])
@pytest.mark.parametrize("depth", [1, 2])
def test_sharded_pipelined_is_bitwise_synchronous(depth, mode, kw):
    """Async pipelining composes with sharding: 8-device depth-1/2 episodes
    equal the single-device depth-0 schedule (the lag-D ring
    defers only bookkeeping, sharded or not)."""
    preds_1, srv_1 = _baseline(mode, kw)
    preds_d, srv_d = _serve(8, depth=depth, **kw)
    assert preds_1 == preds_d
    assert_parity(srv_1.states, srv_d.states)


@needs_devices
def test_sharded_staggered_cohorts_match():
    """Uneven refresh cohorts (C=3 over 8 slots: per-shard row lists need
    cross-shard padding to a common width) refresh the exact same slots on
    the exact same steps as the unsharded schedule."""
    for devices in (2, 8):
        preds_1, srv_1 = _serve(1, cohorts=3)
        preds_n, srv_n = _serve(devices, cohorts=3)
        assert preds_1 == preds_n
        assert_parity(srv_1.states, srv_n.states)


@needs_devices
def test_sharded_pool_growth_mid_service():
    """A longer stream submitted mid-episode grows the staged pool; the
    re-pinned sharded pool keeps serving exactly (vs devices=1 under the
    same submission schedule)."""
    def run(devices):
        srv = StreamServer(CFG, t_max=10, max_streams=4, window=2,
                          phase_steps=2, refresh_every=3, devices=devices)
        for s in _episode_streams()[:4]:
            srv.submit(s)
        for _ in range(2):
            srv.step()
        srv.submit(_make_stream(99, 13, seed=42))   # forces _grow_pool
        done = srv.run_until_drained()
        return {r.rid: list(r.preds) for r in done}, srv

    preds_1, srv_1 = run(1)
    preds_4, srv_4 = run(4)
    assert srv_4.pool.capacity == srv_1.pool.capacity > 10
    assert preds_1 == preds_4
    assert_parity(srv_1.states, srv_4.states)


@needs_devices
@pytest.mark.parametrize("devices", [2, 8])
def test_sharded_quantized_episode_is_bitwise_single_device(devices):
    """quantize='int8' (PR 7) composes with slot sharding: the sharded
    quantized episode - per-slot scale folds riding the shard-local cohort
    refresh, int8 serving logits per device block - matches the
    single-device quantized episode, quant leaves included."""
    preds_1, srv_1 = _serve(1, quantize="int8")
    preds_n, srv_n = _serve(devices, quantize="int8")
    assert preds_1 == preds_n
    assert_parity(srv_1.states, srv_n.states)   # includes states.quant


@needs_devices
def test_sharded_blocked_quantized_parity():
    """step_block (PR 7) composes with sharding and quantization: the
    8-device blocked quantized episode matches the single-device blocked
    quantized one, and both serve the unblocked quantized
    predictions exactly (the block clamp pins the schedule)."""
    preds_u, _ = _serve(1, quantize="int8")
    preds_1, srv_1 = _serve(1, quantize="int8", step_block=3)
    preds_8, srv_8 = _serve(8, quantize="int8", step_block=3)
    assert preds_u == preds_1 == preds_8
    assert_parity(srv_1.states, srv_8.states)


@needs_devices
def test_sharded_step_program_has_no_collective():
    """The device-local invariant in the compiled program itself: the
    8-device step's optimized HLO holds no cross-device collective."""
    _, srv = _serve(8)
    assert collectives_in(srv.step_program_text()) == []


# ---------------------------------------------------------------------------
# Placement: the device-local invariant, structurally
# ---------------------------------------------------------------------------


@needs_devices
def test_sharded_state_trees_stay_slot_sharded():
    """Every per-slot tree is NamedSharding-P('slot') after init AND after
    serving steps (out_specs pin it), the replicated operands replicate,
    and each device holds exactly its contiguous S/n slot block."""
    srv = StreamServer(CFG, t_max=10, max_streams=8, window=2,
                      phase_steps=2, refresh_every=3, devices=8,
                      refresh_mode="incremental", retirement="window",
                      retire_window=4)
    for s in _episode_streams()[:6]:
        srv.submit(s)
    for _ in range(3):
        srv.step()
    srv.drain()
    mesh = srv.mesh
    assert mesh.axis_names == ("slot",) and mesh.size == 8
    slot_sh = NamedSharding(mesh, P("slot"))
    for tree in (srv.states, srv.win, srv.pool):
        for leaf in jax.tree_util.tree_leaves(tree):
            assert leaf.sharding.is_equivalent_to(slot_sh, leaf.ndim), leaf
    assert srv.mask.sharding.is_equivalent_to(
        NamedSharding(mesh, P()), srv.mask.ndim)
    # contiguous ownership: shard d of the (S,) step counter is slot d
    shards = sorted(srv.states.step.addressable_shards,
                    key=lambda sh: sh.device.id)
    assert [sh.index for sh in shards] == [
        (slice(d, d + 1),) for d in range(8)
    ]


def test_sharded_validation():
    """Misconfigurations fail fast: host staging, indivisible S, devices<1
    (all raised before any mesh is built)."""
    with pytest.raises(ValueError, match="staging='device'"):
        StreamServer(CFG, t_max=10, devices=2, staging="host")
    with pytest.raises(ValueError, match="divisible"):
        StreamServer(CFG, t_max=10, max_streams=6, devices=4)
    with pytest.raises(ValueError, match="devices"):
        StreamServer(CFG, t_max=10, devices=0)


# ---------------------------------------------------------------------------
# Host-only properties: placement never migrates, refresh work is bounded
# ---------------------------------------------------------------------------


def _check_no_migration(rng, n_slots, n_shards, n_ops):
    """Random admit/retire schedule: a request's slot index - hence its
    owning device, the fixed map slot // (S/n) - never changes while the
    request is live."""
    s_loc = n_slots // n_shards
    sched = SlotScheduler(n_slots)
    placed = {}          # rid -> (slot, device) at admission
    next_rid = 0
    for _ in range(n_ops):
        op = rng.choice(["submit", "admit", "retire"])
        if op == "submit":
            sched.submit(next_rid)
            next_rid += 1
        elif op == "admit":
            sched.admit(lambda i, rid: placed.setdefault(
                rid, (i, i // s_loc)))
        else:
            live = sched.live()
            if live:
                i, rid = live[int(rng.integers(len(live)))]
                sched.retire(i)
                del placed[rid]
        for i, rid in sched.live():
            slot0, dev0 = placed[rid]
            assert i == slot0 and i // s_loc == dev0


def _check_cohort_schedule(n_slots, refresh_every, n_cohorts, n_shards):
    """The shard-local refresh schedule is the unsharded schedule, re-based:
    same due steps, local rows in range and distinct per shard, the union
    of ok'd global ids is exactly the due cohort, and per-device refresh
    work is bounded by the local cohort size ceil(S/n / C)."""
    s_loc = n_slots // n_shards
    coh = RefreshCohorts(n_slots, refresh_every, n_cohorts)
    c_eff = coh.n_cohorts
    for step in range(refresh_every):
        due_g, _, _ = coh.due_rows_fixed(step)
        due_s, rows, ok = coh.due_rows_fixed_sharded(step, n_shards)
        assert due_s == due_g
        assert rows.shape == ok.shape and rows.shape[0] % n_shards == 0
        r_loc = rows.shape[0] // n_shards
        global_ok = set()
        for d in range(n_shards):
            blk = rows[d * r_loc:(d + 1) * r_loc]
            okb = ok[d * r_loc:(d + 1) * r_loc]
            assert ((blk >= 0) & (blk < s_loc)).all()
            assert len(set(blk.tolist())) == r_loc   # scatter-safe
            assert int(okb.sum()) <= -(-s_loc // c_eff)
            global_ok |= {d * s_loc + int(j) for j, o in zip(blk, okb) if o}
        expect = coh.due_slots(step)
        assert global_ok == set(expect if due_g else [])


if HAVE_HYPOTHESIS:

    @settings(max_examples=60, deadline=None)
    @given(st.data())
    def test_property_live_slot_never_changes_device(data):
        n_slots = data.draw(st.sampled_from([4, 8, 16]), label="n_slots")
        n_shards = data.draw(
            st.sampled_from([d for d in (1, 2, 4, 8) if n_slots % d == 0]),
            label="n_shards")
        seed = data.draw(st.integers(0, 2**31 - 1), label="seed")
        n_ops = data.draw(st.integers(4, 30), label="ops")
        _check_no_migration(
            np.random.default_rng(seed), n_slots, n_shards, n_ops)

    @settings(max_examples=60, deadline=None)
    @given(
        n_slots=st.sampled_from([4, 8, 16, 24]),
        refresh_every=st.integers(1, 12),
        n_cohorts=st.integers(1, 6),
        n_shards=st.sampled_from([1, 2, 4, 8]),
    )
    def test_property_sharded_cohort_schedule(n_slots, refresh_every,
                                              n_cohorts, n_shards):
        if n_slots % n_shards:
            n_shards = 1
        _check_cohort_schedule(n_slots, refresh_every, n_cohorts, n_shards)


def test_grid_live_slot_never_changes_device():
    """Deterministic variant of the migration property (runs with or
    without hypothesis): 24 random schedules across shard widths."""
    for n_slots, n_shards in ((4, 1), (4, 2), (8, 4), (8, 8), (16, 4)):
        for seed in range(5):
            _check_no_migration(
                np.random.default_rng(1000 * n_slots + seed),
                n_slots, n_shards, n_ops=25)


def test_grid_sharded_cohort_schedule():
    """Deterministic variant of the schedule property: the full small grid
    of slots x period x cohorts x shards."""
    for n_slots in (4, 8, 16, 24):
        for refresh_every in (1, 3, 5, 8):
            for n_cohorts in (1, 2, 3, 5):
                for n_shards in (1, 2, 4, 8):
                    if n_slots % n_shards:
                        continue
                    _check_cohort_schedule(
                        n_slots, refresh_every, n_cohorts, n_shards)


def test_sharded_cohort_schedule_rejects_indivisible():
    with pytest.raises(ValueError, match="divisible"):
        RefreshCohorts(6, 4, 2).due_rows_fixed_sharded(0, 4)


# ---------------------------------------------------------------------------
# _sharded_fixed direct unit battery (the padding construction itself)
# ---------------------------------------------------------------------------


def _sharded_fixed_corners():
    """(n_slots, refresh_every, n_cohorts, n_shards) corner grid: single
    cohort (r_loc == s_loc, empty pad pool), one-slot shards, cohorts >
    period (clamped), misaligned cohort stride vs shard blocks, max
    padding (one giant cohort among many shards)."""
    return [
        (4, 3, 1, 1), (4, 3, 1, 2), (4, 3, 1, 4),      # r_loc == s_loc
        (8, 5, 2, 2), (8, 5, 2, 8),                     # s_loc == 1
        (8, 2, 5, 2),                                   # cohorts clamped
        (6, 4, 2, 2), (6, 6, 4, 3), (12, 5, 5, 4),     # misaligned strides
        (16, 8, 8, 2), (24, 12, 5, 8),
    ]


def test_sharded_fixed_blocks_are_duplicate_free_and_in_range():
    """Every (cohort, shard) block holds r_loc DISTINCT local indices in
    [0, s_loc) - the property that makes the traced refresh scatter safe
    (a duplicate index would make the padded no-op write race the real
    refresh write) - and the ok'd ones are exactly the cohort's local
    members."""
    for n_slots, refresh_every, n_cohorts, n_shards in _sharded_fixed_corners():
        coh = RefreshCohorts(n_slots, refresh_every, n_cohorts)
        s_loc = n_slots // n_shards
        r_loc, fixed = coh._sharded_fixed(n_shards)
        assert set(fixed) == set(coh.offsets)
        for c, phase in enumerate(coh.offsets):
            rows, ok = fixed[phase]
            assert rows.shape == ok.shape == (n_shards * r_loc,)
            for d in range(n_shards):
                blk = rows[d * r_loc:(d + 1) * r_loc].tolist()
                okb = ok[d * r_loc:(d + 1) * r_loc].tolist()
                assert all(0 <= j < s_loc for j in blk)
                assert len(set(blk)) == r_loc, (
                    f"duplicate local rows in shard {d} of cohort {c}: {blk}")
                want = {i - d * s_loc for i in range(n_slots)
                        if coh.cohort_of_slot[i] == c
                        and d * s_loc <= i < (d + 1) * s_loc}
                assert {j for j, o in zip(blk, okb) if o} == want


def test_sharded_fixed_pad_pool_never_exhausts():
    """r_loc (the common padded width) never exceeds s_loc, so the pad
    pool of non-member local indices always covers the demand - the
    ``pad_pool.pop(0) if pad_pool else 0`` fallback (which would introduce
    a duplicate row) is unreachable.  Checked structurally: padding demand
    r_loc - len(members) never exceeds the pool s_loc - len(members)."""
    for n_slots, refresh_every, n_cohorts, n_shards in _sharded_fixed_corners():
        coh = RefreshCohorts(n_slots, refresh_every, n_cohorts)
        s_loc = n_slots // n_shards
        r_loc, _ = coh._sharded_fixed(n_shards)
        assert 1 <= r_loc <= s_loc
        for c in range(coh.n_cohorts):
            for d in range(n_shards):
                m = sum(1 for i in range(n_slots)
                        if coh.cohort_of_slot[i] == c
                        and d * s_loc <= i < (d + 1) * s_loc)
                assert r_loc - m <= s_loc - m


def test_sharded_fixed_single_cohort_is_full_permutation():
    """n_cohorts=1 is the r_loc == s_loc corner: the one cohort owns every
    slot, the pad pool is empty AND no padding is needed - each shard
    block must be a full permutation of range(s_loc), all ok."""
    for n_slots, n_shards in ((4, 1), (4, 2), (8, 4), (8, 8), (24, 3)):
        coh = RefreshCohorts(n_slots, 5, 1)
        s_loc = n_slots // n_shards
        r_loc, fixed = coh._sharded_fixed(n_shards)
        assert r_loc == s_loc
        (rows, ok), = fixed.values()
        assert ok.all()
        for d in range(n_shards):
            assert sorted(rows[d * s_loc:(d + 1) * s_loc].tolist()) \
                == list(range(s_loc))


def test_sharded_fixed_misaligned_stride_flags():
    """n_slots=6, n_shards=2, n_cohorts=2: cohort 0 = {0, 2, 4} straddles
    both 3-slot shard blocks unevenly (2 members in shard 0, 1 in shard
    1), so shard 1's block needs one ok=False pad distinct from its
    member."""
    coh = RefreshCohorts(6, 4, 2)
    r_loc, fixed = coh._sharded_fixed(2)
    assert r_loc == 2
    rows, ok = fixed[coh.offsets[0]]          # cohort 0
    s0, o0 = rows[:2].tolist(), ok[:2].tolist()
    s1, o1 = rows[2:].tolist(), ok[2:].tolist()
    assert sorted(j for j, o in zip(s0, o0) if o) == [0, 2]
    assert sorted(j for j, o in zip(s1, o1) if o) == [1]   # global slot 4
    assert len(set(s1)) == 2                  # the pad is distinct


# ---------------------------------------------------------------------------
# Single-device fallback: run the battery under a forced-8-device subprocess
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.skipif(NDEV >= 8, reason="battery already ran in-process")
def test_forced_lane_subprocess():
    """Plain tier-1 runs (one device) still execute the full sharded parity
    battery: re-run this file's device-gated tests in a subprocess with
    REPRO_FORCE_DEVICES=8 (the conftest forces the XLA flag pre-init)."""
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/test_stream_sharded.py",
         "-q", "-k", "sharded_", "-p", "no:cacheprovider"],
        capture_output=True, text=True, timeout=1800,
        env={"PYTHONPATH": "src", "PATH": os.environ.get("PATH", ""),
             "JAX_PLATFORMS": "cpu", "HOME": os.environ.get("HOME", "/root"),
             "REPRO_FORCE_DEVICES": "8"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, (out.stdout[-2000:] + out.stderr[-2000:])
    assert "passed" in out.stdout
