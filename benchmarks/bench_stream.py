"""Stream-serving throughput: batched continuous-batching slots vs
per-stream stepping.

Acceptance target (ISSUE 2): the stream server's fixed-shape batched-slot
jitted step must deliver >= 3x the served-samples/sec of stepping the same
streams one-by-one through ``OnlineDFR`` (infer-before-update + train +
the same periodic ridge-refresh protocol per stream).  Both paths are
jit-warmed before timing, so the comparison is steady-state dispatch +
compute, not compilation.

Also reports p50/p99 per-window step latency for both paths: the batched
step serves ``S`` windows per dispatch, the serial path one - the latency
columns show what continuous batching costs the individual stream.

Second table (ISSUE 3, ``refresh-mode``): the periodic Ridge refresh is the
batched-serving bottleneck at Nx>=16 (the global (s, s) Cholesky round
grows as s^3).  The table compares, at identical protocols:

  * ``recompute``  - global batched re-factorization (the PR-2 path),
  * ``rec+stag``   - recompute with the round staggered over
                     ``refresh_every`` round-robin slot cohorts
                     (``scheduler.RefreshCohorts``: same per-slot cadence,
                     1/C of the slots per step) - the staggering ablation,
  * ``incremental``- live per-slot factor maintained by O(s^2) rank-1
                     cholupdates folded into the fused step; refresh = two
                     batched blocked triangular solves, no factorization,
  * ``inc+stag``   - both.

Honest columns: at window=1 (the paper's sample-by-sample serving regime)
the incremental path wins served-samples/sec AND p99 at Nx=16 (S=16 and
S=32; staggering adds further p99 headroom at S=32 where the refresh bill
is largest).  The window=8 row is the mass-arrival regime: many samples
land per step, the sequential rank-1 rotations cost W * O(s^2) against a
once-per-round LAPACK O(s^3), and recompute wins throughput again.  At
Nx=8 (s = 73) the factorization is cheap enough that all policies tie on
throughput and staggering only adds dispatch overhead - reported as-is.

Third table (ISSUE 5, ``pipeline``): the device-resident serving pipeline
vs the PR-4 synchronous host-staged server, at identical protocols.  The
baseline column ``sync_host`` is literally the PR-4 plumbing
(``staging='host', donate=False, pipeline_depth=0``: per-step host batch
build + upload, un-donated dispatch, separate refresh dispatch, blocking
prediction read every step).  The pipeline columns stage requests once in
the device pool, donate the state buffers, fold the cohort refresh into
the single fused dispatch, and run the prediction ring at depth 0/1/2.
Latency is split honestly: ``dispatch`` (host enqueue work per step) vs
``drain`` (the blocking device read) - a deep pipeline defers the sync but
the drain column still shows what it costs.

Read the columns carefully: ``sync_host`` shares PR 5's *program*
optimizations (the scan-based rotation sweep, the phase-gated backward),
so the table isolates the serving-pipeline delta alone - staging +
donation + folded refresh - not the full PR-5 win.  Against the PR-4
server as committed (fori-loop factor fold, unconditional backward), the
depth-2 pipeline measured ~24x at Nx=16/S=16/W=1 and ~1.6x at Nx=8 on the
same 2-core host (see ROADMAP "Landed (PR 5)").  Honest columns within
the table: retirement='none' ties (~0.95-1.0x at Nx=16) - the scan-based
fold left nothing for donation to save there; forget/window keep
~1.2-1.4x (their per-row fori-loop folds still copy un-donated); Nx=8 is
inside the noise band either way (the shared host swings ~30-40% between
runs); depth>0 is ~neutral on XLA:CPU, which executes on the dispatch
thread (the lag-D ring is built for async backends - TPU - where dispatch
returns before compute finishes).

Fourth table (ISSUE 4, ``drift``): piecewise-stationary NARMA streams
(``repro.data.make_narma10_drift``: the input->output dynamics switch at a
known sample) served under the three retirement policies.  Columns are the
online infer-before-update accuracy just *before* the drift point, right
*at* it, and over the stream tail (*post*, after the policies had time to
re-track), plus served-samples/sec - the cost of retirement.  The honest
story: every policy craters AT the switch (no oracle knows the plant
changed), the growing-memory baseline never recovers (its (A, B) stay
anchored to a regime that no longer exists), and the forget/window paths
climb back to near pre-drift accuracy at a modest throughput cost (the
window path pays the extra per-sample eviction downdate).

Fifth table (ISSUE 6, ``--sharded``): served-samples/sec vs slot-mesh
device count (1/2/4/8) at Nx in {8, 16} x S in {64, 256}, window=1.  The
sharded episodes serve the single-device episodes (``runtime.parity``), so
the columns measure pure serving-harness scaling.  Tracked in BENCH_stream_sharded.json
(written by ``benchmarks/run.py --only stream_sharded``).  Columns where
the mesh has more devices than the host has physical cores
(``os.cpu_count()``) are flagged ``dN_oversubscribed`` and report an
``dN_overhead_ratio`` instead of a ``dN_speedup``: forced host-device
splits time-slice the shared cores, so those numbers measure sharding
*overhead*, never speedup - PR 6 recorded them under the speedup name,
which made the mistake easy to repeat.

Sixth table (ISSUE 7, ``--quant``): the int8 quantized serving fast path
plus multi-sample step blocking vs the fp32 baseline, at identical
protocols (PR-5 paired discipline: same streams, round-robin episodes,
best-of-reps per policy).  Columns: served-samples/sec for
fp32 / int8 / fp32+block / int8+block, int8-vs-fp32 argmax agreement on
the same episode, per-slot serving-readout bytes (int8 codes + scales vs
fp32 weights - the deterministic >= 3x memory acceptance axis), and
optimized-HLO per-step FLOPs/bytes for both serving programs (from
``launch/hlo_cost``, host-noise independent).  A second row kind
(``quant-drift``) serves the NARMA10 piecewise-drift fixture under both
paths and reports the pre/at/post accuracy band plus deltas - the honest
accuracy cost of int8.  Tracked in BENCH_stream_quant.json (written by
``benchmarks/run.py --only stream_quant``).

    PYTHONPATH=src python benchmarks/bench_stream.py [--smoke|--full]
    PYTHONPATH=src python benchmarks/bench_stream.py --sharded [--json]
    PYTHONPATH=src python benchmarks/bench_stream.py --quant [--json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import OnlineDFR
from repro.core.types import DFRConfig
from repro.data import drift_segment_bounds, make_drift_label_streams
from repro.runtime import StreamRequest, StreamServer


def _make_streams(n_streams: int, n_samples: int, t_len: int, n_in: int,
                  n_classes: int, seed: int = 0) -> List[StreamRequest]:
    rng = np.random.default_rng(seed)
    out = []
    for rid in range(n_streams):
        out.append(StreamRequest(
            rid=rid,
            u=rng.normal(size=(n_samples, t_len, n_in)).astype(np.float32),
            length=rng.integers(max(2, t_len // 2), t_len + 1,
                                n_samples).astype(np.int32),
            label=rng.integers(0, n_classes, n_samples).astype(np.int32),
        ))
    return out


def _serve_batched(cfg, streams, t_len, window, phase_steps, refresh_every,
                   **server_kw):
    srv = StreamServer(
        cfg, t_max=t_len, max_streams=len(streams), window=window,
        phase_steps=phase_steps, refresh_every=refresh_every, **server_kw,
    )
    # time from FIRST SUBMIT: device staging pays its one-time pad+upload
    # per stream at submit, so starting the clock after submission would
    # credit the pipeline columns with work the host-staged baseline pays
    # inside its serving loop
    t0 = time.perf_counter()
    for s in streams:
        srv.submit(s)
    srv.run_until_drained()
    elapsed = time.perf_counter() - t0
    return elapsed, srv.latency_percentiles_ms()


def _serve_serial(system, streams, window, phase_steps, refresh_every):
    """The same protocol, one stream at a time through OnlineDFR."""
    lr_on, lr_off = jnp.float32(0.2), jnp.float32(0.0)
    beta = jnp.float32(1e-2)
    step_times = []
    t0 = time.perf_counter()
    for req in streams:
        state = system.init()
        served = 0
        steps = 0
        while served < req.n_samples:
            n = min(window, req.n_samples - served)
            u = jnp.asarray(req.u[served:served + n])
            ln = jnp.asarray(req.length[served:served + n])
            lab = jnp.asarray(req.label[served:served + n])
            ts = time.perf_counter()
            preds = system.infer(state, u, ln)          # infer-before-update
            lr = lr_on if steps < phase_steps else lr_off
            state, _ = system.step(state, u, ln, lab, lr, lr)
            if steps + 1 == phase_steps:
                state = system.reset_statistics(state)
            steps += 1
            if steps % refresh_every == 0 and steps > phase_steps:
                state = system.refresh_output(state, beta)
            jax.block_until_ready(preds)
            step_times.append(time.perf_counter() - ts)
            served += n
    elapsed = time.perf_counter() - t0
    t = np.asarray(step_times) * 1e3
    return elapsed, {"p50_ms": float(np.percentile(t, 50)),
                     "p99_ms": float(np.percentile(t, 99))}


def _bench_case(n_streams: int, n_samples: int, t_len: int, n_nodes: int,
                window: int = 4, reps: int = 2) -> Dict:
    cfg = DFRConfig(n_in=3, n_classes=4, n_nodes=n_nodes)
    phase_steps, refresh_every = 4, 5
    total_samples = n_streams * n_samples

    # NOTE: serial stepping pads the tail window to < `window` samples only
    # on the final step per stream; the batched server zero-weights the tail
    # inside the same fixed shape.  Use n_samples % window == 0 so both
    # paths serve identical work.
    assert n_samples % window == 0

    # one OnlineDFR reused across reps so the serial path's jitted
    # step/infer/refresh compile once (self is a static argument)
    system = OnlineDFR(cfg)

    def run_batched():
        streams = _make_streams(n_streams, n_samples, t_len, 3, 4)
        return _serve_batched(cfg, streams, t_len, window, phase_steps,
                              refresh_every)

    def run_serial():
        streams = _make_streams(n_streams, n_samples, t_len, 3, 4)
        return _serve_serial(system, streams, window, phase_steps,
                             refresh_every)

    run_batched()   # warm both jitted programs (compile excluded)
    run_serial()
    best_b, best_s = None, None
    for _ in range(reps):
        tb, lat_b = run_batched()
        if best_b is None or tb < best_b[0]:
            best_b = (tb, lat_b)
        ts, lat_s = run_serial()
        if best_s is None or ts < best_s[0]:
            best_s = (ts, lat_s)
    tb, lat_b = best_b
    ts, lat_s = best_s

    return {
        "table": "stream-serving",
        "cell": f"S{n_streams}/N{n_samples}/Nx{n_nodes}",
        "bp_time_s": round(tb, 5),
        "serial_time_s": round(ts, 5),
        "batched_samples_per_s": round(total_samples / tb, 1),
        "serial_samples_per_s": round(total_samples / ts, 1),
        "batched_p50_ms": round(lat_b["p50_ms"], 3),
        "batched_p99_ms": round(lat_b["p99_ms"], 3),
        "serial_p50_ms": round(lat_s["p50_ms"], 3),
        "serial_p99_ms": round(lat_s["p99_ms"], 3),
        "speedup": round(ts / tb, 2),
    }


REFRESH_MODES = (
    ("recompute", {}),
    ("rec+stag", {"refresh_cohorts": 0}),
    ("incremental", {"refresh_mode": "incremental"}),
    ("inc+stag", {"refresh_mode": "incremental", "refresh_cohorts": 0}),
)


def _bench_refresh_case(n_streams: int, n_samples: int, t_len: int,
                        n_nodes: int, window: int, reps: int = 2,
                        refresh_every: int = 5) -> Dict:
    """One refresh-mode comparison cell (same streams, same protocol)."""
    cfg = DFRConfig(n_in=3, n_classes=4, n_nodes=n_nodes)
    phase_steps = 4
    assert n_samples % window == 0
    total_samples = n_streams * n_samples

    row: Dict = {
        "table": "refresh-mode",
        "cell": f"S{n_streams}/Nx{n_nodes}/W{window}",
    }
    base_time = None
    base_p99 = None
    for name, kw in REFRESH_MODES:
        kw = dict(kw)
        if kw.get("refresh_cohorts") == 0:  # stagger over the whole period
            kw["refresh_cohorts"] = refresh_every

        def run_once():
            streams = _make_streams(n_streams, n_samples, t_len, 3, 4)
            return _serve_batched(cfg, streams, t_len, window, phase_steps,
                                  refresh_every, **kw)

        run_once()  # warm the jitted step/refresh programs
        best = None
        for _ in range(reps):
            t, lat = run_once()
            if best is None or t < best[0]:
                best = (t, lat)
        t, lat = best
        row[f"{name}_samples_per_s"] = round(total_samples / t, 1)
        row[f"{name}_p50_ms"] = round(lat["p50_ms"], 3)
        row[f"{name}_p99_ms"] = round(lat["p99_ms"], 3)
        if name == "recompute":
            base_time, base_p99 = t, lat["p99_ms"]
        else:
            row[f"{name}_speedup"] = round(base_time / t, 2)
            row[f"{name}_p99_ratio"] = round(base_p99 / max(lat["p99_ms"], 1e-9), 2)
    return row


# ---------------------------------------------------------------------------
# Pipeline table: device-resident serving vs the PR-4 synchronous server
# ---------------------------------------------------------------------------

PIPELINE_POLICIES: Tuple[Tuple[str, Dict], ...] = (
    # the PR-4 server, bit-for-bit: host staging, no donation, synchronous
    ("sync_host", {"staging": "host", "donate": False, "pipeline_depth": 0}),
    ("d0", {"pipeline_depth": 0}),          # pool + donation, synchronous
    ("d1", {"pipeline_depth": 1}),          # + lag-1 prediction ring
    ("d2", {"pipeline_depth": 2}),          # + lag-2 prediction ring
)

PIPELINE_RETIREMENTS: Dict[str, Dict] = {
    "none": {},
    "forget": {"retirement": "forget", "forget": 0.95},
    "window": {"retirement": "window"},      # capacity filled in per case
}


def _bench_pipeline_case(n_streams: int, n_samples: int, t_len: int,
                         n_nodes: int, window: int, retirement: str,
                         reps: int = 5, refresh_every: int = 5) -> Dict:
    """One pipeline comparison cell (same streams, same protocol; all
    policies on refresh_mode='incremental' so the only difference is the
    serving pipeline itself).

    Policies are timed ROUND-ROBIN (one episode each per rep, best-of-reps
    per policy) rather than back to back: on a small shared host, noise
    windows longer than one policy's episode block would otherwise land on
    one column and masquerade as a speedup/slowdown of that policy.
    """
    cfg = DFRConfig(n_in=3, n_classes=4, n_nodes=n_nodes)
    phase_steps = 4
    assert n_samples % window == 0
    total_samples = n_streams * n_samples
    ret_kw = dict(PIPELINE_RETIREMENTS[retirement])
    if ret_kw.get("retirement") == "window":
        ret_kw["retire_window"] = max(window, n_samples // 2)

    def run_once(kw):
        streams = _make_streams(n_streams, n_samples, t_len, 3, 4)
        return _serve_batched(cfg, streams, t_len, window, phase_steps,
                              refresh_every, refresh_mode="incremental",
                              **ret_kw, **kw)

    for _, kw in PIPELINE_POLICIES:     # warm every jitted program first
        run_once(kw)
    best: Dict[str, Tuple] = {}
    for _ in range(reps):
        for name, kw in PIPELINE_POLICIES:
            t, lat = run_once(kw)
            if name not in best or t < best[name][0]:
                best[name] = (t, lat)

    row: Dict = {
        "table": "pipeline",
        "cell": f"S{n_streams}/Nx{n_nodes}/W{window}/{retirement}",
    }
    base_time = best["sync_host"][0]
    for name, _ in PIPELINE_POLICIES:
        t, lat = best[name]
        row[f"{name}_samples_per_s"] = round(total_samples / t, 1)
        if name == "sync_host":
            row["sync_host_p50_ms"] = round(lat["p50_ms"], 3)
            row["sync_host_p99_ms"] = round(lat["p99_ms"], 3)
        else:
            row[f"{name}_speedup"] = round(base_time / t, 2)
        if name == "d2":
            # the honest latency split of the deepest pipeline: dispatch
            # (host enqueue) vs drain (the deferred blocking sync)
            row["d2_dispatch_p50_ms"] = round(lat["dispatch_p50_ms"], 3)
            row["d2_dispatch_p99_ms"] = round(lat["dispatch_p99_ms"], 3)
            row["d2_drain_p50_ms"] = round(lat["drain_p50_ms"], 3)
            row["d2_drain_p99_ms"] = round(lat["drain_p99_ms"], 3)
    return row


# ---------------------------------------------------------------------------
# Drift table: retirement policies on piecewise-stationary streams
# ---------------------------------------------------------------------------

DRIFT_POLICIES: Tuple[Tuple[str, Dict], ...] = (
    ("baseline", {}),                                      # growing memory
    ("forget", {"retirement": "forget"}),                  # lambda filled in
    ("window", {"retirement": "window"}),                  # capacity filled in
    ("adaptive", {"retirement": "adaptive"}),              # untold detector:
    # server defaults only - no lambda, capacity or switch point provided
)


def _make_drift_streams(
    n_streams: int, n_samples: int, t_len: int, n_classes: int, seed: int = 0
) -> Tuple[List[StreamRequest], List[int]]:
    """The shared drift fixture (``repro.data.make_drift_label_streams``)
    wrapped into serving requests."""
    arrays, switches = make_drift_label_streams(
        n_streams, n_samples, t_len, n_classes, seed=seed
    )
    streams = [StreamRequest(rid=rid, **arr) for rid, arr in enumerate(arrays)]
    return streams, switches


def _segment_accuracy(req: StreamRequest, lo: int, hi: int) -> float:
    preds = np.asarray(req.preds[lo:hi])
    return float((preds == req.label[lo:hi]).mean())


def _bench_drift_case(
    n_streams: int, n_samples: int, t_len: int, n_nodes: int, window: int,
    reps: int = 2, forget: float = 0.95, retire_frac: float = 0.25,
    n_classes: int = 4,
) -> Dict:
    """One drift-recovery comparison cell.

    Accuracy segments: ``pre`` = the ``seg`` samples before the switch,
    ``at`` = the ``seg/2`` right after it, ``post`` = the stream tail.
    Throughput is best-of-``reps`` after a warm (compile-absorbing) run,
    same discipline as the other tables.
    """
    cfg = DFRConfig(n_in=1, n_classes=n_classes, n_nodes=n_nodes)
    assert n_samples % window == 0
    retire_window = max(window, int(n_samples * retire_frac))
    total_samples = n_streams * n_samples

    row: Dict = {
        "table": "drift",
        "cell": f"S{n_streams}/N{n_samples}/Nx{n_nodes}/W{window}",
        "forget_lambda": forget,
        "window_capacity": retire_window,
    }
    base_time = None
    for name, kw in DRIFT_POLICIES:
        kw = dict(kw)
        if kw.get("retirement") == "forget":
            kw["forget"] = forget
        if kw.get("retirement") == "window":
            kw["retire_window"] = retire_window

        def run_once():
            streams, switches = _make_drift_streams(
                n_streams, n_samples, t_len, n_classes
            )
            elapsed, _ = _serve_batched(
                cfg, streams, t_len, window, phase_steps=3, refresh_every=2,
                refresh_mode="incremental", **kw,
            )
            return elapsed, streams, switches

        run_once()  # warm the jitted step/refresh programs
        best_t, streams, switches = None, None, None
        for _ in range(reps):
            t, st, sw = run_once()
            if best_t is None or t < best_t:
                best_t, streams, switches = t, st, sw
        pre, at, post = drift_segment_bounds(n_samples, switches[0], window)
        for seg_name, (lo, hi) in (("pre", pre), ("at", at), ("post", post)):
            row[f"{name}_{seg_name}_acc"] = round(float(np.mean(
                [_segment_accuracy(r, lo, hi) for r in streams])), 3)
        row[f"{name}_samples_per_s"] = round(total_samples / best_t, 1)
        if name == "baseline":
            base_time = best_t
        else:
            # retirement overhead: < 1.0 means the policy costs throughput
            row[f"{name}_throughput_ratio"] = round(base_time / best_t, 2)
    return row


# the adaptive detector under each serving mode it must compose with (the
# 8-device sharded variant lives in the forced-device CI parity test -
# the sharded episode is bitwise the unsharded one, so its accuracy IS
# the plain column)
ADAPTIVE_MODES: Tuple[Tuple[str, Dict], ...] = (
    ("plain", {}),
    ("blocked", {"step_block": 4}),
    ("int8", {"quantize": "int8"}),
)


def _bench_adaptive_modes_case(
    n_streams: int, n_samples: int, t_len: int, n_nodes: int, window: int,
    n_classes: int = 4,
) -> Dict:
    """retirement='adaptive' (server defaults, told nothing about the
    drift) under each serving mode: the tracked record behind the
    acceptance gate that the untold detector recovers into the hand-picked
    forget/window post-drift band everywhere it composes."""
    cfg = DFRConfig(n_in=1, n_classes=n_classes, n_nodes=n_nodes)
    row: Dict = {
        "table": "drift-adaptive-modes",
        "cell": f"S{n_streams}/N{n_samples}/Nx{n_nodes}/W{window}",
    }
    for mode, kw in ADAPTIVE_MODES:
        streams, switches = _make_drift_streams(
            n_streams, n_samples, t_len, n_classes
        )
        _serve_batched(
            cfg, streams, t_len, window, phase_steps=3, refresh_every=2,
            refresh_mode="incremental", retirement="adaptive", **kw,
        )
        pre, at, post = drift_segment_bounds(n_samples, switches[0], window)
        for seg_name, (lo, hi) in (("pre", pre), ("at", at), ("post", post)):
            row[f"{mode}_{seg_name}_acc"] = round(float(np.mean(
                [_segment_accuracy(r, lo, hi) for r in streams])), 3)
    return row


def run_drift(full: bool = False, smoke: bool = False) -> List[Dict]:
    """The drift table (now with untuned ``adaptive`` columns next to the
    hand-picked forget/window policies) plus the adaptive-modes record -
    the tracked BENCH_stream_drift.json suite."""
    if smoke:
        drift_cases = [(2, 64, 16, 8, 4)]
    elif full:
        drift_cases = [(4, 160, 16, 8, 4), (4, 160, 16, 16, 4),
                       (8, 160, 16, 16, 1)]
    else:
        drift_cases = [(4, 160, 16, 8, 4), (4, 160, 16, 16, 4)]
    rows = [_bench_drift_case(*c) for c in drift_cases]
    rows += [_bench_adaptive_modes_case(*c) for c in drift_cases]
    return rows


# ---------------------------------------------------------------------------
# Per-step HLO cost (launch/hlo_cost): program FLOPs/bytes, not wall-clock
# ---------------------------------------------------------------------------


def _infer_step_cost(n_nodes: int, n_classes: int, n_streams: int,
                     window: int, t_len: int,
                     quantize: str = "none") -> Dict[str, float]:
    """Optimized-HLO cost of one fused serving-logits dispatch.

    Lowers the slot-batched streaming-logits program (the per-step serving
    compute: S slots x W windows of T reservoir steps + the readout
    contraction) and walks the compiled HLO with ``launch/hlo_cost`` -
    exact loop-aware dot FLOPs and HBM bytes.  Unlike the samples/sec
    columns this is host-noise independent.

    Read fp32-vs-int8 with care: the cost model counts dot/conv FLOPs
    only (its documented scope), and the int8 program expresses the ring
    recurrence as per-step int8 dots while the fp32 program keeps it
    elementwise (invisible to the model).  The columns are therefore
    per-program absolute costs for trend tracking, NOT a cross-path
    speedup ratio.

    Delegates to ``runtime.planner.program_cost``, which memoizes the
    lower+compile per distinct ``(Nx, n_classes, S, window, t_len,
    quantize)`` - bench sweeps that revisit a shape (every policy column
    of a row, every rep) no longer pay a redundant XLA compile.
    """
    from repro.runtime import planner

    flops, mem_bytes = planner.program_cost(
        n_nodes, n_classes, n_streams, window, t_len, quantize)
    return {"flops": flops, "mem_bytes": mem_bytes}


# ---------------------------------------------------------------------------
# Sharded table (ISSUE 6): served-samples/sec vs slot-mesh device count
# ---------------------------------------------------------------------------

SHARDED_DEVICE_COUNTS: Tuple[int, ...] = (1, 2, 4, 8)


def _bench_sharded_case(n_streams: int, n_samples: int, t_len: int,
                        n_nodes: int, window: int = 1, reps: int = 2,
                        device_counts: Tuple[int, ...] = SHARDED_DEVICE_COUNTS
                        ) -> Dict:
    """One device-count scaling cell: the identical episode (same streams,
    refresh_mode='incremental', window=1 sample-by-sample serving) served
    by ``StreamServer(devices=n)`` for each mesh size.  Sharded episodes
    serve the devices=1 episode (tests/test_stream_sharded.py), so
    every column serves exactly the same computation - the table measures
    the scaling of the serving harness alone.

    Honest caveat, enforced per column: with
    ``--xla_force_host_platform_device_count`` the "devices" share the
    host's physical cores (``host_cores = os.cpu_count()``).  A column
    with more mesh devices than physical cores measures sharding
    *overhead* (per-device dispatch on a time-sliced core), not speedup -
    such columns are flagged ``dN_oversubscribed`` and their ratio is
    emitted as ``dN_overhead_ratio``, never ``dN_speedup``, so the tracked
    JSON cannot present overhead as a scaling datapoint.  A real speedup
    column needs cores >= devices (or real accelerators).
    """
    cfg = DFRConfig(n_in=3, n_classes=4, n_nodes=n_nodes)
    phase_steps, refresh_every = 4, 5
    total_samples = n_streams * n_samples
    row: Dict = {
        "table": "stream-sharded",
        "cell": f"S{n_streams}/Nx{n_nodes}",
        "samples": n_samples,
        "window": window,
        "host_cores": os.cpu_count(),
        "host_devices": jax.device_count(),
    }
    base_time = None
    for nd in device_counts:
        if n_streams % nd or nd > jax.device_count():
            continue

        def run_once():
            streams = _make_streams(n_streams, n_samples, t_len, 3, 4,
                                    seed=1)
            return _serve_batched(cfg, streams, t_len, window, phase_steps,
                                  refresh_every, refresh_mode="incremental",
                                  devices=nd)

        run_once()      # warm this mesh size's jitted program
        best = None
        for _ in range(reps):
            t, _ = run_once()
            best = t if best is None or t < best else best
        row[f"d{nd}_samples_per_s"] = round(total_samples / best, 1)
        if nd > (os.cpu_count() or 1):
            row[f"d{nd}_oversubscribed"] = True
        if base_time is None:
            base_time = best
        elif f"d{nd}_oversubscribed" in row:
            row[f"d{nd}_overhead_ratio"] = round(base_time / best, 2)
        else:
            row[f"d{nd}_speedup"] = round(base_time / best, 2)
    cost = _infer_step_cost(n_nodes, 4, n_streams, window, t_len)
    row["infer_flops_per_step"] = cost["flops"]
    row["infer_mem_bytes_per_step"] = cost["mem_bytes"]
    return row


def run_sharded(full: bool = False, smoke: bool = False) -> List[Dict]:
    """The device-count scaling table over the devices this process has:
    columns for mesh sizes beyond ``jax.device_count()`` are left out.  On
    a CPU host, give the process virtual devices before it starts
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``); nothing here
    starts a second process, which on a chip host could not reach a chip
    this one already holds.
    """
    # sharded cases (n_streams, n_samples, t_len, n_nodes): Nx in {8, 16} x
    # S in {64, 256} per the tracked BENCH_stream_sharded.json contract
    if smoke:
        cases = [(16, 8, 16, 8)]
        counts: Tuple[int, ...] = (1, 2, 8)
    else:
        cases = [(64, 16, 24, 8), (64, 16, 24, 16),
                 (256, 16, 24, 8), (256, 16, 24, 16)]
        counts = SHARDED_DEVICE_COUNTS
    return [_bench_sharded_case(*c, device_counts=counts) for c in cases]


# ---------------------------------------------------------------------------
# Quant table (ISSUE 7): int8 serving fast path + multi-sample step blocking
# ---------------------------------------------------------------------------

QUANT_POLICIES: Tuple[Tuple[str, Dict], ...] = (
    ("fp32", {}),                                        # the PR-6 fast path
    ("int8", {"quantize": "int8"}),
    ("fp32_b4", {"step_block": 4}),
    ("int8_b4", {"quantize": "int8", "step_block": 4}),
)


def _bench_quant_case(n_streams: int, n_samples: int, t_len: int,
                      n_nodes: int, window: int, reps: int = 5,
                      refresh_every: int = 5) -> Dict:
    """One quantized-serving comparison cell (PR-5 paired discipline: same
    streams, identical protocol, policies timed ROUND-ROBIN with
    best-of-reps per policy so shared-host noise windows cannot land on a
    single column).

    Besides samples/sec the row records the two host-independent axes:
    the per-slot serving-readout footprint (int8 codes + 3 f32 scale
    scalars vs fp32 weights - the deterministic memory-reduction
    acceptance) and the optimized-HLO per-step FLOPs/bytes of both
    serving programs.  Predictions are captured per policy so the int8
    column carries its own argmax-agreement-vs-fp32 number; training is
    fp32 either way (tests/test_stream_quant.py proves the states bitwise
    equal), so agreement measures exactly the serving-path rounding.
    """
    cfg = DFRConfig(n_in=3, n_classes=4, n_nodes=n_nodes)
    phase_steps = 4
    assert n_samples % window == 0
    total_samples = n_streams * n_samples

    def run_once(kw):
        streams = _make_streams(n_streams, n_samples, t_len, 3, 4)
        elapsed, _ = _serve_batched(
            cfg, streams, t_len, window, phase_steps, refresh_every,
            refresh_mode="incremental", **kw,
        )
        return elapsed, streams

    for _, kw in QUANT_POLICIES:        # warm every jitted program first
        run_once(kw)
    best: Dict[str, float] = {}
    preds: Dict[str, List[np.ndarray]] = {}
    for _ in range(reps):
        for name, kw in QUANT_POLICIES:
            t, streams = run_once(kw)
            if name not in best or t < best[name]:
                best[name] = t
            # episodes are deterministic per policy - any rep's preds do
            preds[name] = [np.asarray(r.preds).copy() for r in streams]

    row: Dict = {
        "table": "stream-quant",
        "cell": f"S{n_streams}/Nx{n_nodes}/W{window}",
        "samples": n_samples,
        "t_len": t_len,     # the planner replay gate re-prices this row
    }
    base_time = best["fp32"]
    for name, _ in QUANT_POLICIES:
        row[f"{name}_samples_per_s"] = round(total_samples / best[name], 1)
        if name != "fp32":
            row[f"{name}_speedup"] = round(base_time / best[name], 2)
    row["int8_fp32_agreement"] = round(float(np.mean(
        [(a == b).mean() for a, b in zip(preds["int8"], preds["fp32"])])), 4)

    # serving-state footprint per slot: what the serving step reads beyond
    # the (shared-shape) reservoir inputs - int8 readout codes + the three
    # f32 quant scalars (w_scale, x_scale, x_absmax) vs the fp32 readout
    nr = n_nodes * (n_nodes + 1)
    fp32_bytes = 4 * cfg.n_classes * nr
    int8_bytes = 1 * cfg.n_classes * nr + 3 * 4
    row["fp32_readout_bytes_per_slot"] = fp32_bytes
    row["int8_readout_bytes_per_slot"] = int8_bytes
    row["readout_bytes_ratio"] = round(fp32_bytes / int8_bytes, 2)

    for qname, quantize in (("fp32", "none"), ("int8", "int8")):
        cost = _infer_step_cost(n_nodes, 4, n_streams, window, t_len,
                                quantize=quantize)
        row[f"{qname}_infer_flops_per_step"] = cost["flops"]
        row[f"{qname}_infer_mem_bytes_per_step"] = cost["mem_bytes"]
    return row


def _bench_quant_drift_case(n_streams: int, n_samples: int, t_len: int,
                            n_nodes: int, window: int, reps: int = 2,
                            forget: float = 0.95,
                            n_classes: int = 4) -> Dict:
    """int8 vs fp32 accuracy band on the NARMA10 piecewise-drift fixture.

    Both paths serve the identical episode (forget retirement, the drift
    table's protocol); training statistics stay fp32 under int8 serving,
    so any accuracy delta is pure serving-path rounding.  The pre/at/post
    segments and the ``*_acc_delta`` columns are the tracked tolerance
    band the acceptance gate reads.
    """
    cfg = DFRConfig(n_in=1, n_classes=n_classes, n_nodes=n_nodes)
    assert n_samples % window == 0
    row: Dict = {
        "table": "quant-drift",
        "cell": f"S{n_streams}/N{n_samples}/Nx{n_nodes}/W{window}",
        "forget_lambda": forget,
    }
    for name, kw in (("fp32", {}), ("int8", {"quantize": "int8"})):
        def run_once():
            streams, switches = _make_drift_streams(
                n_streams, n_samples, t_len, n_classes
            )
            elapsed, _ = _serve_batched(
                cfg, streams, t_len, window, phase_steps=3, refresh_every=2,
                refresh_mode="incremental", retirement="forget",
                forget=forget, **kw,
            )
            return elapsed, streams, switches

        run_once()      # warm
        best_t, streams, switches = None, None, None
        for _ in range(reps):
            t, st, sw = run_once()
            if best_t is None or t < best_t:
                best_t, streams, switches = t, st, sw
        pre, at, post = drift_segment_bounds(n_samples, switches[0], window)
        for seg_name, (lo, hi) in (("pre", pre), ("at", at), ("post", post)):
            row[f"{name}_{seg_name}_acc"] = round(float(np.mean(
                [_segment_accuracy(r, lo, hi) for r in streams])), 3)
        row[f"{name}_samples_per_s"] = round(
            n_streams * n_samples / best_t, 1)
    for seg in ("pre", "at", "post"):
        row[f"{seg}_acc_delta"] = round(
            row[f"int8_{seg}_acc"] - row[f"fp32_{seg}_acc"], 3)
    return row


def run_quant(full: bool = False, smoke: bool = False) -> List[Dict]:
    """The quantized fast-path table (tracked in BENCH_stream_quant.json).

    The Nx=16/S=16/W=1 cell is the ISSUE-7 acceptance regime (the PR-5
    pipeline protocol's headline cell); Nx=8 is the honest dispatch-bound
    column where the int8 kernel saves little compute.
    """
    if smoke:
        quant_cases = [(4, 8, 16, 8, 1)]
        drift_cases = [(2, 64, 16, 8, 4)]
    elif full:
        quant_cases = [(16, 20, 24, 16, 1), (16, 20, 24, 8, 1),
                       (32, 20, 24, 16, 1)]
        drift_cases = [(4, 160, 16, 8, 4), (4, 160, 16, 16, 4)]
    else:
        quant_cases = [(16, 20, 24, 16, 1), (16, 20, 24, 8, 1)]
        drift_cases = [(4, 160, 16, 16, 4)]
    rows = [_bench_quant_case(*c) for c in quant_cases]
    rows += [_bench_quant_drift_case(*c) for c in drift_cases]
    return rows


# ---------------------------------------------------------------------------
# Planner-validation table (ISSUE 8): measured lattice vs the cost model
# ---------------------------------------------------------------------------

#: the searched performance-knob lattice, named for the bench columns
PLANNER_LATTICE: Tuple[Tuple[str, Dict], ...] = (
    ("rec_b1", {"refresh_mode": "recompute", "step_block": 1}),
    ("inc_b1", {"refresh_mode": "incremental", "step_block": 1}),
    ("rec_b4", {"refresh_mode": "recompute", "step_block": 4}),
    ("inc_b4", {"refresh_mode": "incremental", "step_block": 4}),
)

#: the ROADMAP contract: auto pick within 1.3x of the measured best
PLANNER_GATE = 1.3


def _bench_planner_case(n_streams: int, n_samples: int, t_len: int,
                        n_nodes: int, window: int, reps: int = 3,
                        refresh_every: int = 5) -> Dict:
    """One planner-validation cell: measure every config of the knob
    lattice (PR-5 paired round-robin discipline, best-of-reps per config),
    then ask ``runtime.planner`` to rank the SAME configs from its
    calibrated cost model alone.  The row records both rankings and the
    gate: the planner's pick must serve within ``PLANNER_GATE`` (1.3x) of
    the measured-best config's samples/sec.  ``ok=False`` rows make
    ``--planner`` exit nonzero - the CI teeth of ``config='auto'``.
    """
    from repro.runtime import planner as rplanner

    cfg = DFRConfig(n_in=3, n_classes=4, n_nodes=n_nodes)
    phase_steps = 4
    assert n_samples % window == 0
    total_samples = n_streams * n_samples

    def run_once(kw):
        streams = _make_streams(n_streams, n_samples, t_len, 3, 4)
        elapsed, _ = _serve_batched(
            cfg, streams, t_len, window, phase_steps, refresh_every, **kw,
        )
        return elapsed

    for _, kw in PLANNER_LATTICE:       # warm every jitted program first
        run_once(kw)
    best: Dict[str, float] = {}
    for _ in range(reps):
        for name, kw in PLANNER_LATTICE:
            t = run_once(kw)
            if name not in best or t < best[name]:
                best[name] = t

    cal = rplanner.get_calibration()
    predicted = {
        name: rplanner.predict_step_cost(
            n_nodes, n_streams, window, "none", kw["refresh_mode"], 1,
            kw["step_block"], "none", n_classes=4, t_len=t_len,
            refresh_every=refresh_every, cal=cal,
        )
        for name, kw in PLANNER_LATTICE
    }
    measured = {n: total_samples / t for n, t in best.items()}
    pick = min(predicted, key=predicted.get)
    meas_best = max(measured, key=measured.get)
    ratio = measured[meas_best] / measured[pick]

    row: Dict = {
        "table": "stream-planner",
        "cell": f"S{n_streams}/Nx{n_nodes}/W{window}",
        "samples": n_samples,
        "t_len": t_len,
        "refresh_every": refresh_every,
    }
    for name, _ in PLANNER_LATTICE:
        row[f"{name}_samples_per_s"] = round(measured[name], 1)
        row[f"{name}_predicted_samples_per_s"] = round(
            1.0 / predicted[name], 1)
    row["planner_pick"] = pick
    row["measured_best"] = meas_best
    row["best_over_pick_ratio"] = round(ratio, 3)
    row["gate"] = PLANNER_GATE
    row["ok"] = bool(ratio <= PLANNER_GATE)
    return row


def run_planner(full: bool = False, smoke: bool = False) -> List[Dict]:
    """The planner-validation table (tracked in BENCH_stream_planner.json).

    Cells span the regimes where the lattice's winner is known to flip:
    Nx=16/W=1 (refresh-bound - incremental wins), Nx=8/W=1
    (dispatch-bound - step blocking wins), and in ``--full`` the Nx=8/W=8
    mass-arrival column where recompute historically wins.  Rows also
    replay the tracked quant table through the model
    (``planner.replay_bench_tables``), so regenerating this table
    re-validates the planner against every benched shape at once.
    """
    from repro.runtime import planner as rplanner

    if smoke:
        cases = [(4, 8, 16, 8, 1)]
    elif full:
        cases = [(16, 20, 24, 16, 1), (16, 20, 24, 8, 1),
                 (16, 80, 24, 8, 8), (32, 20, 24, 16, 1)]
    else:
        cases = [(16, 20, 24, 16, 1), (16, 20, 24, 8, 1)]
    rows = [_bench_planner_case(*c) for c in cases]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for rep in rplanner.replay_bench_tables(root):
        rep["table"] = "stream-planner-replay"
        rep["gate"] = PLANNER_GATE
        rows.append(rep)
    return rows


def run(full: bool = False, smoke: bool = False) -> List[Dict]:
    # The batched step amortizes dispatch + the per-window small-op work
    # across all S slots; the headline Nx=8/S=16 regime is where the >= 3x
    # acceptance target lands (~4x on 2-core CPU).  At paper nodes (Nx=16+)
    # the periodic batched (s, s) Cholesky refresh grows as s^3 and eats
    # into the step speedup (~2.5-3x) - reported honestly, as with
    # bench_population's dispatch-amortization regime.
    # refresh-mode cases (n_streams, n_samples, t_len, n_nodes, window):
    # window=1 is the paper's sample-by-sample serving regime where the
    # refresh dominates at Nx=16; window=8 is the honest mass-arrival
    # column where recompute still wins (see module docstring)
    # drift cases (n_streams, n_samples, t_len, n_nodes, window): streams
    # long enough that the retirement policies have post-switch samples to
    # re-track with (the post segment is the last n/5)
    # pipeline cases (n_streams, n_samples, t_len, n_nodes, window,
    # retirement): window=1 sample-by-sample serving, the regime where the
    # PR-2/PR-4 loop was host/refresh-bound; Nx=8 is the honest
    # dispatch-bound column where the pipeline roughly ties
    if smoke:
        cases = [(4, 8, 16, 8)]
        refresh_cases = [(4, 8, 16, 8, 1)]
        pipeline_cases = [(4, 8, 16, 8, 1, "none")]
        drift_cases = [(2, 64, 16, 8, 4)]
    elif full:
        cases = [(16, 24, 24, 8), (16, 24, 24, 16), (16, 64, 32, 16),
                 (12, 24, 24, 30)]
        refresh_cases = [(16, 20, 24, 8, 1), (16, 20, 24, 16, 1),
                         (32, 20, 24, 16, 1), (16, 80, 24, 16, 8),
                         (32, 20, 24, 8, 1)]
        pipeline_cases = [(16, 20, 24, 8, 1, "none"),
                          (16, 20, 24, 16, 1, "none"),
                          (16, 20, 24, 8, 1, "forget"),
                          (16, 20, 24, 16, 1, "forget"),
                          (16, 20, 24, 8, 1, "window"),
                          (16, 20, 24, 16, 1, "window"),
                          (32, 20, 24, 16, 1, "none"),
                          (32, 20, 24, 16, 1, "forget"),
                          (32, 20, 24, 16, 1, "window")]
        drift_cases = [(4, 160, 16, 8, 4), (4, 160, 16, 16, 4),
                       (8, 160, 16, 16, 1)]
    else:
        cases = [(16, 24, 24, 8), (16, 24, 24, 16)]
        refresh_cases = [(16, 20, 24, 8, 1), (16, 20, 24, 16, 1),
                         (32, 20, 24, 16, 1), (16, 80, 24, 16, 8)]
        pipeline_cases = [(16, 20, 24, 8, 1, "none"),
                          (16, 20, 24, 16, 1, "none"),
                          (16, 20, 24, 8, 1, "forget"),
                          (16, 20, 24, 16, 1, "forget"),
                          (16, 20, 24, 8, 1, "window"),
                          (16, 20, 24, 16, 1, "window")]
        drift_cases = [(4, 160, 16, 8, 4), (4, 160, 16, 16, 4)]
    rows = [_bench_case(*c) for c in cases]
    rows += [_bench_refresh_case(*c) for c in refresh_cases]
    rows += [_bench_pipeline_case(*c) for c in pipeline_cases]
    rows += [_bench_drift_case(*c) for c in drift_cases]
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="one tiny case (CI lane)")
    ap.add_argument("--sharded", action="store_true",
                    help="the device-count scaling table only, over the "
                         "devices this process has (on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8 first)")
    ap.add_argument("--quant", action="store_true",
                    help="the int8 fast-path + step-blocking table only")
    ap.add_argument("--planner", action="store_true",
                    help="the planner-validation table only; exits nonzero "
                         "when the auto pick misses the 1.3x gate")
    ap.add_argument("--drift", action="store_true",
                    help="the drift-recovery table (retirement policies "
                         "incl. untuned adaptive) + adaptive-modes record")
    ap.add_argument("--json", action="store_true",
                    help="emit rows as JSON lines (machine readable)")
    args = ap.parse_args()
    if args.sharded:
        rows = run_sharded(full=args.full, smoke=args.smoke)
    elif args.quant:
        rows = run_quant(full=args.full, smoke=args.smoke)
    elif args.planner:
        rows = run_planner(full=args.full, smoke=args.smoke)
    elif args.drift:
        rows = run_drift(full=args.full, smoke=args.smoke)
    else:
        rows = run(full=args.full, smoke=args.smoke)
    for row in rows:
        print(json.dumps(row) if args.json else row)
    if args.planner:
        bad = [r for r in rows if r.get("ok") is False]
        if bad:
            cells = ", ".join(r.get("cell", "?") for r in bad)
            print(f"PLANNER GATE FAILED ({PLANNER_GATE}x): {cells}",
                  file=sys.stderr)
            raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
