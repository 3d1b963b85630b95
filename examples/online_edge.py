"""Online edge training + inference (the paper's deployment scenario),
served through the continuous-batching stream server.

    PYTHONPATH=src python examples/online_edge.py [--size-cap 100]
        [--nodes 30] [--streams 4] [--window 4] [--max-streams 2]

Simulates a fleet of predictive-maintenance sensors (Sec. 1): several
independent streams submit labeled sample windows; the server packs them
into fixed slots and advances every live stream with ONE fused jitted step
per window round - the 'everything on the FPGA' analogue, multi-tenant:

  * infer-before-update: each window is answered from the parameters the
    slot had before seeing the labels (the honest online metric),
  * phase 1 (slot-local): truncated-bp SGD adapts (p, q, W, b),
  * phase 2: the reservoir freezes and the slot accumulates the Ridge
    sufficient statistics (A, B) in place; ``reset_statistics`` semantics
    guarantee no stale phase-1 features leak into them,
  * every few rounds the server re-solves every live slot's output layer
    with one batched Cholesky (the paper's 1-D Cholesky, batched).

With fewer slots than streams, finished streams retire and the slots
refill (continuous batching).  The retired snapshot of each stream is a
complete ``OnlineState``: we pick the best stream's model, give it the
single-stream ``reset_statistics`` / ``refresh_output`` treatment on a
held-out pass, and report final accuracy.

Drift mode (``--drift``): serve piecewise-stationary NARMA streams
(``repro.data.make_narma10_drift``) instead of a dataset, and report the
online accuracy before / at / after each stream's drift point - the
regime where the sample-retirement policies (``--forget`` lambda, or
``--retire-window`` capacity with the guarded hyperbolic downdate) keep
tracking while the grow-only default stays anchored to the dead regime.
``--retirement adaptive`` (PR 9) needs neither knob: a per-slot loss-EMA
breakpoint detector anneals that slot's statistics only when its own
error rate breaks out, so it recovers like the hand-tuned policies
without being told lambda, the capacity, or that a drift exists.
``--autotune`` attaches the warm-pool background autotuner: a per-cohort
(p, q, beta) population re-evaluated on recent retained windows, with
winners hot-swapped into live slots at refresh boundaries.

Sharded serving (``--devices N``): shard the server's slot axis over N of
the process's devices (PR 6; ``--max-streams`` is rounded up to a multiple
of N); asking for more devices than exist fails.  On a CPU-only host, give
the process virtual devices before it starts
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  The episode is
the single-device one (``repro.runtime.parity``); only the placement
changes.

Quantized serving (``--quantize int8``, PR 7): armed slots answer from the
int8 fused fast path (coded readout + reservoir state, integer compute,
fp32 dequantized logits); scales calibrate online and fold at the ridge
refresh boundaries, training stays fp32.  Step blocking
(``--step-block T``) fuses up to T window rounds per slot into one
dispatch; the served episode is exactly the ``--step-block 1`` one.  Both
compose with ``--devices``:

    PYTHONPATH=src python examples/online_edge.py --quantize int8
    PYTHONPATH=src python examples/online_edge.py --step-block 4 \
        --quantize int8 --devices 8
"""
import argparse

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import OnlineDFR
from repro.core.types import DFRConfig
from repro.data import (
    PAPER_DATASETS,
    drift_segment_bounds,
    load,
    make_drift_label_streams,
)
from repro.runtime import StreamRequest, StreamServer


def _server_retirement_kw(args) -> dict:
    """Map --forget / --retire-window to StreamServer retirement kwargs.

    ``refresh_mode`` stays ``None`` when the flag was not given, so
    ``--config auto`` can plan it; the retirement policies still pin
    ``incremental`` explicitly (a semantic requirement, not a tuning
    choice - window retirement downdates a live factor)."""
    picked = [f for f, v in (("--forget", args.forget),
                             ("--retire-window", args.retire_window),
                             ("--retirement", args.retirement)) if v is not None]
    if len(picked) > 1:
        raise SystemExit(f"pick one of {' / '.join(picked)}")
    if args.retirement == "adaptive":
        # the self-adjusting policy: no lambda / capacity to supply - the
        # per-slot detector runs on the server's default thresholds
        return {"retirement": "adaptive",
                "refresh_mode": args.refresh_mode or "incremental"}
    if args.forget is not None:
        return {"retirement": "forget", "forget": args.forget,
                "refresh_mode": args.refresh_mode or "incremental"}
    if args.retire_window is not None:
        return {"retirement": "window", "retire_window": args.retire_window,
                "refresh_mode": args.refresh_mode or "incremental"}
    return {"refresh_mode": args.refresh_mode}


def _server_pipeline_kw(args) -> dict:
    """Map the serving-pipeline flags to StreamServer kwargs (PR 5/6/8).

    Unset knobs pass ``None`` through: the server resolves them to the
    historical defaults, or - under ``--config auto`` - to the calibrated
    planner's picks."""
    return {
        "pipeline_depth": args.pipeline_depth,
        "staging": "host" if args.host_staging else "device",
        "devices": args.devices,
        "quantize": args.quantize,
        "step_block": args.step_block,
        "config": args.config,
    }


def _attach_autotuner(server, args):
    """--autotune: hang the warm-pool (p, q, beta) autotuner off the server."""
    if not args.autotune:
        return None
    from repro.runtime import WarmPoolAutotuner
    tuner = WarmPoolAutotuner(server)
    server.attach_autotuner(tuner)
    return tuner


def _print_tuner(tuner) -> None:
    if tuner is not None:
        st = tuner.stats()
        print(f"  autotuner: {st['rounds_run']} tune round(s), "
              f"{st['swaps_applied']} hot-swap(s) applied "
              f"({st['swaps_pending']} still pending at drain)")


def _fmt_ms(v) -> str:
    """A latency percentile for humans: NaN means 'no records', never a
    fake 0.0 ms reading."""
    return "n/a" if np.isnan(v) else f"{v:.1f} ms"


def _print_plan(server) -> None:
    if server.plan is not None:
        pl = server.plan
        print(f"  auto config (calibrated planner): "
              f"refresh_mode={server.refresh_mode}, "
              f"refresh_cohorts={server.cohorts.n_cohorts}, "
              f"step_block={server.step_block} "
              f"(predicted {pl.predicted_samples_per_s:.0f} samples/s)")


def _effective_max_streams(args) -> int:
    """Round --max-streams up to a multiple of --devices (equal shards)."""
    ms = args.max_streams
    if args.devices > 1 and ms % args.devices:
        ms = -(-ms // args.devices) * args.devices
        print(f"note: rounding --max-streams up to {ms} "
              f"(multiple of --devices {args.devices})")
    return ms


def _print_mesh(server) -> None:
    if server.mesh is not None:
        print(f"  slot mesh: {server.devices} devices x "
              f"{server.max_streams // server.devices} slots each "
              f"({jax.device_count()} XLA devices visible)")
    if server.quantize != "none" or server.step_block > 1:
        print(f"  serving fast path: quantize={server.quantize}, "
              f"step_block={server.step_block} (training stays fp32; the "
              f"episode schedule matches the unblocked fp32 server)")


def run_drift(args) -> None:
    """Serve drifting NARMA streams and report drift-recovery accuracy."""
    n = 64 if args.smoke else 160
    t_len, n_classes = 16, 4
    nodes = min(args.nodes, 8) if args.smoke else args.nodes
    cfg = DFRConfig(n_in=1, n_classes=n_classes, n_nodes=nodes)
    arrays, switches = make_drift_label_streams(
        args.streams, n, t_len, n_classes)
    streams = [StreamRequest(rid=rid, **arr)
               for rid, arr in enumerate(arrays)]

    kw = _server_retirement_kw(args)
    server = StreamServer(
        cfg, t_max=t_len, max_streams=_effective_max_streams(args),
        window=args.window, phase_steps=3, refresh_every=2,
        refresh_cohorts=args.refresh_cohorts,
        **_server_pipeline_kw(args), **kw,
    )
    policy = kw.get("retirement", "none")
    print(f"serving {len(streams)} drifting NARMA streams x {n} samples "
          f"(switch at sample {switches[0]}; retirement={policy})")
    _print_mesh(server)
    _print_plan(server)
    tuner = _attach_autotuner(server, args)
    for s in streams:
        server.submit(s)
    done = server.run_until_drained()
    _print_tuner(tuner)

    for r in sorted(done, key=lambda r: r.rid):
        bounds = drift_segment_bounds(n, switches[r.rid], args.window)
        p = np.asarray(r.preds)
        pre, at, post = (float((p[lo:hi] == r.label[lo:hi]).mean())
                         for lo, hi in bounds)
        print(f"  stream {r.rid}: online acc pre-drift {pre:.3f} / at "
              f"{at:.3f} / post {post:.3f} "
              f"({int(r.final_state.ridge.count)} samples in (A,B))")
    lat = server.latency_percentiles_ms()
    print(f"  window-round latency p50 {_fmt_ms(lat['p50_ms'])} / "
          f"p99 {_fmt_ms(lat['p99_ms'])} over {server.global_step} rounds "
          f"(p99 absorbs the one-time jit compile at these few rounds; "
          f"bench_stream reports warmed steady-state latency)")
    if server.pipeline_depth > 0:
        print(f"  pipeline depth {server.pipeline_depth}: dispatch p50 "
              f"{_fmt_ms(lat['dispatch_p50_ms'])}, drain (sync) p50 "
              f"{_fmt_ms(lat['drain_p50_ms'])} / "
              f"p99 {_fmt_ms(lat['drain_p99_ms'])}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="ECG")
    ap.add_argument("--size-cap", type=int, default=100)
    ap.add_argument("--nodes", type=int, default=30)
    ap.add_argument("--streams", type=int, default=4,
                    help="how many sensor streams to carve the data into")
    ap.add_argument("--max-streams", type=int, default=2,
                    help="server slots (< streams exercises refill)")
    ap.add_argument("--window", type=int, default=4)
    ap.add_argument("--refresh-mode", choices=("recompute", "incremental"),
                    default=None,
                    help="periodic ridge refresh: re-factorize B (O(s^3)) "
                         "or keep a live rank-1-updated Cholesky factor per "
                         "slot (O(s^2) solves); default recompute, or the "
                         "planner's pick under --config auto")
    ap.add_argument("--refresh-cohorts", type=int, default=None,
                    help="stagger the refresh round over this many "
                         "round-robin slot cohorts (default 1 = global "
                         "round, or the planner's pick under --config auto)")
    ap.add_argument("--forget", type=float, default=None, metavar="LAMBDA",
                    help="forgetting-factor retirement: decay (A, B) and "
                         "the live factor by lambda per accumulated sample "
                         "(implies --refresh-mode incremental; lambda=1.0 "
                         "is exactly the non-retiring path)")
    ap.add_argument("--retire-window", type=int, default=None, metavar="W",
                    help="sliding-window retirement: keep only the last W "
                         "samples per slot in (A, B, Lt) via guarded "
                         "hyperbolic downdates (implies --refresh-mode "
                         "incremental; W >= stream length is exactly the "
                         "non-retiring path)")
    ap.add_argument("--retirement", choices=("adaptive",), default=None,
                    help="'adaptive' (PR 9): per-slot loss-EMA breakpoint "
                         "detector anneals a slot's (A, B, Lt) only when "
                         "that slot's own error rate breaks out - drift "
                         "recovery without hand-picking --forget or "
                         "--retire-window (implies --refresh-mode "
                         "incremental; bitwise the non-retiring path while "
                         "the detector stays silent)")
    ap.add_argument("--autotune", action="store_true",
                    help="attach the warm-pool background autotuner (PR 9): "
                         "a per-cohort (p, q, beta) population re-evaluated "
                         "on each slot's recent retained windows, winners "
                         "hot-swapped into live slots just after their "
                         "cohort's refresh boundary (factor invariant "
                         "re-seeded, quant scales re-arm)")
    ap.add_argument("--pipeline-depth", type=int, default=0, metavar="D",
                    help="async serving pipeline depth: predictions ride a "
                         "lag-D device ring while the host books step k "
                         "during device compute of k+1..k+D (0 = fully "
                         "synchronous; the served episode is bit-identical "
                         "at every depth)")
    ap.add_argument("--devices", type=int, default=1, metavar="N",
                    help="shard the server's slot axis over N of the "
                         "process's devices (PR 6; rounds --max-streams up "
                         "to a multiple of N; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N first)")
    ap.add_argument("--quantize", choices=("none", "int8"), default="none",
                    help="serve armed slots from the int8 fused fast path "
                         "(PR 7): coded readout + reservoir state, integer "
                         "reservoir/DPRR/readout compute, fp32 dequantized "
                         "logits; scales fold at ridge-refresh boundaries "
                         "and training stays fp32 (requires device staging)")
    ap.add_argument("--step-block", type=int, default=None, metavar="T",
                    help="multi-sample step blocking: fuse up to T window "
                         "rounds per slot into ONE dispatch (PR 7); blocks "
                         "clamp at retirement boundaries so the served "
                         "episode is exactly the T=1 one (requires device "
                         "staging; default 1, or the planner's pick under "
                         "--config auto)")
    ap.add_argument("--config", choices=("auto",), default=None,
                    help="'auto': fill the unset performance knobs "
                         "(--refresh-mode / --refresh-cohorts / "
                         "--step-block) from the calibrated cost-model "
                         "planner (PR 8; first run on a host pays a few "
                         "seconds of micro-calibration, persisted to "
                         ".planner_calibration.json)")
    ap.add_argument("--host-staging", action="store_true",
                    help="use the PR-4 host-staged batch build instead of "
                         "the device-resident request pool (A/B baseline; "
                         "bit-identical, slower)")
    ap.add_argument("--drift", action="store_true",
                    help="serve piecewise-stationary NARMA streams and "
                         "report before/at/after-drift online accuracy")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes (CI drift smoke lane)")
    args = ap.parse_args()

    if args.drift:
        run_drift(args)
        return

    spec = PAPER_DATASETS[args.dataset]
    train, test = load(args.dataset, size_cap=args.size_cap)
    cfg = DFRConfig(n_in=spec.n_in, n_classes=spec.n_classes,
                    n_nodes=args.nodes)

    # carve the training set into independent streams (one per 'sensor');
    # array_split uses every sample and honors --streams exactly
    n = train.batch
    u, ln, lab = (np.asarray(train.u), np.asarray(train.length),
                  np.asarray(train.label))
    splits = [idx for idx in np.array_split(np.arange(n), args.streams)
              if len(idx)]
    streams = [
        StreamRequest(rid=i, u=u[idx], length=ln[idx], label=lab[idx])
        for i, idx in enumerate(splits)
    ]

    # phase 1 covers ~40% of each stream's windows, but always leaves at
    # least one phase-2 window so (A, B) accumulate and the refresh runs
    windows_per_stream = max(1, len(splits[0]) // args.window)
    phase_steps = max(1, min(int(windows_per_stream * 0.4) or 1,
                             windows_per_stream - 1))
    kw = _server_retirement_kw(args)
    server = StreamServer(
        cfg, t_max=train.t_max, max_streams=_effective_max_streams(args),
        window=args.window, phase_steps=phase_steps, refresh_every=5,
        refresh_cohorts=args.refresh_cohorts,
        **_server_pipeline_kw(args), **kw,
    )
    print(f"serving {len(streams)} streams x ~{len(splits[0])} samples "
          f"({server.max_streams} slots, windows of {args.window}); phase 1 "
          f"(reservoir adaptation) for {phase_steps} windows/stream, then "
          f"phase 2 ((A,B) accumulation, {server.refresh_mode} ridge refresh "
          f"every 5 rounds over {server.cohorts.n_cohorts} cohort(s), "
          f"retirement={server.retirement}) - the paper's protocol, "
          f"train-while-serve")
    _print_mesh(server)
    _print_plan(server)
    tuner = _attach_autotuner(server, args)
    for s in streams:
        server.submit(s)
    done = server.run_until_drained()
    _print_tuner(tuner)

    for r in sorted(done, key=lambda r: r.rid):
        print(f"  stream {r.rid}: {r.n_samples} samples, rolling online acc "
              f"{r.online_accuracy:.3f} "
              f"({int(r.final_state.ridge.count)} samples in (A,B))")
    lat = server.latency_percentiles_ms()
    print(f"  window-round latency p50 {_fmt_ms(lat['p50_ms'])} / "
          f"p99 {_fmt_ms(lat['p99_ms'])} over {server.global_step} rounds")
    if server.pipeline_depth > 0:
        print(f"  pipeline depth {server.pipeline_depth}: dispatch p50 "
              f"{_fmt_ms(lat['dispatch_p50_ms'])}, drain (sync) p50 "
              f"{_fmt_ms(lat['drain_p50_ms'])} / "
              f"p99 {_fmt_ms(lat['drain_p99_ms'])}")

    # held-out evaluation with the best stream's retired model: refresh the
    # readout from its streamed statistics, then classify the test split
    best = max(done, key=lambda r: (r.online_accuracy, -r.rid))
    system = OnlineDFR(cfg, mask=server.mask)
    state = best.final_state
    if int(state.ridge.count) > 0:
        state = system.refresh_output(state, jnp.float32(1e-2))
    else:
        print("  note: no phase-2 samples accumulated (stream too short for "
              "the phase split) - evaluating the SGD readout unrefreshed")
    preds = system.infer(state, test.u, test.length)
    acc = float(jnp.mean((preds == test.label).astype(jnp.float32)))
    print(f"final held-out accuracy (best stream {best.rid}'s model, "
          f"p={float(state.params.p):.4f} q={float(state.params.q):.4f}): "
          f"{acc:.3f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
