"""Benchmark harness entry point - one function per paper table.

Prints ``name,us_per_call,derived`` CSV rows (one per table entry) and a
human-readable summary.  ``--full`` runs the complete 12-dataset versions.

    PYTHONPATH=src python -m benchmarks.run [--full]
"""
from __future__ import annotations

import argparse
import sys
import time


def _emit(rows):
    for r in rows:
        name = r.pop("table")
        key = r.pop("dataset", r.pop("cell", ""))
        us = r.pop("bp_time_s", r.pop("gaussian_us", r.pop(
            "bound_s", r.pop("fused_time_s", 0.0))))
        derived = ";".join(f"{k}={v}" for k, v in r.items())
        print(f"{name}/{key},{us},{derived}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="all 12 datasets at full Table-4 sizes (slow)")
    ap.add_argument("--only", default=None,
                    help="comma list: ridge,backprop,truncation,system,"
                         "population,stream,stream_quant,stream_planner,"
                         "stream_drift,train_fused,roofline")
    args = ap.parse_args()

    from benchmarks import (bench_backprop, bench_population, bench_ridge,
                            bench_stream, bench_system, bench_truncation,
                            roofline)

    suites = {
        "ridge": lambda: bench_ridge.run(args.full),
        "backprop": lambda: bench_backprop.run(args.full),
        "truncation": lambda: bench_truncation.run(args.full),
        "system": lambda: bench_system.run(args.full),
        "population": lambda: bench_population.run(args.full),
        "stream": lambda: bench_stream.run(args.full),
        "stream_sharded": lambda: bench_stream.run_sharded(args.full),
        "stream_quant": lambda: bench_stream.run_quant(args.full),
        "stream_planner": lambda: bench_stream.run_planner(args.full),
        "stream_drift": lambda: bench_stream.run_drift(args.full),
        "train_fused": lambda: bench_backprop.run_train_fused(args.full),
        "roofline": lambda: roofline.summary_csv(),
    }
    # opt-in only: the sharded sweep needs a process started with several
    # devices (on CPU: XLA_FLAGS=--xla_force_host_platform_device_count=8)
    default_suites = [s for s in suites if s != "stream_sharded"]
    selected = (args.only.split(",") if args.only else default_suites)

    t0 = time.time()
    for name in selected:
        print(f"# --- {name} ---", flush=True)
        try:
            rows = suites[name]()
            if name in _BENCH_JSON:
                _write_bench_json(name, rows)
            _emit([dict(r) for r in rows])
        except Exception as ex:  # noqa: BLE001
            print(f"{name},0,error={type(ex).__name__}:{ex}", file=sys.stderr)
            raise
    print(f"# done in {time.time()-t0:.1f}s")


# tracked perf records at the repo root, one per suite that owns a
# BENCH_*.json contract: (filename, unit, honest caveat).  Every row of
# these files also carries per-step FLOPs/bytes from launch/hlo_cost
# (groundwork for the ROADMAP cost-model planner item).
_BENCH_JSON = {
    "stream_sharded": (
        "BENCH_stream_sharded.json",
        "served samples/sec vs slot-mesh device count",
        "columns with more mesh devices than physical host cores are "
        "flagged dN_oversubscribed and report dN_overhead_ratio instead "
        "of dN_speedup: forced host-device splits time-slice the shared "
        "cores, so those numbers measure sharding OVERHEAD, never "
        "speedup; regenerate on a host with real parallel devices for a "
        "scaling curve",
    ),
    "stream_quant": (
        "BENCH_stream_quant.json",
        "int8 quantized serving fast path + step blocking vs fp32",
        "samples/sec columns are wall-clock on this host (PR-5 paired "
        "round-robin protocol); readout_bytes_ratio and the "
        "*_infer_flops/_mem_bytes_per_step columns are host-independent "
        "but count dot/conv work only - the int8 program casts the ring "
        "recurrence as int8 dots while fp32 keeps it elementwise "
        "(invisible to the model), so they track per-program trends, not "
        "a cross-path ratio; quant-drift rows track the int8 accuracy "
        "band (training stays fp32, so deltas are pure serving-path "
        "rounding)",
    ),
    "stream_drift": (
        "BENCH_stream_drift.json",
        "drift-recovery accuracy by retirement policy (pre/at/post switch)",
        "accuracy columns are host-independent (deterministic episodes); "
        "samples/sec columns are wall-clock on this host. forget/window "
        "columns use HAND-PICKED lambda / capacity (the forget_lambda / "
        "window_capacity fields); adaptive columns run the in-step "
        "detector on server defaults - it is never told the forget "
        "factor, the window, or that (let alone where) a drift exists. "
        "drift-adaptive-modes rows re-serve the adaptive policy under "
        "step blocking and int8 serving; the 8-device sharded episode is "
        "bitwise the plain one (CI parity tests), so its accuracy is the "
        "plain column",
    ),
    "train_fused": (
        "BENCH_train_fused.json",
        "fused training kernel (no materialized state tensor) vs scan "
        "baseline: truncated-BP grads + population refinement",
        "samples/sec and speedup columns are wall-clock on this host (CI "
        "containers often expose 1-2 cores, flattening memory-bound "
        "wins); the *_hlo_flops/_hlo_mem_bytes and *_temp_alloc_bytes "
        "columns are host-independent - fused_temp_alloc_bytes staying "
        "flat in T while scan_temp_alloc_bytes grows ~linearly is the "
        "O(T*Nx)->O(Nx^2) per-sample activation-memory claim, auditable "
        "per cell",
    ),
    "stream_planner": (
        "BENCH_stream_planner.json",
        "cost-model planner picks vs measured knob-lattice best",
        "stream-planner rows measure every config of the knob lattice "
        "(round-robin best-of-reps) and record the calibrated planner's "
        "pick; ok=false means the pick's MEASURED samples/sec fell more "
        "than the 1.3x gate below the measured best (CI fails on it). "
        "stream-planner-replay rows re-price the tracked "
        "BENCH_stream_quant measurements through the same model - they "
        "validate ranking only, no wall-clock of their own. predicted_* "
        "columns are model outputs: calibrated to this host, never "
        "comparable across hosts",
    ),
}


def _write_bench_json(name, rows) -> None:
    """The tracked perf records (see ``_BENCH_JSON``; the ROADMAP notes
    the perf trajectory was off the record until these files; regenerate
    with ``--only <suite>``)."""
    import json
    import os
    import platform

    fname, unit, note = _BENCH_JSON[name]
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), fname)
    doc = {
        "bench": name,
        "unit": unit,
        "command": f"PYTHONPATH=src python -m benchmarks.run --only {name}",
        "host": {"cores": os.cpu_count(), "machine": platform.machine(),
                 "python": platform.python_version()},
        "note": note,
        "rows": list(rows),
    }
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=2)
        fh.write("\n")
    print(f"# wrote {path}", flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
