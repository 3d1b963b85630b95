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
                         "population,train_fused,roofline")
    args = ap.parse_args()

    from benchmarks import (bench_backprop, bench_population, bench_ridge,
                            bench_system, bench_truncation, roofline)

    suites = {
        "ridge": lambda: bench_ridge.run(args.full),
        "backprop": lambda: bench_backprop.run(args.full),
        "truncation": lambda: bench_truncation.run(args.full),
        "system": lambda: bench_system.run(args.full),
        "population": lambda: bench_population.run(args.full),
        "train_fused": lambda: bench_backprop.run_train_fused(args.full),
        "roofline": lambda: roofline.summary_csv(),
    }
    selected = (args.only.split(",") if args.only else list(suites))

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
# BENCH_*.json contract: (filename, unit, honest caveat).
_BENCH_JSON = {
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
