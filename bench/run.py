"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names its configuration
(``bench/configs/<config>.json``), its traffic mix (``bench/traffic/
<traffic>.json``, whose ``kind`` picks the driver ``bench/drivers/
<kind>.py``) and its limits (``bench/limits/<workload>.json``).  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window is profiled and the result carries its per-layer
metrics, each read by ``bench/metrics/<metric>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), and last ``checks``, each compared number beside its
limit.  The checks are also the last lines of standard error.  Without a
TPU, or with fewer chips than the cell asks for, the run exits nonzero and
prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

from harness import common, trace  # noqa: E402

TRACE_DIR = BENCH / ".out" / "trace"


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw trace into this directory")
    args = ap.parse_args(argv)

    cell = common.Cell(args.workload)
    devices = common.tpu_devices(cell.chips)
    cache = common.enable_cache()
    common.log(f"cell {cell.name}: {cell.entry['config']} x "
               f"{cell.entry['traffic']} on {len(devices)} x "
               f"{devices[0].device_kind}; seed {args.seed}; compile cache "
               f"{cache}")
    driver = importlib.import_module(f"drivers.{cell.traffic['kind']}")
    tracer = (lambda: trace.capture(TRACE_DIR)) if args.trace else None
    res = driver.run(cell, args.seed, args.seconds, devices, T_PROCESS,
                     tracer=tracer)

    checks = common.judge(res["readings"], cell.limits["limits"])
    device = common.device_record(devices)
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    out = {"correct": all(c["ok"] for c in checks) and res["failed"] == 0,
           "attempted": res["attempted"], "failed": res["failed"]}
    if args.trace:
        path = trace.xplane_file(TRACE_DIR)
        tr = trace.reduce(path)
        if args.keep_trace:
            Path(args.keep_trace).mkdir(parents=True, exist_ok=True)
            shutil.copy(path, args.keep_trace)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = dict(res["ctx"], trace=tr)
        out["metrics"] = common.read_per_layer(cell, ctx)
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        out["device"] = device
        out["breakdown"] = {"device_ops": tr.top_ops(),
                            "idle_gaps": tr.idle_gaps()}
    else:
        names = {m["name"]: m["unit"] for m in cell.end_to_end}
        out["metrics"] = {k: {"value": v, "unit": names[k]}
                          for k, v in res["e2e"].items() if k in names}
        out["device"] = device
    out["checks"] = common.checks_line(checks)
    common.print_checks(checks)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
