"""Readings behind the benchmark's limits, many runs in one process (one
compile, one chip).

    python3 bench/calibrate.py readings --workload <name> --seeds 1,2,3 \
        --seconds 5 [--control | --fault <name>]

``readings`` runs the cell once per seed (a fresh server or job loop each
time) and prints, per seed, the numbers ``correct`` compares and, with
``--control``, the same numbers of the control: the reference computed
at the precision below the configuration's.  With ``--fault`` the
program runs with that fault planted under the timed path
(``harness.faults``).  The limits in ``bench/limits/`` are set from these
(``PERF.md`` gives the readings).  One JSON line per run goes to standard
output.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

from harness import common, faults  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("readings",))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=faults.FAULTS, default=None)
    args = ap.parse_args(argv)

    cell = common.Cell(args.workload)
    devices = common.tpu_devices(cell.chips)
    common.enable_cache()
    driver = importlib.import_module(f"drivers.{cell.traffic['kind']}")
    plant = (faults.planted(args.fault, cell.config["model"]["n_classes"])
             if args.fault else contextlib.nullcontext())
    with plant:
        for seed in (int(s) for s in args.seeds.split(",")):
            res = driver.run(cell, seed, args.seconds, devices,
                             time.perf_counter(), control=args.control)
            checks = common.judge(res["readings"], cell.limits["limits"])
            print(json.dumps({
                "seed": seed, "fault": args.fault,
                "correct": all(c["ok"] for c in checks)
                and res["failed"] == 0, "readings": res["readings"],
                "control": res["control"], "e2e": res["e2e"],
                "memory_peak_bytes": res["memory_peak_bytes"]}),
                flush=True)


if __name__ == "__main__":
    main()
