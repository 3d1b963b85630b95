"""What every cell shares: the cell's files, the chip check, the compile
cache, compile counting, memory, and the result line."""
from __future__ import annotations

import importlib.util
import json
import math
import sys
from pathlib import Path
from typing import Callable, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def log(msg: str) -> None:
    """Progress lines go to standard error; standard output carries only
    the result line."""
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


class Cell:
    """One entry of ``workloads`` with its configuration, traffic mix,
    limits and metrics, all found by name."""

    def __init__(self, workload: str):
        bm = load_json(ROOT / "BENCHMARK.json")
        cells = {w["name"]: w for w in bm["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; "
                             f"known: {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in bm["configs"]}[self.entry["config"]]
        self.config = load_json(ROOT / cfg_entry["file"])
        self.traffic = load_json(BENCH / "traffic" / f"{self.entry['traffic']}.json")
        self.limits = load_json(BENCH / "limits" / f"{workload}.json")
        self.end_to_end = [m for m in bm["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        self.per_layer = [m for m in bm["per_layer"]
                          if workload in m.get("workloads", [workload])]


def tpu_devices(chips: int):
    """The first ``chips`` TPU devices; anything else ends the run with no
    result."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX found {devices[0].platform}")
        raise SystemExit(1)
    if len(devices) < chips:
        log(f"{chips} chips asked, {len(devices)} found")
        raise SystemExit(1)
    return devices[:chips]


def enable_cache() -> str:
    """The program's persistent compile cache (inside the checkout unless
    ``JAX_COMPILATION_CACHE_DIR`` names one), keeping every program so
    that a second run of a cell compiles nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Counts traces and backend compiles while ``active``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self.active and event in self.EVENTS:
            self.count += 1


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def device_record(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def load_reader(name: str) -> Callable:
    """The per-layer metric reader ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(cell: Cell, ctx: dict) -> Dict[str, dict]:
    """Every per-layer metric of the cell.  Each is declared for the cell,
    so a reader that finds nothing to read means the trace or the driver's
    counts are not what the reader expects: that ends the run."""
    out, missing = {}, []
    for m in cell.per_layer:
        value = load_reader(m["name"])(ctx)
        if value is None or not math.isfinite(value):
            missing.append(m["name"])
        else:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    if missing:
        raise RuntimeError(f"per-layer metrics read nothing: {missing}")
    return out


def checks_line(checks: List[dict]) -> dict:
    """{name: {"value": v, "limit": l}} of every compared number."""
    return {c["name"]: {"value": c["value"], "limit": c["limit"]}
            for c in checks}


def print_checks(checks: List[dict]) -> None:
    for c in checks:
        log(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}) "
            f"{'ok' if c['ok'] else 'FAILED'}")


def judge(values: Dict[str, float], limits: Dict[str, float]) -> List[dict]:
    """Each compared number against its limit: a reading at or below the
    limit passes; a missing or non-finite reading fails."""
    out = []
    for name, limit in limits.items():
        v = values.get(name, math.nan)
        ok = v is not None and math.isfinite(v) and v <= limit
        out.append({"name": name, "value": v, "limit": limit, "ok": bool(ok)})
    return out
