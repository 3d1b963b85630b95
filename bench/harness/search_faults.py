"""Faults planted in the population search, each of which the search
cell's check has to catch (``correct`` false): the check of the check.

* ``refine_skipped``: the refinement returns the population it was given;
* ``half_epoch``: the refinement sees only the first half of the epoch's
  minibatches;
* ``half_eval``: the evaluation scores the members on the first half of
  the eval split;
* ``ridge_beta_x10``: the evaluation's ridge adds ten times each beta of
  the sweep to the Gram's diagonal, and reports the sweep's own betas;
* ``readouts_reversed``: the cull hands its survivors their readouts in
  reverse rank order, the best survivor the worst one's (W, b).

Each replaces a function of ``repro.core.population`` that the round
driver looks up when it runs.  ``bench/tests/test_search.py`` reads each
on the CPU at a tiny size; on the chip, one run per fault and seed:

    python3 bench/harness/search_faults.py --seeds 1,2,3 [--faults a,b]

prints one JSON line per run: the fault, the seed, the readings and
whether the cell's check read ``correct``.  The run has no warm-up job
(its numbers are not timings) and a window of one job.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math

FAULTS = ("refine_skipped", "half_epoch", "half_eval", "ridge_beta_x10",
          "readouts_reversed")


@contextlib.contextmanager
def planted(name: str):
    """Plant the named fault for the duration of the block; the traced
    programs are dropped on the way in and out."""
    import jax
    import jax.numpy as jnp
    from repro.core import population as pop_mod

    jax.clear_caches()
    saved = {k: getattr(pop_mod, k) for k in
             ("refine_population", "evaluate_population", "cull_population")}
    refine, evaluate = saved["refine_population"], saved["evaluate_population"]
    cull = saved["cull_population"]

    if name == "refine_skipped":
        def skipped(cfg, mask, pop, *args, **kw):
            return pop, jnp.zeros(pop.p.shape, pop.p.dtype)
        pop_mod.refine_population = skipped
    elif name == "half_epoch":
        def half(cfg, mask, pop, u, lengths, y, *args, minibatch=4, **kw):
            n = u.shape[0] // minibatch // 2 * minibatch
            return refine(cfg, mask, pop, u[:n], lengths[:n], y[:n], *args,
                          minibatch=minibatch, **kw)
        pop_mod.refine_population = half
    elif name == "half_eval":
        def half_eval(cfg, mask, ps, qs, tu, tl, ty, eu, el, ey, **kw):
            n = eu.shape[0] // 2
            return evaluate(cfg, mask, ps, qs, tu, tl, ty, eu[:n], el[:n],
                            ey[:n], **kw)
        pop_mod.evaluate_population = half_eval
    elif name == "ridge_beta_x10":
        def scaled(cfg, *args, **kw):
            cfg = dataclasses.replace(
                cfg, betas=tuple(10.0 * b for b in cfg.betas))
            return evaluate(cfg, *args, **kw)
        pop_mod.evaluate_population = scaled
    elif name == "readouts_reversed":
        def reversed_readouts(pop, fitness, key, survive_frac=0.5, **kw):
            out = cull(pop, fitness, key, survive_frac=survive_frac, **kw)
            k = out.p.shape[0]
            n = max(1, min(k, math.ceil(k * survive_frac)))
            return dataclasses.replace(
                out, W=out.W.at[:n].set(out.W[:n][::-1]),
                b=out.b.at[:n].set(out.b[:n][::-1]))
        pop_mod.cull_population = reversed_readouts
    else:
        raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
    try:
        yield
    finally:
        for k, v in saved.items():
            setattr(pop_mod, k, v)
        jax.clear_caches()


def main(argv=None) -> None:
    import argparse
    import copy
    import json
    import sys
    import time
    from pathlib import Path

    bench = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(bench.parent / "src"))
    sys.path.insert(0, str(bench))
    from drivers import search
    from harness import common

    ap = argparse.ArgumentParser(description="each planted fault of the "
                                 "population search, read on the chip")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--faults", default=",".join(FAULTS))
    args = ap.parse_args(argv)

    cell = copy.deepcopy(common.Cell("net-search"))
    cell.traffic["warmup_jobs"] = 0
    devices = common.tpu_devices(cell.chips)
    common.enable_cache()
    for fault in args.faults.split(","):
        with planted(fault):
            for seed in (int(s) for s in args.seeds.split(",")):
                res = search.run(cell, seed, 0.0, devices,
                                 time.perf_counter())
                checks = common.judge(res["readings"],
                                      cell.limits["limits"])
                print(json.dumps({
                    "fault": fault, "seed": seed,
                    "correct": all(c["ok"] for c in checks)
                    and res["failed"] == 0, "readings": res["readings"]}),
                    flush=True)


if __name__ == "__main__":
    main()
