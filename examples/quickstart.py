"""Quickstart: train a modular DFR classifier end to end (paper pipeline).

    PYTHONPATH=src python examples/quickstart.py [--dataset JPVOW] [--full]
                                                 [--population]

Runs the paper's recipe - truncated-backprop SGD on the two reservoir
parameters (p, q) + output layer, then a Ridge refit via the in-place
Cholesky solver - on a synthetic stand-in of the chosen Table-4 dataset,
and compares against the grid-search baseline (itself a single vmapped
program over all candidates).  ``--population`` additionally runs the
population engine: grid-seeded candidates refined with truncated-BP and
culled by fitness, all population-parallel (repro.core.population).
"""
import argparse
import time

import jax.numpy as jnp

from repro.core import DFRModel, train_population_classification
from repro.core.grid_search import grid_search
from repro.core.types import DFRConfig
from repro.data import PAPER_DATASETS, load


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="JPVOW", choices=sorted(PAPER_DATASETS))
    ap.add_argument("--full", action="store_true", help="full Table-4 sizes")
    ap.add_argument("--nodes", type=int, default=30)
    ap.add_argument("--population", action="store_true",
                    help="also run the population-parallel search engine")
    args = ap.parse_args()

    spec = PAPER_DATASETS[args.dataset]
    train, test = load(args.dataset, size_cap=None if args.full else 120)
    print(f"{args.dataset}: {train.batch} train / {test.batch} test, "
          f"{spec.n_in} channels, {spec.n_classes} classes, "
          f"T in [{spec.t_min}, {spec.t_max}] (synthetic stand-in)")

    cfg = DFRConfig(n_in=spec.n_in, n_classes=spec.n_classes,
                    n_nodes=args.nodes)
    model = DFRModel.create(cfg)

    t0 = time.time()
    params = model.fit(train, minibatch=4)
    bp_t = time.time() - t0
    acc = float(model.accuracy(test, params))
    print(f"[backprop]    test acc {acc:.3f}  ({bp_t:.1f}s)  "
          f"p={float(params.p):.4f} q={float(params.q):.4f}")

    t0 = time.time()
    gs = grid_search(cfg, train, test, divs=4)
    gs_t = time.time() - t0
    print(f"[grid search] test acc {gs['acc']:.3f}  ({gs_t:.1f}s over "
          f"{gs['n_points']} points)  p={gs['p']:.4f} q={gs['q']:.4f}")
    print(f"speed ratio (gs/bp at 4 divisions): {gs_t / bp_t:.1f}x "
          f"(paper protocol grows divisions until accuracy parity; "
          f"see benchmarks/bench_backprop.py)")

    if args.population:
        t0 = time.time()
        divs = 4
        res = train_population_classification(
            cfg, train, test, divs=divs, rounds=2, steps_per_round=2,
            minibatch=4,
        )
        print(f"[population]  test acc {res.best_acc:.3f}  "
              f"({time.time() - t0:.1f}s, {divs * divs} members x "
              f"{len(res.history) - 1} rounds)  "
              f"p={res.best_p:.4f} q={res.best_q:.4f} beta={res.best_beta:g}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
