"""Grid search over (p, q, beta) - the paper's baseline optimizer (Sec. 4.1).

Ranges (paper): p in [10^-3.75, 10^-0.25], q in [10^-2.75, 10^-0.25],
divided into ``divs`` equidistant points in log space simultaneously; beta is
swept over the same four values as the proposed method.

``grid_search`` is now a thin compatibility shim over the vmapped population
engine (``repro.core.population``): all K = divs^2 candidates run through the
reservoir -> DPRR -> batched-ridge pipeline in ONE jitted program instead of
a per-candidate Python loop.  The original per-candidate implementation is
kept as ``grid_search_serial`` - it is the honest serial baseline the
population engine's throughput is benchmarked against
(``benchmarks/bench_population.py``), and the oracle its ranking is tested
against (``tests/test_population.py``).
"""
from __future__ import annotations

import itertools
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backprop, dprr, masking, population, reservoir, ridge
from repro.core.population import grid_points  # noqa: F401  (compat re-export)
from repro.core.types import (DOT_PRECISION, Array, DFRConfig, DFRParams,
                              TimeSeriesBatch)


def _eval_pq(
    cfg: DFRConfig,
    mask: Array,
    p: Array,
    q: Array,
    train: TimeSeriesBatch,
    test: TimeSeriesBatch,
    betas: Tuple[float, ...],
) -> Tuple[Array, Array]:
    """Accuracy (test) and loss (train) for one (p, q) across all betas."""
    f = cfg.f()

    def feats(batch: TimeSeriesBatch) -> Array:
        j_seq = masking.apply_mask(mask, batch.u)
        x = reservoir.run_reservoir(p, q, j_seq, f=f, lengths=batch.length)
        return dprr.compute_dprr(x, lengths=batch.length)

    r_train = feats(train)
    r_test = feats(test)
    rt = dprr.r_tilde(r_train)
    onehot = jax.nn.one_hot(train.label, cfg.n_classes, dtype=cfg.dtype)
    A = jnp.einsum("bc,bs->cs", onehot, rt, precision=DOT_PRECISION)
    B = jnp.einsum("bs,bt->st", rt, rt, precision=DOT_PRECISION)

    accs, losses = [], []
    for beta in betas:
        Wt = ridge.ridge_cholesky_blocked(A, ridge.regularize(B, jnp.asarray(beta, B.dtype)))
        W, b = Wt[:, :-1], Wt[:, -1]
        logits_test = jnp.matmul(r_test, W.T, precision=DOT_PRECISION) + b
        acc = jnp.mean((jnp.argmax(logits_test, -1) == test.label).astype(jnp.float32))
        logits_train = jnp.matmul(r_train, W.T, precision=DOT_PRECISION) + b
        loss = jnp.mean(backprop.loss_from_logits(
            logits_train, jax.nn.one_hot(train.label, cfg.n_classes, dtype=cfg.dtype)))
        accs.append(acc)
        losses.append(loss)
    return jnp.stack(accs), jnp.stack(losses)


def grid_search_serial(
    cfg: DFRConfig,
    train: TimeSeriesBatch,
    test: TimeSeriesBatch,
    divs: int,
    p_range: Tuple[float, float] = population.P_LOG_RANGE,
    q_range: Tuple[float, float] = population.Q_LOG_RANGE,
    mask: Optional[Array] = None,
) -> dict:
    """Per-candidate serial sweep (one jitted eval per grid point).

    The pre-population-engine implementation, retained as the benchmark
    baseline and ranking oracle.  Returns the same dict as ``grid_search``.
    """
    if mask is None:
        mask = masking.make_mask(jax.random.PRNGKey(cfg.mask_seed), cfg.n_nodes, cfg.n_in, cfg.dtype)
    ps = grid_points(divs, *p_range)
    qs = grid_points(divs, *q_range)

    t0 = time.perf_counter()
    eval_j = jax.jit(lambda p, q: _eval_pq(cfg, mask, p, q, train, test, cfg.betas))
    best = {"acc": -1.0, "p": None, "q": None, "beta": None}
    for p, q in itertools.product(ps, qs):
        accs, _ = eval_j(jnp.asarray(p, cfg.dtype), jnp.asarray(q, cfg.dtype))
        accs = np.asarray(accs)
        bi = int(np.argmax(accs))
        if accs[bi] > best["acc"]:
            best = {"acc": float(accs[bi]), "p": float(p), "q": float(q),
                    "beta": float(cfg.betas[bi])}
    best["time_s"] = time.perf_counter() - t0
    best["n_points"] = len(ps) * len(qs) * len(cfg.betas)
    return best


def grid_search(
    cfg: DFRConfig,
    train: TimeSeriesBatch,
    test: TimeSeriesBatch,
    divs: int,
    p_range: Tuple[float, float] = population.P_LOG_RANGE,
    q_range: Tuple[float, float] = population.Q_LOG_RANGE,
    mask: Optional[Array] = None,
) -> dict:
    """Full (p, q, beta) grid sweep; returns best accuracy + params + timing.

    Thin shim over ``population.evaluate_population`` with zero refinement:
    the whole sweep is one vmapped program.  Candidate order, accuracy
    selection, and first-best tie-breaking match ``grid_search_serial``.
    """
    if mask is None:
        mask = masking.make_mask(jax.random.PRNGKey(cfg.mask_seed), cfg.n_nodes, cfg.n_in, cfg.dtype)

    t0 = time.perf_counter()
    ps, qs = population.grid_candidates(divs, p_range, q_range, cfg.dtype)
    y_tr = jax.nn.one_hot(train.label, cfg.n_classes, dtype=cfg.dtype)
    y_ev = jax.nn.one_hot(test.label, cfg.n_classes, dtype=cfg.dtype)
    # solver='primal' uses the serial sweep's formulation (factor the (s, s)
    # normal matrix per beta), so rankings agree wherever that factorization
    # is numerically healthy; in float32-degenerate cells (beta below the
    # noise floor of a rank-deficient B) both paths produce garbage, and not
    # necessarily the same garbage
    ev = population.evaluate_population(
        cfg, mask, ps, qs, train.u, train.length, y_tr,
        test.u, test.length, y_ev, select="acc", solver="primal",
    )
    accs = np.asarray(ev.acc)
    bi = int(np.argmax(accs))  # product order + first-max == serial tie-break
    return {
        "acc": float(accs[bi]),
        "p": float(ps[bi]),
        "q": float(qs[bi]),
        "beta": float(cfg.betas[int(ev.beta_idx[bi])]),
        "time_s": time.perf_counter() - t0,
        "n_points": int(ps.shape[0]) * len(cfg.betas),
    }


def grid_search_until(
    cfg: DFRConfig,
    train: TimeSeriesBatch,
    test: TimeSeriesBatch,
    target_acc: float,
    max_divs: int = 20,
) -> dict:
    """Paper protocol: increase divisions from 1 until matching target_acc."""
    total_t = 0.0
    out = None
    for divs in range(1, max_divs + 1):
        out = grid_search(cfg, train, test, divs)
        total_t += out["time_s"]
        out["divs"] = divs
        out["total_time_s"] = total_t
        if out["acc"] >= target_acc - 1e-9:
            return out
    return out
