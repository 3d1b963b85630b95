"""Population-parallel DFR hyperparameter engine.

The paper replaces offline grid search with single-start truncated-BP
gradient descent on (p, q); its companion work (arXiv:2504.12363) shows the
loss landscape is multi-modal, so a single start can land in a poor basin.
This module runs an entire *population* of K candidates concurrently through
the reservoir -> DPRR -> truncated-BP pipeline as one vmapped/jitted XLA
program:

  1. ``grid_candidates``       - grid-seeded (p, q) starts (the paper's own
                                 log-space search box, Sec. 4.1).
  2. ``evaluate_population``   - one jitted program: vmapped reservoir+DPRR
                                 features (the fused training forward's r,
                                 ``kernels.ops.train_forward``),
                                 population-axis sufficient
                                 statistics A (K, Ny, s) / B (K, s, s),
                                 batched packed ridge solves over the beta
                                 sweep (``ridge.ridge_solve_batched``; the
                                 Pallas tile driver is
                                 ``kernels.ridge_solve.ridge_solve_blocked_batched``),
                                 and per-member NRMSE / accuracy on a held-out
                                 split.
  3. ``refine_population``     - per-member truncated-BP SGD
                                 (``backprop.grads_truncated``), vmapped over
                                 the population, scanned over minibatches.
  4. ``cull_population``       - NRMSE-ranked selection: survivors keep their
                                 parameters, culled slots are re-seeded with
                                 log-space-jittered clones of the survivors
                                 (the seeding/culling primitives live in
                                 ``repro.core.candidates``, shared with the
                                 online ensemble; re-exported here).
  5. ``train_population``      - the round driver (evaluate -> cull ->
                                 refine -> evaluate), with elitist tracking:
                                 the best member ever evaluated is returned,
                                 so the result is never worse than the best
                                 grid seed.

Fitness is NRMSE of the ridge-refit readout on the evaluation split:
``sqrt(mean((pred - y)^2) / var(y))``.  For classification the targets are
one-hot rows (NRMSE then tracks the Brier-style readout error) and accuracy
is also computed; ``select='acc'`` reproduces the serial grid-search ranking
exactly when refinement is disabled (``repro.core.grid_search`` is now a thin
shim over this path).

Shapes: every population tensor carries a leading K axis; ``DFRParams`` is
reused as the population pytree with leaves p (K,), q (K,), W (K, Ny, Nr),
b (K, Ny).  Memory in ``evaluate_population`` scales as K * B * s for the
feature matrices - size the population to the accelerator accordingly.

The round driver records host spans on the profiler's clock
(``repro.runtime.tracing``): ``search.evaluate``, ``search.select``,
``search.refine`` and ``search.round``; the programs carry the op scopes
``search.features``, ``search.gram``, ``search.solve``, ``search.predict``
and ``search.sgd``.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backprop, dprr, masking, ridge
from repro.core.candidates import (  # noqa: F401  (shared candidate machinery,
    P_LOG_RANGE,                     # re-exported for compatibility - the
    Q_LOG_RANGE,                     # online ensemble imports the same
    cull_population,                 # primitives from repro.core.candidates)
    grid_candidates,
    grid_points,
    init_population,
)
from repro.core.types import (
    DOT_PRECISION,
    Array,
    DFRConfig,
    DFRParams,
    RegressionBatch,
    TimeSeriesBatch,
)
from repro.kernels import ops as kops
from repro.runtime import tracing


# ---------------------------------------------------------------------------
# Vmapped evaluation: features -> batched ridge -> NRMSE/accuracy
# ---------------------------------------------------------------------------


class PopulationEval(NamedTuple):
    """Per-member evaluation at each member's best beta."""

    nrmse: Array      # (K,) eval-split NRMSE
    acc: Array        # (K,) eval-split argmax accuracy (degenerate for Ny=1)
    beta_idx: Array   # (K,) int32 index into cfg.betas
    Wt: Array         # (K, Ny, s) ridge readout [W | b]
    nrmse_all: Array  # (K, n_beta) full sweep (diagnostics / shim)
    acc_all: Array    # (K, n_beta)


@partial(jax.jit, static_argnames=("cfg", "select", "ridge_method", "solver"))
def evaluate_population(
    cfg: DFRConfig,
    mask: Array,
    ps: Array,
    qs: Array,
    train_u: Array,
    train_len: Array,
    y_train: Array,
    eval_u: Array,
    eval_len: Array,
    y_eval: Array,
    select: str = "nrmse",
    ridge_method: str = "cholesky_blocked",
    solver: str = "auto",
) -> PopulationEval:
    """Evaluate K (p, q) candidates in one XLA program.

    y_train: (B, Ny) targets (one-hot rows for classification);
    y_eval: (Be, Ny).  ``select`` picks each member's beta by 'nrmse'
    (lower wins) or 'acc' (higher wins; serial-grid-search-compatible,
    first-best tie-break in cfg.betas order).

    ``solver`` chooses the ridge formulation:
      * 'primal' - per-beta batched Cholesky of B = R~^T R~ + beta I
        (s, s); the serial grid search's formulation, so rankings agree
        with it wherever the factorization is numerically healthy (when
        beta sits below the float32 noise floor both produce garbage, not
        necessarily the same garbage).
      * 'dual'   - kernel form W~ = Y^T (R~ R~^T + beta I)^{-1} R~ with ONE
        batched factorization over the whole (beta, member) sweep.  Exact
        same solution when B samples >= rank, far better conditioned and
        much cheaper when B < s (the search regime), since the factored
        system is (B, B) instead of (s, s).
      * 'auto'   - 'dual' when the train split has fewer samples than s.
    """
    f = cfg.f()

    def feats(p, q, u, lengths):
        # the fused training forward's r: the state sequence is never
        # materialized (K x B x T x Nx floats would not fit the device at
        # the paper's Table-4 lengths)
        j_seq = masking.apply_mask(mask, u)
        return kops.train_forward(j_seq, lengths, p, q, cfg.n_nodes, f=f)[0]

    vfeats = jax.vmap(feats, in_axes=(0, 0, None, None))
    with jax.named_scope("search.features"):
        rt_train = dprr.r_tilde(vfeats(ps, qs, train_u, train_len))  # (K, B, s)
        rt_eval = dprr.r_tilde(vfeats(ps, qs, eval_u, eval_len))     # (K, Be, s)

    k = rt_train.shape[0]
    n_train, s = rt_train.shape[1], rt_train.shape[2]
    n_beta = len(cfg.betas)
    betas = jnp.asarray(cfg.betas, rt_train.dtype)
    use_dual = solver == "dual" or (solver == "auto" and n_train < s)

    if use_dual:
        # one factorization for the whole (beta, member) sweep
        with jax.named_scope("search.gram"):
            Kmat = jnp.einsum("kbs,kcs->kbc", rt_train, rt_train,
                              precision=DOT_PRECISION)   # (K, B, B)
        with jax.named_scope("search.solve"):
            eye = jnp.eye(n_train, dtype=Kmat.dtype)
            G = Kmat[None] + betas[:, None, None, None] * eye    # (nb, K, B, B)
            C = jnp.linalg.cholesky(G.reshape(n_beta * k, n_train, n_train))
            y_b = jnp.broadcast_to(y_train, (n_beta * k, *y_train.shape))
            X = jax.vmap(
                lambda c, y: jax.scipy.linalg.cho_solve((c, True), y)
            )(C, y_b).reshape(n_beta, k, n_train, -1)
            Wt_all = jnp.einsum("nkby,kbs->nkys", X, rt_train,
                                precision=DOT_PRECISION)  # (nb, K, Ny, s)
    else:
        with jax.named_scope("search.gram"):
            A = jnp.einsum("by,kbs->kys", y_train, rt_train,
                           precision=DOT_PRECISION)
            Bmat = jnp.einsum("kbs,kbt->kst", rt_train, rt_train,
                              precision=DOT_PRECISION)
        with jax.named_scope("search.solve"):
            Wt_all = jnp.stack([
                ridge.ridge_solve_batched(
                    A, ridge.regularize(Bmat, beta.astype(Bmat.dtype)),
                    ridge_method,
                )
                for beta in betas
            ])

    with jax.named_scope("search.predict"):
        pred = jnp.einsum("kbs,nkys->nkby", rt_eval, Wt_all,
                          precision=DOT_PRECISION)       # (nb, K, Be, Ny)
        var = jnp.mean(jnp.square(y_eval - jnp.mean(y_eval))) + 1e-12
        err = pred - y_eval[None, None]
        nrmse = jnp.sqrt(jnp.mean(err * err, axis=(2, 3)) / var)  # (nb, K)
        nrmse = jnp.where(jnp.isfinite(nrmse), nrmse, jnp.inf)
        labels_eval = jnp.argmax(y_eval, axis=-1)
        acc = jnp.mean(
            (jnp.argmax(pred, -1) == labels_eval[None, None]).astype(
                jnp.float32),
            axis=2,
        )                                                        # (nb, K)

    # argmax/argmin keep the earliest beta on ties, matching the serial grid
    # search's argmax semantics over the beta sweep
    beta_idx = (jnp.argmax(acc, 0) if select == "acc"
                else jnp.argmin(nrmse, 0)).astype(jnp.int32)     # (K,)
    arange_k = jnp.arange(k)
    return PopulationEval(
        nrmse=nrmse[beta_idx, arange_k],
        acc=acc[beta_idx, arange_k],
        beta_idx=beta_idx,
        Wt=Wt_all[beta_idx, arange_k],
        nrmse_all=nrmse.T,
        acc_all=acc.T,
    )


# ---------------------------------------------------------------------------
# Vmapped truncated-BP refinement
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg", "steps", "minibatch", "loss", "fused"))
def refine_population(
    cfg: DFRConfig,
    mask: Array,
    pop: DFRParams,
    u: Array,
    lengths: Array,
    y: Array,
    lr_res: Array,
    lr_out: Array,
    steps: int = 1,
    minibatch: int = 4,
    loss: str = "ce",
    fused: bool = True,
) -> Tuple[DFRParams, Array]:
    """``steps`` epochs of truncated-BP SGD on every member concurrently.

    All members see the same minibatch schedule; the member loop is a vmap,
    the minibatch loop a lax.scan - one fused program for the whole
    population.  Returns (refined population, (K,) final-epoch mean loss).

    ``fused=True`` (production default) runs each SGD step through the
    fused reservoir->DPRR forward with the closed-form truncated VJP
    (``backprop.grads_truncated_fused``): the state sequence is never
    materialized and the backward is O(Nx^2).  ``fused=False`` keeps the
    scan + stop_gradient autodiff path (the same gradients up to fp
    reduction order - the benchmark baseline).
    """
    if steps == 0:
        return pop, jnp.zeros(pop.p.shape, pop.p.dtype)
    f = cfg.f()
    loss_fn = backprop.loss_from_logits if loss == "ce" else backprop.loss_mse
    grads = (backprop.grads_truncated_fused if fused
             else backprop.grads_truncated)
    mb = min(minibatch, u.shape[0])
    n = u.shape[0] // mb * mb
    u_b = u[:n].reshape(-1, mb, *u.shape[1:])
    len_b = lengths[:n].reshape(-1, mb)
    y_b = y[:n].reshape(-1, mb, y.shape[-1])

    def member(params_k: DFRParams):
        def sgd_step(params, inp):
            ub, lb, yb = inp
            with jax.named_scope("search.sgd"):
                j_seq = masking.apply_mask(mask, ub)
                l, g = grads(
                    params, j_seq, yb, f, lengths=lb, loss_fn=loss_fn
                )
                new = backprop.apply_sgd(
                    params, g, lr_res, lr_out, inv_batch=1.0 / mb
                )
            return new, l / mb

        def epoch(params, _):
            params, losses = jax.lax.scan(sgd_step, params, (u_b, len_b, y_b))
            return params, jnp.mean(losses)

        params_k, losses = jax.lax.scan(epoch, params_k, None, length=steps)
        return params_k, losses[-1]

    return jax.vmap(member)(pop)


# ---------------------------------------------------------------------------
# Round driver
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PopulationResult:
    """Outcome of a population search (elitist: never worse than the best
    grid seed, because the best member ever evaluated is what's returned)."""

    best_params: DFRParams  # single member; (W, b) are the ridge readout
    best_nrmse: float
    best_acc: float
    best_beta: float
    best_p: float
    best_q: float
    history: List[dict]
    population: DFRParams   # final stacked population
    final_eval: PopulationEval
    time_s: float
    # the population that entered the last refinement (after its cull);
    # None when no round ran
    refined_from: Optional[DFRParams]


def _load_readout(pop: DFRParams, Wt: Array) -> DFRParams:
    """Fold each member's ridge solution into its (W, b) so refinement's SGD
    starts from the solved readout rather than a stale one."""
    return DFRParams(p=pop.p, q=pop.q, W=Wt[..., :-1], b=Wt[..., -1])


def _best_member(pop: DFRParams, ev: PopulationEval, cfg: DFRConfig,
                 select: str) -> dict:
    metric = np.asarray(ev.acc) if select == "acc" else -np.asarray(ev.nrmse)
    bi = int(np.argmax(metric))
    params = DFRParams(
        p=pop.p[bi], q=pop.q[bi],
        W=ev.Wt[bi, :, :-1], b=ev.Wt[bi, :, -1],
    )
    return {
        "metric": float(metric[bi]),
        "params": params,
        "nrmse": float(ev.nrmse[bi]),
        "acc": float(ev.acc[bi]),
        "beta": float(cfg.betas[int(ev.beta_idx[bi])]),
        "p": float(pop.p[bi]),
        "q": float(pop.q[bi]),
    }


def train_population(
    cfg: DFRConfig,
    train_u: Array,
    train_len: Array,
    y_train: Array,
    eval_u: Array,
    eval_len: Array,
    y_eval: Array,
    *,
    divs: int = 4,
    rounds: int = 1,
    steps_per_round: int = 1,
    minibatch: int = 4,
    survive_frac: float = 0.5,
    jitter: float = 0.15,
    task: str = "classification",
    select: Optional[str] = None,
    lr: Optional[float] = None,
    solver: str = "auto",
    p_range: Tuple[float, float] = P_LOG_RANGE,
    q_range: Tuple[float, float] = Q_LOG_RANGE,
    mask: Optional[Array] = None,
    seed: int = 0,
    fused: bool = True,
) -> PopulationResult:
    """Grid-seed K = divs^2 members, then ``rounds`` of (cull -> truncated-BP
    refine -> ridge re-evaluate), returning the best member ever evaluated.

    ``rounds=0`` is a pure vmapped grid search.  The per-round learning rate
    anneals as lr * 0.1^round (the paper's drop schedule compressed to round
    granularity); ``lr`` defaults to cfg.lr for classification and to a
    gentler 0.3 * cfg.lr for regression, where the unnormalized MSE gradient
    runs much hotter than cross-entropy's.  ``fused`` picks the refinement
    forward (``refine_population``): the fused kernel path, or the plain
    scan it is checked against.
    """
    if task not in ("classification", "regression"):
        raise ValueError(f"unknown task: {task}")
    if select is None:
        select = "acc" if task == "classification" else "nrmse"
    loss = "ce" if task == "classification" else "mse"
    if lr is None:
        lr = cfg.lr if task == "classification" else 0.3 * cfg.lr
    if mask is None:
        mask = masking.make_mask(
            jax.random.PRNGKey(cfg.mask_seed), cfg.n_nodes, cfg.n_in, cfg.dtype
        )

    t0 = time.perf_counter()
    ps, qs = grid_candidates(divs, p_range, q_range, cfg.dtype)
    pop = init_population(cfg, ps, qs)
    k = int(pop.p.shape[0])
    key = jax.random.PRNGKey(seed)
    mb = min(minibatch, train_u.shape[0])
    n_sgd = train_u.shape[0] // mb * mb

    def ev_pop(pop):
        # enqueue the evaluation and read what the ranking needs
        with tracing.span("search.evaluate") as sp:
            ev = evaluate_population(
                cfg, mask, pop.p, pop.q, train_u, train_len, y_train,
                eval_u, eval_len, y_eval, select=select, solver=solver,
            )
            jax.block_until_ready((ev.nrmse, ev.acc, ev.beta_idx))
            if tracing.recording():
                sp.set_metadata(**_timestep_stats(
                    k, [train_len, eval_len],
                    [train_u.shape[1], eval_u.shape[1]]))
        return ev

    refined_from = None
    ev = ev_pop(pop)
    with tracing.span("search.select"):
        elite = _best_member(pop, ev, cfg, select)
    history = [{
        "round": 0, "best_nrmse": elite["nrmse"], "best_acc": elite["acc"],
        "mean_nrmse": float(np.mean(np.asarray(ev.nrmse))), "refine_loss": None,
    }]

    for r in range(rounds):
        with tracing.span("search.round", round=r + 1, members=k):
            with tracing.span("search.select"):
                fitness = -ev.acc if select == "acc" else ev.nrmse
                key, kc = jax.random.split(key)
                pop = cull_population(
                    _load_readout(pop, ev.Wt), fitness, kc,
                    survive_frac=survive_frac, jitter=jitter,
                    p_range=p_range, q_range=q_range,
                )
            refined_from = pop
            lr_r = jnp.asarray(lr * (0.1 ** r), cfg.dtype)
            with tracing.span("search.refine") as sp:
                pop, losses = refine_population(
                    cfg, mask, pop, train_u, train_len, y_train, lr_r, lr_r,
                    steps=steps_per_round, minibatch=minibatch, loss=loss,
                    fused=fused,
                )
                refine_loss = float(np.mean(np.asarray(losses)))
                if tracing.recording():
                    batches = np.asarray(train_len)[:n_sgd].reshape(-1, mb)
                    sp.set_metadata(
                        sgd_steps=steps_per_round * (n_sgd // mb),
                        **_timestep_stats(
                            k * steps_per_round, list(batches),
                            [train_u.shape[1]] * len(batches)))
            ev = ev_pop(pop)
            with tracing.span("search.select"):
                cand = _best_member(pop, ev, cfg, select)
                if cand["metric"] > elite["metric"]:
                    elite = cand
            history.append({
                "round": r + 1, "best_nrmse": elite["nrmse"],
                "best_acc": elite["acc"],
                "mean_nrmse": float(np.mean(np.asarray(ev.nrmse))),
                "refine_loss": refine_loss,
            })

    return PopulationResult(
        best_params=elite["params"],
        best_nrmse=elite["nrmse"],
        best_acc=elite["acc"],
        best_beta=elite["beta"],
        best_p=elite["p"],
        best_q=elite["q"],
        history=history,
        population=pop,
        final_eval=ev,
        time_s=time.perf_counter() - t0,
        refined_from=refined_from,
    )


def _timestep_stats(members: int, lengths: List[Array],
                    t_max: List[int]) -> dict:
    """Span stats of a pass of ``members`` over batches with these
    lengths, padded to ``t_max`` steps, each batch one call of the
    training kernel: member-samples, the real time steps, the time steps
    the arrays hold and those the kernel runs (its Pallas layout, after
    the length sort and the dead-chunk skip), and the kernel's accumulator
    folds (one per live block-chunk).  Read on the host: a device op here
    would compile inside a traced window."""
    lens = [np.asarray(x) for x in lengths]
    return {
        "samples": members * sum(x.size for x in lens),
        "real_timesteps": members * sum(int(x.sum()) for x in lens),
        "padded_timesteps": members * sum(
            x.size * t for x, t in zip(lens, t_max)),
        "kernel_timesteps": members * sum(
            kops.train_kernel_timesteps(x, t) for x, t in zip(lens, t_max)),
        "kernel_folds": members * sum(
            kops.train_kernel_folds(x, t) for x, t in zip(lens, t_max)),
    }


# ---------------------------------------------------------------------------
# Batch-type conveniences
# ---------------------------------------------------------------------------


def train_population_classification(
    cfg: DFRConfig,
    train: TimeSeriesBatch,
    evalb: TimeSeriesBatch,
    **kwargs,
) -> PopulationResult:
    """Population search on a labeled batch pair (targets one-hot encoded)."""
    y_tr = jax.nn.one_hot(train.label, cfg.n_classes, dtype=cfg.dtype)
    y_ev = jax.nn.one_hot(evalb.label, cfg.n_classes, dtype=cfg.dtype)
    return train_population(
        cfg, train.u, train.length, y_tr, evalb.u, evalb.length, y_ev,
        task="classification", **kwargs,
    )


def train_population_regression(
    cfg: DFRConfig,
    train: RegressionBatch,
    evalb: RegressionBatch,
    **kwargs,
) -> PopulationResult:
    """Population search on a regression batch pair (NRMSE fitness)."""
    return train_population(
        cfg, train.u, train.length, train.y, evalb.u, evalb.length, evalb.y,
        task="regression", **kwargs,
    )
