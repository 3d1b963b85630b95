"""Plain reference of the population search, one member at a time, written
from the paper and built on ``reference.dfr`` (which it reuses unedited).

arXiv:2504.11970 Sec. 4.1 fits the readout by ridge regression over a beta
sweep and searches (p, q); this repository's search (``core/population.py``)
replaces the grid by truncated-BP SGD on a population.  For one member this
file computes, in float32 with every dot at the named precision
(``reference.dfr.dot``: ``"highest"``, or ``"high"`` for the control):

* ``evaluate``: the readout features r~ = [r, 1] of both splits (Eq. 14 and
  27-28), the dual-form ridge solution for each beta, W~ = Y^T (R~ R~^T +
  beta I)^-1 R~ (Eq. 20-22 in kernel form, one Cholesky factor per beta),
  its predictions on the evaluation split, their NRMSE against the one-hot
  targets, sqrt(mean((pred - y)^2) / var(y)), and accuracy, and the beta
  chosen by accuracy (the first of the sweep on a tie) or by NRMSE;
  ``ridge``, that solution from given features at one beta;
  ``readout_nrmse``, the eval NRMSE of a given readout on those features;
* ``refine_epoch``: one epoch of truncated-BP SGD (Eq. 25-26 and 33-36,
  ``reference.dfr.truncated_grads`` and ``reference.dfr.sgd``).

Departures from the paper, each the program's:

* the epoch visits the train split in its fixed order, in minibatches of
  ``minibatch`` samples, and drops the last ``n % minibatch`` samples, where
  the paper updates sample by sample;
* the learning rate of round r is lr * 0.1^r for (p, q) and (W, b) alike,
  the paper's drop schedule by epoch compressed to whole rounds;
* the gradient groups are clipped and (p, q) clamped to the search box
  (``reference.dfr.sgd``'s guards).

Nothing here imports the program.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from reference import dfr

F32 = jnp.float32


def features(p, q, mask, u, length, f, prec):
    """r~ (B, s) of a batch of samples."""
    j = dfr.dot(u, mask, prec, "bti,ni->btn")
    X = dfr.reservoir(p, q, j, length, f, prec)
    r = dfr.dprr(X, length, prec)
    return jnp.concatenate([r, jnp.ones((r.shape[0], 1), F32)], -1)


@partial(jax.jit, static_argnames=("prec",))
def ridge(rt, y, beta, *, prec):
    """The dual-form ridge readout W~ (Ny, s) of features r~ (B, s) and
    targets y (B, Ny) at one beta: one Cholesky factor of R~ R~^T + beta I."""
    gram = dfr.dot(rt, rt, prec, "bs,cs->bc")
    with jax.default_matmul_precision(prec):
        C = jnp.linalg.cholesky(gram + beta * jnp.eye(rt.shape[0], dtype=F32))
        X = jax.scipy.linalg.cho_solve((C, True), y)
    return dfr.dot(X, rt, prec, "by,bs->ys")


@partial(jax.jit, static_argnames=("model", "betas", "prec"))
def _evaluate(p, q, mask, u_tr, len_tr, y_tr, u_ev, len_ev, y_ev, *,
              model, betas, prec):
    f = dfr.nonlinearity(model[0], model[1])
    rt = features(p, q, mask, u_tr, len_tr, f, prec)
    rte = features(p, q, mask, u_ev, len_ev, f, prec)
    gram = dfr.dot(rt, rt, prec, "bs,cs->bc")
    var = jnp.mean(jnp.square(y_ev - jnp.mean(y_ev))) + 1e-12
    nrmse, acc, Wts = [], [], []
    for beta in betas:
        Wt = ridge(rt, y_tr, beta, prec=prec)
        pred = dfr.dot(rte, Wt, prec, "bs,ys->by")
        err = pred - y_ev
        nrmse.append(jnp.sqrt(jnp.mean(err * err) / var))
        acc.append(jnp.mean((jnp.argmax(pred, -1)
                             == jnp.argmax(y_ev, -1)).astype(F32)))
        Wts.append(Wt)
    return jnp.stack(nrmse), jnp.stack(acc), jnp.stack(Wts), gram, rt


def evaluate(p, q, mask, train, evalb, model: dict, betas, select: str,
             prec: str) -> dict:
    """The member's evaluation over the beta sweep (module docstring).

    ``train`` and ``evalb`` are (u, length, onehot) triples.  Returns numpy
    ``nrmse`` and ``acc`` (one per beta, NaN read as inf), ``beta_idx``
    (the chosen beta), ``Wt`` (n_beta, Ny, s), the readout at each beta,
    ``gram``, the train split's R~ R~^T, and ``rt``, its features R~."""
    nrmse, acc, Wt, gram, rt = _evaluate(
        jnp.asarray(p, F32), jnp.asarray(q, F32), mask, *train, *evalb,
        model=(model["nonlinearity"], float(model["alpha"])),
        betas=tuple(float(b) for b in betas), prec=prec)
    nrmse = np.where(np.isfinite(nrmse), np.asarray(nrmse), np.inf)
    acc = np.asarray(acc)
    # first best on ties, in the sweep's order
    idx = int(np.argmax(acc) if select == "acc" else np.argmin(nrmse))
    return {"nrmse": nrmse, "acc": acc, "beta_idx": idx,
            "Wt": np.asarray(Wt), "gram": np.asarray(gram),
            "rt": np.asarray(rt)}


@partial(jax.jit, static_argnames=("model", "prec"))
def _readout_nrmse(p, q, mask, u_ev, len_ev, y_ev, Wt, *, model, prec):
    f = dfr.nonlinearity(model[0], model[1])
    pred = dfr.dot(features(p, q, mask, u_ev, len_ev, f, prec), Wt, prec,
                   "bs,ys->by")
    var = jnp.mean(jnp.square(y_ev - jnp.mean(y_ev))) + 1e-12
    return jnp.sqrt(jnp.mean(jnp.square(pred - y_ev)) / var)


def readout_nrmse(p, q, mask, evalb, Wt, model: dict, prec: str) -> float:
    """Eval NRMSE of a given readout W~ (Ny, s) on the member's features
    (``evalb`` a (u, length, onehot) triple); inf where not finite."""
    v = float(_readout_nrmse(
        jnp.asarray(p, F32), jnp.asarray(q, F32), mask, *evalb,
        jnp.asarray(Wt, F32),
        model=(model["nonlinearity"], float(model["alpha"])), prec=prec))
    return v if np.isfinite(v) else float("inf")


def condition(gram: np.ndarray, beta: float) -> float:
    """2-norm condition number of gram + beta I, from its eigenvalues in
    float64 (negative ones, rounding of a semi-definite matrix, read as 0);
    inf where the Gram holds a non-finite entry."""
    gram = np.asarray(gram, np.float64)
    if not np.isfinite(gram).all():
        return float("inf")
    lam = np.linalg.eigvalsh(gram)
    return float((max(lam[-1], 0.0) + beta) / (max(lam[0], 0.0) + beta))


def feature_share(gram: np.ndarray) -> float:
    """(tr(R~ R~^T) - n) / n: the features' share of the Gram's diagonal
    against the bias column's ones (inf where the Gram is not finite)."""
    gram = np.asarray(gram, np.float64)
    if not np.isfinite(gram).all():
        return float("inf")
    n = gram.shape[0]
    return float((np.trace(gram) - n) / n)


def refine_epoch_fn(model: dict, train_cfg: dict, minibatch: int, prec: str):
    """jit((p, q, W, b), mask, u, length, onehot, lr -> (p, q, W, b)): one
    epoch of truncated-BP SGD in the program's fixed order, the tail of the
    split past the last whole minibatch dropped."""
    f = dfr.nonlinearity(model["nonlinearity"], model["alpha"])

    @jax.jit
    def run(params, mask, u, length, onehot, lr):
        mb = min(minibatch, u.shape[0])
        n = u.shape[0] // mb * mb
        ub = u[:n].reshape(-1, mb, *u.shape[1:])
        lb = length[:n].reshape(-1, mb)
        yb = onehot[:n].reshape(-1, mb, onehot.shape[-1])
        weight = jnp.ones((mb,), F32)

        def step(prm, batch):
            p, q, W, b = prm
            u_k, len_k, y_k = batch
            aux = dfr.forward(p, q, W, b, mask, u_k, len_k, f, prec)
            g = dfr.truncated_grads(p, q, W, b, aux, y_k, weight, f, prec)
            return dfr.sgd(p, q, W, b, g, lr, 1.0 / mb, train_cfg), None

        out, _ = jax.lax.scan(step, tuple(params), (ub, lb, yb))
        return out

    return run
