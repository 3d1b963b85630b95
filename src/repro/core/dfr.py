"""End-to-end DFR classifier: the paper's training recipe (Sec. 4.1).

Pipeline:
  1. SGD with truncated backprop for 25 epochs on (p, q, W, b); LR starts at
     1.0, x0.1 for the reservoir params at epochs {5,10,15,20} and for the
     output params at {10,15,20}.
  2. Re-fit the output layer with Ridge regression; sweep
     beta in {1e-6, 1e-4, 1e-2, 1} and keep the lowest training loss.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backprop, dprr, masking, reservoir, ridge
from repro.core.types import (DOT_PRECISION, Array, DFRConfig, DFRParams,
                              TimeSeriesBatch)


@partial(jax.jit, static_argnames=("cfg", "minibatch"))
def _sgd_epoch(
    cfg: DFRConfig,
    mask: Array,
    params: DFRParams,
    u: Array,
    length: Array,
    onehot: Array,
    lr_res: Array,
    lr_out: Array,
    minibatch: int = 1,
) -> Tuple[DFRParams, Array]:
    """One SGD epoch over a padded dataset, minibatch at a time."""
    f = cfg.f()
    n = u.shape[0] // minibatch * minibatch
    u_b = u[:n].reshape(-1, minibatch, *u.shape[1:])
    len_b = length[:n].reshape(-1, minibatch)
    oh_b = onehot[:n].reshape(-1, minibatch, onehot.shape[-1])

    def step(params, inp):
        ub, lb, ohb = inp
        j_seq = masking.apply_mask(mask, ub)
        loss, g = backprop.grads_truncated(params, j_seq, ohb, f, lengths=lb)
        inv = 1.0 / minibatch
        new = backprop.apply_sgd(params, g, lr_res, lr_out, inv_batch=inv)
        return new, loss * inv

    params, losses = jax.lax.scan(step, params, (u_b, len_b, oh_b))
    return params, jnp.mean(losses)


@dataclasses.dataclass
class DFRModel:
    cfg: DFRConfig
    mask: Array  # (Nx, n_in)

    @classmethod
    def create(cls, cfg: DFRConfig) -> "DFRModel":
        key = jax.random.PRNGKey(cfg.mask_seed)
        return cls(cfg=cfg, mask=masking.make_mask(key, cfg.n_nodes, cfg.n_in, cfg.dtype))

    # -- forward ------------------------------------------------------------

    def mask_inputs(self, u: Array) -> Array:
        return masking.apply_mask(self.mask, u)

    def features(self, batch: TimeSeriesBatch, params: DFRParams) -> Array:
        """DPRR feature vectors r for a batch: (B, Nr)."""
        j_seq = self.mask_inputs(batch.u)
        f = self.cfg.f()
        x = reservoir.run_reservoir(params.p, params.q, j_seq, f=f, lengths=batch.length)
        return dprr.compute_dprr(x, lengths=batch.length)

    def logits(self, batch: TimeSeriesBatch, params: DFRParams) -> Array:
        r = self.features(batch, params)
        return jnp.matmul(r, params.W.T, precision=DOT_PRECISION) + params.b

    def predict(self, batch: TimeSeriesBatch, params: DFRParams) -> Array:
        return jnp.argmax(self.logits(batch, params), axis=-1)

    def accuracy(self, batch: TimeSeriesBatch, params: DFRParams) -> Array:
        return jnp.mean((self.predict(batch, params) == batch.label).astype(jnp.float32))

    # -- SGD with truncated backprop -----------------------------------------

    def _lr_at(self, epoch: int) -> Tuple[float, float]:
        cfg = self.cfg
        lr_res = cfg.lr * (0.1 ** sum(1 for e in cfg.res_lr_drop_epochs if epoch >= e))
        lr_out = cfg.lr * (0.1 ** sum(1 for e in cfg.out_lr_drop_epochs if epoch >= e))
        return lr_res, lr_out

    def _epoch(self, params, u, length, onehot, lr_res, lr_out, minibatch=1):
        return _sgd_epoch(
            self.cfg, self.mask, params, u, length, onehot, lr_res, lr_out, minibatch
        )

    def fit_sgd(
        self,
        train: TimeSeriesBatch,
        params: Optional[DFRParams] = None,
        minibatch: int = 1,
        shuffle_seed: int = 0,
        verbose: bool = False,
    ) -> Tuple[DFRParams, list]:
        cfg = self.cfg
        if params is None:
            params = DFRParams.init(cfg)
        onehot = jax.nn.one_hot(train.label, cfg.n_classes, dtype=cfg.dtype)
        rng = np.random.default_rng(shuffle_seed)
        history = []
        for epoch in range(cfg.epochs):
            lr_res, lr_out = self._lr_at(epoch)
            perm = rng.permutation(train.batch)
            params, loss = self._epoch(
                params,
                train.u[perm],
                train.length[perm],
                onehot[perm],
                jnp.asarray(lr_res, cfg.dtype),
                jnp.asarray(lr_out, cfg.dtype),
                minibatch=minibatch,
            )
            history.append((float(loss), params))
            if verbose:
                print(f"epoch {epoch:3d}  loss {float(loss):.5f}  lr ({lr_res:g},{lr_out:g})")
        return params, history

    # -- Ridge refit of the output layer --------------------------------------

    def fit_ridge(
        self,
        train: TimeSeriesBatch,
        params: DFRParams,
        method: str = "cholesky_blocked",
        chunk: int = 256,
    ) -> DFRParams:
        """Re-train (W, b) with Ridge regression, sweeping beta (paper 4.1)."""
        cfg = self.cfg
        s = cfg.s
        A = jnp.zeros((cfg.n_classes, s), cfg.dtype)
        B = jnp.zeros((s, s), cfg.dtype)
        onehot = jax.nn.one_hot(train.label, cfg.n_classes, dtype=cfg.dtype)
        # stream (A, B) in chunks - the same associative accumulation the
        # edge system performs sample-by-sample (Eq. 38)
        for lo in range(0, train.batch, chunk):
            sub = TimeSeriesBatch(
                u=train.u[lo : lo + chunk],
                length=train.length[lo : lo + chunk],
                label=train.label[lo : lo + chunk],
            )
            r = self.features(sub, params)
            rt = dprr.r_tilde(r)
            A, B = ridge.accumulate_ab(A, B, rt, onehot[lo : lo + chunk])

        best = None
        for beta in cfg.betas:
            Wt = ridge.ridge_solve(A, ridge.regularize(B, jnp.asarray(beta, B.dtype)), method)
            if not bool(jnp.all(jnp.isfinite(Wt))):
                # beta below float32 noise floor of this B: Cholesky/elimination
                # breaks down; the paper's sweep simply moves to the next beta
                continue
            W, b = Wt[:, :-1], Wt[:, -1]
            cand = DFRParams(p=params.p, q=params.q, W=W, b=b)
            logits = self.logits(train, cand)
            loss = float(jnp.mean(backprop.loss_from_logits(logits, onehot)))
            if jnp.isfinite(loss) and (best is None or loss < best[0]):
                best = (loss, cand)
        return best[1] if best is not None else params

    def fit(
        self,
        train: TimeSeriesBatch,
        minibatch: int = 1,
        ridge_method: str = "cholesky_blocked",
        select: str = "val",
        val_fraction: float = 0.25,
        verbose: bool = False,
        seed: int = 0,
    ) -> DFRParams:
        """Truncated-bp SGD then Ridge refit.

        select='final' is the paper's recipe verbatim (keep the last-epoch
        (p, q)).  select='val' (default) additionally holds out
        ``val_fraction`` of the training set and picks the epoch checkpoint
        whose ridge-refit validation accuracy is best, then refits on the
        full training set - a guard for loss landscapes where train CE and
        generalization decouple (observed on the synthetic datasets; see
        DESIGN.md Sec. 9).  All of this cost is charged to 'bp time' in the
        benchmarks.
        """
        if select == "final":
            params, _ = self.fit_sgd(train, minibatch=minibatch, verbose=verbose)
            return self.fit_ridge(train, params, method=ridge_method)
        if select != "val":
            raise ValueError(f"unknown select mode: {select}")
        rng = np.random.default_rng(seed)
        perm = rng.permutation(train.batch)
        n_val = max(1, int(train.batch * val_fraction))
        val_idx, tr_idx = perm[:n_val], perm[n_val:]
        sub = lambda b, idx: TimeSeriesBatch(u=b.u[idx], length=b.length[idx], label=b.label[idx])
        tr, val = sub(train, tr_idx), sub(train, val_idx)
        _, history = self.fit_sgd(tr, minibatch=minibatch, verbose=verbose)
        # evaluate distinct (p, q) checkpoints on the held-out split
        best, seen = None, set()
        for _, ckpt in history:
            key = (round(float(ckpt.p), 6), round(float(ckpt.q), 6))
            if key in seen:
                continue
            seen.add(key)
            fitted = self.fit_ridge(tr, ckpt, method=ridge_method)
            acc = float(self.accuracy(val, fitted))
            if best is None or acc > best[0]:
                best = (acc, ckpt)
        return self.fit_ridge(train, best[1], method=ridge_method)
