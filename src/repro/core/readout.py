"""DFR readout at scale: the paper's online trainer distributed over a mesh.

Lifts the edge system to pods: a frozen LM backbone emits a feature stream
h(k) (B, T, D); a fixed random mask projects it to the Nx-node reservoir; the
modular DFR + DPRR produce r; Ridge sufficient statistics (A, B) are
*associative sums over samples* (paper Eq. 38), so a single ``psum`` over the
data axes makes the online trainer exactly correct under data parallelism -
every pod sees the global (A, B) and solves the same small Cholesky system.

This module is mesh-agnostic: it works inside ``shard_map`` (axis names
present) or single-device (axis_names=()); the launcher wires it to the
production mesh.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import backprop, dprr, masking, reservoir, ridge
from repro.core.types import (DOT_PRECISION, Array, DFRConfig, DFRParams,
                              RidgeState)


def _maybe_psum(x, axis_names: Sequence[str]):
    return jax.lax.psum(x, axis_names) if axis_names else x


@dataclasses.dataclass(frozen=True)
class ReadoutConfig:
    feature_dim: int          # D of the backbone features
    n_classes: int
    n_nodes: int = 30
    nonlinearity: str = "tanh"  # features are unbounded -> saturating f
    alpha: float = 1.0
    mask_seed: int = 0
    dtype: type = jnp.float32

    def dfr(self) -> DFRConfig:
        return DFRConfig(
            n_in=self.feature_dim,
            n_classes=self.n_classes,
            n_nodes=self.n_nodes,
            nonlinearity=self.nonlinearity,
            alpha=self.alpha,
            mask_seed=self.mask_seed,
        )


class DistributedDFRReadout:
    """Online DFR classification head over frozen backbone features."""

    def __init__(self, cfg: ReadoutConfig, axis_names: Sequence[str] = ()):
        self.cfg = cfg
        self.dfr_cfg = cfg.dfr()
        self.axis_names = tuple(axis_names)
        key = jax.random.PRNGKey(cfg.mask_seed)
        # scale by 1/sqrt(D): keeps the masked projection O(1) for
        # unit-variance features regardless of backbone width
        self.mask = masking.make_mask(key, cfg.n_nodes, cfg.feature_dim, cfg.dtype)
        self.mask = self.mask / jnp.sqrt(jnp.asarray(cfg.feature_dim, cfg.dtype))

    def init(self) -> Tuple[DFRParams, RidgeState]:
        return (
            DFRParams.init(self.dfr_cfg),
            RidgeState.zeros(self.dfr_cfg.s, self.cfg.n_classes, self.cfg.dtype),
        )

    # -- pure functions usable inside shard_map -------------------------------

    def features(self, params: DFRParams, h: Array, lengths: Optional[Array] = None) -> Array:
        """h: (B, T, D) backbone features -> r: (B, Nr)."""
        j_seq = masking.apply_mask(self.mask, h.astype(self.cfg.dtype))
        f = self.dfr_cfg.f()
        x = reservoir.run_reservoir(params.p, params.q, j_seq, f=f, lengths=lengths)
        return dprr.compute_dprr(x, lengths=lengths)

    def accumulate(
        self,
        ridge_state: RidgeState,
        params: DFRParams,
        h: Array,
        label: Array,
        lengths: Optional[Array] = None,
    ) -> RidgeState:
        """Accumulate LOCAL (A, B) contributions (no collective yet)."""
        r = self.features(params, h, lengths)
        rt = dprr.r_tilde(r)
        onehot = jax.nn.one_hot(label, self.cfg.n_classes, dtype=self.cfg.dtype)
        A, B = ridge.accumulate_ab(ridge_state.A, ridge_state.B, rt, onehot)
        # B moved without rotating L: invalidate any live factor
        return RidgeState(A=A, B=B, count=ridge_state.count + h.shape[0],
                          Lt=ridge_state.Lt,
                          factor_beta=jnp.zeros_like(ridge_state.factor_beta))

    def solve(
        self, ridge_state: RidgeState, params: DFRParams, beta: Array,
        method: str = "cholesky_blocked",
    ) -> DFRParams:
        """Global Ridge solve: psum the sufficient statistics, then factor.

        The psum is the ONLY collective the readout needs - the paper's
        memory argument (state is O(s^2), independent of stream length)
        becomes a bandwidth argument at scale: s^2 floats per refresh versus
        shipping features.
        """
        A = _maybe_psum(ridge_state.A, self.axis_names)
        B = _maybe_psum(ridge_state.B, self.axis_names)
        Wt = ridge.ridge_solve(A, ridge.regularize(B, beta), method)
        return DFRParams(p=params.p, q=params.q, W=Wt[:, :-1], b=Wt[:, -1])

    def sgd_step(
        self,
        params: DFRParams,
        h: Array,
        label: Array,
        lr_res: Array,
        lr_out: Array,
        lengths: Optional[Array] = None,
    ) -> Tuple[DFRParams, Array]:
        """Truncated-bp SGD step with gradients psum-averaged over the mesh."""
        f = self.dfr_cfg.f()
        j_seq = masking.apply_mask(self.mask, h.astype(self.cfg.dtype))
        onehot = jax.nn.one_hot(label, self.cfg.n_classes, dtype=self.cfg.dtype)
        loss, g = backprop.grads_truncated(params, j_seq, onehot, f, lengths=lengths)
        bsz = jnp.asarray(h.shape[0], self.cfg.dtype)
        loss = _maybe_psum(loss, self.axis_names)
        g = jax.tree_util.tree_map(lambda t: _maybe_psum(t, self.axis_names), g)
        total = _maybe_psum(bsz, self.axis_names)
        inv = 1.0 / total
        new = backprop.apply_sgd(params, g, lr_res, lr_out, inv_batch=inv)
        return new, loss * inv

    def predict(self, params: DFRParams, h: Array, lengths: Optional[Array] = None) -> Array:
        r = self.features(params, h, lengths)
        return jnp.argmax(jnp.matmul(r, params.W.T, precision=DOT_PRECISION) + params.b,
                          axis=-1)
