"""Backpropagation through the DFR (paper Sec. 3.2-3.5).

Three gradient paths are implemented:

* ``grads_truncated_manual`` - the paper's hand-derived truncated equations
  (Eq. 25-26, 33-36), written exactly as the FPGA datapath computes them.
* ``grads_truncated`` - the same truncated objective expressed with
  ``stop_gradient`` so ``jax.grad`` reproduces Eq. 33-36 (validated
  against the manual path in tests); this is the production batched path.
* ``grads_full_bptt`` - full unrolled backprop through all T steps
  (the expensive reference the truncation approximates; Eq. 29-32).

Loss: softmax cross-entropy (Eq. 24), with dL/dlogits = y - e (Eq. 25).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dprr as dprr_mod
from repro.core import reservoir as res_mod
from repro.core.types import DOT_PRECISION, Array, DFRConfig, DFRParams


class ForwardAux(NamedTuple):
    logits: Array     # (..., Ny)
    probs: Array      # (..., Ny)
    r: Array          # (..., Nr)
    x_last: Array     # (..., Nx)  x(T)
    x_prev: Array     # (..., Nx)  x(T-1)
    j_last: Array     # (..., Nx)  j(T)


def loss_from_logits(logits: Array, onehot: Array) -> Array:
    """Cross-entropy (Eq. 24) with a numerically-safe log-softmax."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(onehot * logp, axis=-1)


def loss_mse(logits: Array, targets: Array) -> Array:
    """Squared-error loss for regression readouts (population engine).

    0.5 * ||logits - targets||^2 per sample, so dL/dlogits = logits - targets
    mirrors the cross-entropy case's (probs - onehot) in Eq. 25 and the same
    truncated-BP machinery applies unchanged.
    """
    d = logits - targets
    return 0.5 * jnp.sum(d * d, axis=-1)


def forward(
    params: DFRParams,
    j_seq: Array,
    f: Callable[[Array], Array],
    lengths: Optional[Array] = None,
) -> ForwardAux:
    """Full forward pass: reservoir -> DPRR -> output layer.

    j_seq: (T, Nx) or (B, T, Nx) masked inputs.
    """
    batched = j_seq.ndim == 3
    x = res_mod.run_reservoir(params.p, params.q, j_seq, f=f, lengths=lengths)
    r = dprr_mod.compute_dprr(x, lengths=lengths)
    logits = jnp.matmul(r, params.W.T, precision=DOT_PRECISION) + params.b
    probs = jax.nn.softmax(logits, axis=-1)
    # gather x(T), x(T-1), j(T) (with variable lengths, T = lengths per row)
    if lengths is None:
        x_last = x[..., -1, :]
        x_prev0 = dprr_mod.shifted_states(x)
        x_prev = x_prev0[..., -1, :]
        j_last = j_seq[..., -1, :]
    else:
        idx_last = jnp.maximum(lengths - 1, 0)
        idx_prev = lengths - 2  # may be -1 -> x(0) = 0 handled below
        if batched:
            barange = jnp.arange(x.shape[0])
            x_last = x[barange, idx_last]
            x_prev = jnp.where(
                (idx_prev >= 0)[:, None], x[barange, jnp.maximum(idx_prev, 0)], 0.0
            )
            j_last = j_seq[barange, idx_last]
        else:
            x_last = x[idx_last]
            x_prev = jnp.where(idx_prev >= 0, x[jnp.maximum(idx_prev, 0)], 0.0)
            j_last = j_seq[idx_last]
    return ForwardAux(logits, probs, r, x_last, x_prev, j_last)


# ---------------------------------------------------------------------------
# Manual truncated backprop: Eq. (25)-(26) + (33)-(36), verbatim.
# ---------------------------------------------------------------------------


def grads_truncated_manual(
    params: DFRParams,
    j_seq: Array,
    onehot: Array,
    f: Callable[[Array], Array],
    f_prime: Callable[[Array], Array],
    lengths: Optional[Array] = None,
) -> Tuple[Array, DFRParams]:
    """Single-sample (or batched) truncated gradients, paper equations.

    Returns (loss, grads) where grads is a DFRParams pytree; batched inputs
    produce *summed* gradients (divide by batch for the mean).
    """
    aux = forward(params, j_seq, f, lengths)
    n_nodes = aux.x_last.shape[-1]
    n_y = onehot.shape[-1]

    dlogits = aux.probs - onehot                                 # Eq. 25
    batched = j_seq.ndim == 3

    def _sum_b(x):
        return jnp.sum(x, axis=0) if batched else x

    grad_b = _sum_b(dlogits)                                     # Eq. 26
    grad_W = (
        jnp.einsum("bc,br->cr", dlogits, aux.r) if batched
        else jnp.outer(dlogits, aux.r)
    )
    dr = jnp.einsum("cr,...c->...r", params.W, dlogits)          # Eq. 26

    # Eq. 33:  bpv_n = sum_j x(T-1)_j dL/dr_{(n-1)Nx+j} + dL/dr_{Nx^2+n}
    dr_outer = dr[..., : n_nodes * n_nodes].reshape(*dr.shape[:-1], n_nodes, n_nodes)
    dr_sum = dr[..., n_nodes * n_nodes :]
    bpv = jnp.einsum("...nj,...j->...n", dr_outer, aux.x_prev) + dr_sum

    # Eq. 34:  dL/dx(T)_n = bpv_n + q * dL/dx(T)_{n+1}   (n = Nx .. 1)
    # -> reversed first-order linear recurrence; reuse the ring closed form.
    Lq = res_mod.ring_matrix(params.q, n_nodes, bpv.dtype)
    dx = jnp.einsum("nm,...n->...m", Lq, bpv)  # dx_m = sum_{n>=m} q^(n-m) bpv_n

    # Eq. 35:  dL/dp = sum_n f(j(T)_n + x(T-1)_n) dL/dx(T)_n
    f_T = f(aux.j_last + aux.x_prev)
    grad_p = jnp.sum(f_T * dx)

    # Eq. 36:  dL/dq = sum_n x(T)_{n-1} dL/dx(T)_n  (x(T)_0 = x(T-1)_{Nx})
    x_shift = jnp.concatenate(
        [aux.x_prev[..., -1:], aux.x_last[..., :-1]], axis=-1
    )
    grad_q = jnp.sum(x_shift * dx)

    loss = jnp.sum(loss_from_logits(aux.logits, onehot))
    grads = DFRParams(p=grad_p.astype(params.p.dtype),
                      q=grad_q.astype(params.q.dtype),
                      W=grad_W.astype(params.W.dtype),
                      b=grad_b.astype(params.b.dtype))
    return loss, grads


# ---------------------------------------------------------------------------
# Truncated backprop via autodiff of the truncated objective.
#
# The truncation keeps gradient flow ONLY through x(T) (and its within-step
# ring chain) - everything earlier is stop_gradient'ed, exactly matching
# Eq. 33-36 (see tests/test_backprop.py for the numerical identity).
# ---------------------------------------------------------------------------


def truncated_loss_from_aux(
    params: DFRParams,
    aux: ForwardAux,
    onehot: Array,
    f: Callable[[Array], Array],
    loss_fn: Callable[[Array, Array], Array] = loss_from_logits,
) -> Array:
    """Truncated objective from a precomputed forward pass.

    Every use of ``aux`` below is stop_gradient'ed, so gradients flow only
    through the re-derived k = T step and the readout - which is why the
    forward pass can be computed once and shared (e.g. with the serving
    path's infer-before-update, ``repro.core.online.online_serve_step``)
    without changing the gradients at all.
    """
    sg = jax.lax.stop_gradient
    n_nodes = aux.x_last.shape[-1]

    x_prev = sg(aux.x_prev)
    # recompute x(T) with gradient flowing only through (p, q) and the
    # within-step ring chain (Eq. 14 at k = T with x(T-1) detached)
    x_last = res_mod.reservoir_step(params.p, params.q, f, sg(aux.j_last), x_prev)

    # r = sg(prefix) + the k = T contribution, with the x(T-1) pairing frozen
    prev_tilde = jnp.concatenate(
        [x_prev, jnp.ones((*x_prev.shape[:-1], 1), x_prev.dtype)], -1
    )
    contrib_T = jnp.einsum("...i,...j->...ij", x_last, prev_tilde)
    contrib_T_sg = jnp.einsum("...i,...j->...ij", sg(aux.x_last), prev_tilde)
    # gradient-carrying part; its *value* is identically zero, so r keeps the
    # exact forward value while autodiff sees only the k = T contribution
    delta = contrib_T - contrib_T_sg
    delta_outer = delta[..., :, :n_nodes].reshape(*x_last.shape[:-1], -1)
    delta_sum = delta[..., :, n_nodes]
    r = sg(aux.r) + jnp.concatenate([delta_outer, delta_sum], axis=-1)

    logits = jnp.matmul(r, params.W.T, precision=DOT_PRECISION) + params.b
    return jnp.sum(loss_fn(logits, onehot))


def _truncated_loss(
    params: DFRParams,
    j_seq: Array,
    onehot: Array,
    f: Callable[[Array], Array],
    lengths: Optional[Array] = None,
    loss_fn: Callable[[Array, Array], Array] = loss_from_logits,
) -> Array:
    aux = forward(params, j_seq, f, lengths)
    return truncated_loss_from_aux(params, aux, onehot, f, loss_fn)


def grads_truncated_from_aux(
    params: DFRParams,
    aux: ForwardAux,
    onehot: Array,
    f: Callable[[Array], Array],
    loss_fn: Callable[[Array, Array], Array] = loss_from_logits,
) -> Tuple[Array, DFRParams]:
    """Truncated-BP gradients reusing a precomputed forward pass (identical
    to ``grads_truncated`` - the truncation stop_gradients everything the
    forward produced, so sharing it is free)."""
    loss, g = jax.value_and_grad(truncated_loss_from_aux)(
        params, aux, onehot, f, loss_fn
    )
    return loss, g


def grads_truncated(
    params: DFRParams,
    j_seq: Array,
    onehot: Array,
    f: Callable[[Array], Array],
    lengths: Optional[Array] = None,
    loss_fn: Callable[[Array, Array], Array] = loss_from_logits,
) -> Tuple[Array, DFRParams]:
    """Truncated-BP gradients; ``loss_fn`` selects the readout objective
    (cross-entropy default; ``loss_mse`` for regression populations)."""
    loss, g = jax.value_and_grad(_truncated_loss)(
        params, j_seq, onehot, f, lengths, loss_fn
    )
    return loss, g


# ---------------------------------------------------------------------------
# Fused truncated backprop: the production training path.
#
# The forward runs the fused reservoir->DPRR kernel (``kernels.ops.
# train_forward``) that never materializes the state sequence X, and the
# backward is a ``jax.custom_vjp`` implementing Eq. 33-36 in closed form
# from the four emitted tensors (r, x(T), x(T-1), j(T)) - the exact
# quantities the FPGA latches for its truncated update.  Validated against
# both ``grads_truncated_manual`` and the stop_gradient autodiff path in
# tests/test_train_fused.py.
# ---------------------------------------------------------------------------


class _FusedSpec(NamedTuple):
    """Static (hashable) half of the fused forward's signature: the
    nonlinearity plus the kernel dispatch knobs, and the time length the
    backward needs to rebuild j_seq's (identically zero) cotangent."""

    f: Callable[[Array], Array]
    backend: Optional[str]
    chunk_t: Optional[int]
    block_b: int
    t_len: int


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_features(spec, p, q, j_seq, lengths):
    from repro.kernels import ops as kops

    return kops.train_forward(
        j_seq, lengths, p, q, j_seq.shape[-1],
        f=spec.f, block_b=spec.block_b, chunk_t=spec.chunk_t,
        backend=spec.backend,
    )


def _fused_features_fwd(spec, p, q, j_seq, lengths):
    out = _fused_features(spec, p, q, j_seq, lengths)
    r, x_last, x_prev, j_last = out
    # residuals are O(Nx) per sample - X was never materialized, and the
    # backward re-reads nothing else (Table 7's truncated storage words)
    return out, (p, q, x_last, x_prev, j_last, lengths)


def _fused_features_bwd(spec, res, cts):
    p, q, x_last, x_prev, j_last, lengths = res
    # only r's cotangent is honored: the truncation stop_gradients the
    # boundary tensors wherever they are consumed (truncated_loss_from_aux),
    # so their cotangents are identically zero on every training path
    dr = cts[0]
    n_nodes = x_last.shape[-1]

    # Eq. 33:  bpv_n = sum_j x(T-1)_j dL/dr_{(n-1)Nx+j} + dL/dr_{Nx^2+n}
    dr_outer = dr[..., : n_nodes * n_nodes].reshape(
        *dr.shape[:-1], n_nodes, n_nodes
    )
    dr_sum = dr[..., n_nodes * n_nodes:]
    bpv = jnp.einsum("...nj,...j->...n", dr_outer, x_prev) + dr_sum

    # Eq. 34: reversed ring recurrence, closed form via L(q)
    Lq = res_mod.ring_matrix(q, n_nodes, bpv.dtype)
    dx = jnp.einsum("nm,...n->...m", Lq, bpv)

    # Eq. 35 / Eq. 36
    f_T = spec.f(j_last + x_prev)
    grad_p = jnp.sum(f_T * dx).astype(p.dtype)
    x_shift = jnp.concatenate(
        [x_prev[..., -1:], x_last[..., :-1]], axis=-1
    )
    grad_q = jnp.sum(x_shift * dx).astype(q.dtype)

    dj = jnp.zeros(
        (*x_prev.shape[:-1], spec.t_len, n_nodes), x_prev.dtype
    )
    dlen = np.zeros(np.shape(lengths), jax.dtypes.float0)
    return grad_p, grad_q, dj, dlen


_fused_features.defvjp(_fused_features_fwd, _fused_features_bwd)


def forward_fused(
    params: DFRParams,
    j_seq: Array,
    f: Callable[[Array], Array],
    lengths: Optional[Array] = None,
    *,
    backend: Optional[str] = None,
    chunk_t: Optional[int] = None,
    block_b: int = 8,
) -> ForwardAux:
    """``forward`` through the fused no-materialized-X kernel path.

    Same ForwardAux contract (values equal to ``forward`` up to the fp
    reordering of the DPRR reduction); differentiable, with the custom
    truncated VJP - ``jax.grad`` of a loss over its logits/r IS the
    truncated gradient, no stop_gradient machinery needed.
    """
    t_len = j_seq.shape[-2]
    if lengths is None:
        lengths = jnp.full(j_seq.shape[:-2], t_len, jnp.int32)
    spec = _FusedSpec(f, backend, chunk_t, block_b, t_len)
    r, x_last, x_prev, j_last = _fused_features(
        spec, params.p, params.q, j_seq, lengths
    )
    logits = jnp.matmul(r, params.W.T, precision=DOT_PRECISION) + params.b
    probs = jax.nn.softmax(logits, axis=-1)
    return ForwardAux(logits, probs, r, x_last, x_prev, j_last)


def grads_truncated_fused(
    params: DFRParams,
    j_seq: Array,
    onehot: Array,
    f: Callable[[Array], Array],
    lengths: Optional[Array] = None,
    loss_fn: Callable[[Array, Array], Array] = loss_from_logits,
    *,
    backend: Optional[str] = None,
    chunk_t: Optional[int] = None,
    block_b: int = 8,
) -> Tuple[Array, DFRParams]:
    """Truncated-BP gradients through the fused forward (production path).

    Identical contract to ``grads_truncated``; (W, b) gradients flow
    through the readout autodiff while (p, q) come from the closed-form
    custom VJP, so the whole backward is O(Nx^2) work with no scan
    transpose."""

    def _loss(prm):
        aux = forward_fused(
            prm, j_seq, f, lengths,
            backend=backend, chunk_t=chunk_t, block_b=block_b,
        )
        return jnp.sum(loss_fn(aux.logits, onehot))

    return jax.value_and_grad(_loss)(params)


# ---------------------------------------------------------------------------
# Full BPTT (reference; memory grows with T - the cost Eq. 29-32 pay).
# ---------------------------------------------------------------------------


def _full_loss(
    params: DFRParams,
    j_seq: Array,
    onehot: Array,
    f: Callable[[Array], Array],
    lengths: Optional[Array] = None,
    loss_fn: Callable[[Array, Array], Array] = loss_from_logits,
) -> Array:
    aux = forward(params, j_seq, f, lengths)
    return jnp.sum(loss_fn(aux.logits, onehot))


def grads_full_bptt(
    params: DFRParams,
    j_seq: Array,
    onehot: Array,
    f: Callable[[Array], Array],
    lengths: Optional[Array] = None,
    loss_fn: Callable[[Array, Array], Array] = loss_from_logits,
) -> Tuple[Array, DFRParams]:
    loss, g = jax.value_and_grad(_full_loss)(
        params, j_seq, onehot, f, lengths, loss_fn
    )
    return loss, g


# ---------------------------------------------------------------------------
# SGD update rule shared by the offline/online/distributed trainers.
#
# Two guards are added on top of the paper's plain SGD (noted in DESIGN.md):
# global-norm gradient clipping, and clamping (p, q) to the paper's own
# grid-search ranges (p in [10^-3.75, 10^-0.25], q in [10^-2.75, 10^-0.25]).
# Without them lr = 1.0 can push q past the reservoir's stability edge where
# states grow as q^T and the loss overflows; the clamp box is exactly the
# region the paper itself declares to "cover the optimal parameters".
# ---------------------------------------------------------------------------

P_RANGE = (10.0 ** -3.75, 10.0 ** -0.25)
Q_RANGE = (10.0 ** -2.75, 10.0 ** -0.25)


def clip_by_global_norm(grads: DFRParams, max_norm: float) -> DFRParams:
    """Clip the reservoir grads (p, q) and output grads (W, b) as two
    independent groups, so a large output-layer gradient cannot mute the
    two-scalar reservoir gradient (and vice versa)."""

    def _clip(leaves):
        # norm accumulates in f32 for range, but the scale is applied in
        # the grads' own dtype: a low-precision config (bf16) must not be
        # silently promoted here - the f32 scale would infect the grads,
        # then the params, then the reservoir scan carry
        gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in leaves))
        scale = jnp.minimum(1.0, max_norm / (gnorm + 1e-12))
        return scale.astype(leaves[0].dtype)

    s_res = _clip([grads.p, grads.q])
    s_out = _clip([grads.W, grads.b])
    return DFRParams(p=grads.p * s_res, q=grads.q * s_res,
                     W=grads.W * s_out, b=grads.b * s_out)


def apply_sgd(
    params: DFRParams,
    grads: DFRParams,
    lr_res: Array,
    lr_out: Array,
    inv_batch: float | Array = 1.0,
    grad_clip: float = 1.0,
    clamp_pq: bool = True,
) -> DFRParams:
    g = jax.tree_util.tree_map(lambda t: t * inv_batch, grads)
    if grad_clip is not None:
        g = clip_by_global_norm(g, grad_clip)
    p = params.p - lr_res * g.p
    q = params.q - lr_res * g.q
    if clamp_pq:
        p = jnp.clip(p, *P_RANGE)
        q = jnp.clip(q, *Q_RANGE)
    return DFRParams(
        p=p,
        q=q,
        W=params.W - lr_out * g.W,
        b=params.b - lr_out * g.b,
    )


# ---------------------------------------------------------------------------
# Storage accounting for the truncation (paper Table 7).
# ---------------------------------------------------------------------------


def storage_words_naive(cfg: DFRConfig, t_len: int) -> int:
    """(T+1) reservoir states + reservoir representation + output weights."""
    return (t_len + 1) * cfg.n_nodes + cfg.n_rep + cfg.n_classes * (cfg.n_rep + 1)


def storage_words_truncated(cfg: DFRConfig, t_len: int) -> int:
    """Only x(T-1), x(T) are kept (+ representation + output weights)."""
    del t_len
    return 2 * cfg.n_nodes + cfg.n_rep + cfg.n_classes * (cfg.n_rep + 1)
