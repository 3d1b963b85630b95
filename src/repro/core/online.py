"""Online edge training + inference (paper Sec. 3.1): one fused step.

The paper's system processes a stream sample-by-sample, entirely on-device:

    reservoir forward -> DPRR -> (a) inference: y = W r + b
                               -> (b) training: truncated-bp SGD update of
                                      (p, q, W, b) AND streaming (A, B)
                                      accumulation; the Ridge solve runs
                                      periodically (or on demand) to refresh
                                      the output layer.

Everything below is a single jitted program per step - the TPU analogue of
"everything on the FPGA, no host round trips".

The module is organized as a *functional* core plus thin stateful wrappers:

  * ``online_step`` / ``online_infer`` / ``online_logits`` /
    ``refresh_output`` / ``reset_statistics`` - pure functions over
    ``OnlineState`` pytrees.  All of them vmap cleanly over a leading
    population axis (``OnlineEnsemble``) or a leading slot axis (the
    continuous-batching stream server in ``repro.runtime.stream_server``).
  * ``OnlineDFR``   - the single-stream system (thin jitted wrapper).
  * ``OnlineEnsemble`` - K independent members (jittered (p, q) seeds,
    shared mask) vmapped over the member axis, with online culling /
    re-seeding via the shared candidate machinery in
    ``repro.core.candidates`` - the offline population engine's protocol
    applied to a live serving ensemble.

Scale-out: (A, B) and the parameter grads are associative sums, so
``online_step(axis_names=...)`` psums them across the data axes
(``repro.distributed.sharding.data_axes()``) for data-parallel streams, and
the ensemble's member axis shards across devices via the ``member`` logical
axis in the sharding rule table (members are embarrassingly parallel).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import backprop, candidates, dprr, masking, reservoir, ridge
from repro.core.types import (DOT_PRECISION, Array, DFRConfig, DFRParams,
                              QuantParams, RidgeState)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class OnlineState:
    """Carry of the online system (a pytree).

    Leaves may carry a leading member/slot axis: every pure function below
    is written for the single-system shapes and vmapped by the ensemble and
    stream-server wrappers.

    ``quant`` is the int8 serving fast-path state (``QuantParams``): inert
    zeros unless the serving stack runs with ``quantize='int8'``.  It rides
    the state tree so admission resets, retirement snapshots, donation and
    slot sharding all cover it for free; the fp32 math never reads it.
    """

    params: DFRParams
    ridge: RidgeState
    step: Array          # int32 counter
    loss_ema: Array      # scalar diagnostics
    quant: QuantParams   # int8 serving codes + scales (inert when fp32)
    # per-slot drift detector (retirement='adaptive'): fast/slow EMAs of
    # the serve step's 0/1 error rate.  Inert zeros in every other mode -
    # they ride the state tree so admission resets, retirement snapshots,
    # donation and slot sharding cover them for free (the QuantParams
    # pattern); the serving math never reads them.
    loss_fast: Array     # scalar fast error EMA (drift detector numerator)
    loss_slow: Array     # scalar slow error EMA (drift detector baseline)

    def tree_flatten(self):
        return (self.params, self.ridge, self.step, self.loss_ema,
                self.quant, self.loss_fast, self.loss_slow), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


# ---------------------------------------------------------------------------
# Pure functional API (vmappable / shard_map-able)
# ---------------------------------------------------------------------------


def init_state(cfg: DFRConfig, factor_beta: Optional[float] = None) -> OnlineState:
    """Fresh single-system state: paper init (p, q), zero readout + stats.

    With ``factor_beta`` set, the state additionally carries a *live*
    incremental Cholesky factor seeded for the empty system
    (``ridge.seed_factor``: chol(0 + beta I) = sqrt(beta) I), enabling the
    O(s^2) rank-1 maintenance path of ``online_serve_step`` and the
    triangular-solve fast path of ``refresh_output`` - no O(s^3)
    factorization ever runs for this stream.
    """
    rs = RidgeState.zeros(cfg.s, cfg.n_classes, cfg.dtype)
    if factor_beta is not None:
        rs = RidgeState(
            A=rs.A, B=rs.B, count=rs.count,
            Lt=ridge.seed_factor(cfg.s, factor_beta, cfg.dtype),
            factor_beta=jnp.asarray(factor_beta, cfg.dtype),
        )
    return OnlineState(
        params=DFRParams.init(cfg),
        ridge=rs,
        step=jnp.zeros((), jnp.int32),
        loss_ema=jnp.zeros((), cfg.dtype),
        quant=QuantParams.zeros(cfg.n_classes, cfg.n_rep),
        loss_fast=jnp.zeros((), cfg.dtype),
        loss_slow=jnp.zeros((), cfg.dtype),
    )


def reset_statistics(
    state: OnlineState,
    factor_beta: Optional[float] = None,
    forget: Optional[Array] = None,
) -> OnlineState:
    """Zero the Ridge sufficient statistics, keeping (p, q, W, b) and the
    step counter.

    This is the phase-switch primitive of the paper's protocol: once the
    reservoir parameters stop moving (truncated-bp phase ends), the (A, B)
    accumulated under the *old* features are stale and must be restarted.
    Pure and shape-preserving, so it vmaps over member/slot axes and can be
    applied selectively with ``jax.tree_util.tree_map`` + ``jnp.where``.

    The zeroed ``factor_beta`` also drops any live incremental factor (it
    factored the stale B); pass ``factor_beta`` to re-seed a fresh live
    factor for the restarted statistics, as ``init_state`` does.

    ``forget`` (exclusive with ``factor_beta``) is the *soft* reset: one
    forgetting-factor application that scales (A, B) - and any live factor
    consistently, ``Lt`` by sqrt(lambda) and ``factor_beta`` by lambda, so
    ``Lt^T Lt == B + factor_beta I`` is preserved exactly - instead of
    zeroing.  ``forget=1.0`` is bit-for-bit the identity (multiplying by
    1.0 changes no value); the sample ``count`` keeps the raw number of
    folded samples either way.
    """
    if forget is not None and factor_beta is not None:
        raise ValueError(
            "reset_statistics: factor_beta (hard reset re-seed) and forget "
            "(soft decaying reset) are exclusive - the soft reset keeps the "
            "existing decayed prior"
        )
    if forget is not None:
        try:
            lam_concrete = float(forget)
        except TypeError:          # traced value: the caller holds the
            lam_concrete = None    # (0, 1] contract (as StreamServer does)
        if lam_concrete is not None and not 0.0 < lam_concrete <= 1.0:
            # lambda = 0 would zero the live factor, and the next
            # maintained fold divides by its zero diagonal -> NaNs
            raise ValueError(
                f"forget must be in (0, 1], got {lam_concrete!r}"
            )
        lam = jnp.asarray(forget, state.ridge.B.dtype)
        rs = RidgeState(
            A=state.ridge.A * lam,
            B=state.ridge.B * lam,
            count=state.ridge.count,
            Lt=state.ridge.Lt * jnp.sqrt(lam),
            factor_beta=state.ridge.factor_beta * lam,
        )
        return dataclasses.replace(state, ridge=rs)
    rs = jax.tree_util.tree_map(jnp.zeros_like, state.ridge)
    if factor_beta is not None:
        rs = RidgeState(
            A=rs.A, B=rs.B, count=rs.count,
            Lt=ridge.seed_factor(rs.B.shape[-1], factor_beta, rs.B.dtype),
            factor_beta=jnp.asarray(factor_beta, rs.B.dtype),
        )
    return dataclasses.replace(state, ridge=rs)


# retirement='adaptive': loss-EMA breakpoint detector rates (per serving
# step, not per sample - a step folds up to `window` samples).  The fast
# EMA tracks the current regime over a few steps; the slow EMA is the
# baseline the break is measured against.  Server-tunable knobs (trip
# ratio, fire-time lambda, warmup) live on StreamServer; these two rates
# are the detector's fixed time constants.
ADAPT_FAST_ALPHA = 0.3
# the slow baseline is asymmetric: it chases improvements quickly (the
# noisy just-admitted phase seeds both EMAs near error 1.0 and the
# baseline must fall to the converged error before the detector can see a
# jump over it) but degrades only glacially, so at a drift point it stays
# anchored at the pre-drift error while the fast EMA runs away from it
ADAPT_SLOW_ALPHA_DOWN = 0.15
ADAPT_SLOW_ALPHA_UP = 0.01
# additive trip margin on the error-rate EMAs: guards against false fires
# when the slow baseline sits near zero (a near-perfect slot), where any
# multiplicative ratio alone would trip on the first stray miss
ADAPT_MARGIN = 0.25
# floor applied to the slow baseline after its first update, so "slow == 0"
# stays an unambiguous not-yet-initialized marker even for a slot whose
# first observed window had zero error
_ADAPT_EPS = 1e-6


def adaptive_anneal(
    states: OnlineState,
    step_err: Array,    # (S,) this step's serving error rate (1 - acc)
    update: Array,      # (S,) bool: slot folded live frozen-phase samples
    armed: Array,       # (S,) bool: slot past its detector warmup
    ratio: float,
    forget: Array,      # scalar lambda in (0, 1] applied to a tripped slot
) -> Tuple[OnlineState, Array]:
    """Per-slot drift detection + soft statistics anneal (batched).

    The slot-batched composition of ``reset_statistics(forget=...)`` with
    an in-step breakpoint detector: each slot keeps fast/slow EMAs of its
    serve-step *error rate* (the two detector leaves on ``OnlineState``);
    a slot whose fast EMA exceeds ``ratio * slow + ADAPT_MARGIN`` *trips*
    and has its Ridge statistics annealed by the traced per-slot forget
    vector ``lam = where(trip, forget, 1.0)`` - (A, B) and
    ``factor_beta`` scale by lam, any live factor by sqrt(lam), so
    ``Lt^T Lt == B + factor_beta I`` survives exactly (the
    ``reset_statistics`` soft-reset contract).  Tripping re-arms the
    detector by snapping the slow baseline to the fast EMA, so it cannot
    re-fire until the error rises again *relative to the post-drift
    regime*.

    The detector watches the 0/1 serving error (DDM-style) rather than
    the cross-entropy loss the serve step also reports: near a drift
    point the saturating CE loss moves by ~20% while the error rate jumps
    several-fold, so the error signal separates drift from stationary
    noise at far safer thresholds.

    Bitwise-silence contract: the anneal is ``lax.cond``-gated on any slot
    tripping, so a step where no detector fires leaves ``ridge`` (and
    everything downstream of it) bit-for-bit untouched - only the two
    detector leaves move.  EMAs update only where ``update`` is set (live
    slots folding frozen-phase samples); the first such step seeds both
    EMAs with the observed error.
    """
    fast0, slow0 = states.loss_fast, states.loss_slow
    init = update & (slow0 <= 0)
    fa = jnp.asarray(ADAPT_FAST_ALPHA, fast0.dtype)
    sa = jnp.where(
        step_err <= slow0,
        jnp.asarray(ADAPT_SLOW_ALPHA_DOWN, slow0.dtype),
        jnp.asarray(ADAPT_SLOW_ALPHA_UP, slow0.dtype),
    )
    fast = jnp.where(
        init, step_err,
        jnp.where(update, (1.0 - fa) * fast0 + fa * step_err, fast0),
    )
    slow = jnp.where(
        init, step_err,
        jnp.where(update, (1.0 - sa) * slow0 + sa * step_err, slow0),
    )
    slow = jnp.where(
        update, jnp.maximum(slow, jnp.asarray(_ADAPT_EPS, slow.dtype)), slow
    )
    trip = (
        update & armed & ~init
        & (fast > jnp.asarray(ratio, fast.dtype) * slow
           + jnp.asarray(ADAPT_MARGIN, fast.dtype))
    )
    lam = jnp.where(trip, jnp.asarray(forget, fast.dtype), 1.0)  # (S,)

    def _anneal(rs: RidgeState) -> RidgeState:
        lam2 = lam[:, None, None]
        return RidgeState(
            A=rs.A * lam2, B=rs.B * lam2, count=rs.count,
            Lt=rs.Lt * jnp.sqrt(lam)[:, None, None],
            factor_beta=rs.factor_beta * lam,
        )

    ridge_state = jax.lax.cond(
        jnp.any(trip), _anneal, lambda rs: rs, states.ridge
    )
    slow = jnp.where(trip, fast, slow)
    return dataclasses.replace(
        states, ridge=ridge_state, loss_fast=fast, loss_slow=slow
    ), trip


def online_logits(
    cfg: DFRConfig,
    mask: Array,
    state: OnlineState,
    u: Array,        # (B, T, n_in)
    length: Array,   # (B,)
) -> Array:
    """Readout logits on a window: (B, Ny)."""
    f = cfg.f()
    j_seq = masking.apply_mask(mask, u)
    x = reservoir.run_reservoir(
        state.params.p, state.params.q, j_seq, f=f, lengths=length
    )
    r = dprr.compute_dprr(x, lengths=length)
    logits = jnp.matmul(r, state.params.W.T, precision=DOT_PRECISION)
    return logits + state.params.b


def online_infer(
    cfg: DFRConfig,
    mask: Array,
    state: OnlineState,
    u: Array,
    length: Array,
) -> Array:
    """Inference on a window: class predictions (B,)."""
    return jnp.argmax(online_logits(cfg, mask, state, u, length), axis=-1)


def online_step(
    cfg: DFRConfig,
    mask: Array,
    state: OnlineState,
    u: Array,        # (B, T, n_in) window of streamed samples
    length: Array,   # (B,)
    label: Array,    # (B,) int32
    lr_res: Array,
    lr_out: Array,
    axis_names: Sequence[str] = (),
    weight: Optional[Array] = None,
) -> Tuple[OnlineState, Dict[str, Array]]:
    """One online training step: SGD update + (A, B) accumulation.

    With ``axis_names`` (inside ``shard_map`` over the data axes), the loss,
    grads, (A, B) increments and sample count are psum-reduced so every
    shard applies the identical global update - the sums are associative
    (paper Eq. 38), so this is exact, not an approximation.

    ``weight`` is an optional (B,) 0/1 live-sample mask for fixed-shape
    batching (the stream server's tail windows): dead samples contribute
    nothing to the loss, the grads, the (A, B) statistics or the count.
    ``weight=None`` is the exact unweighted path.
    """
    f = cfg.f()
    axis_names = tuple(axis_names)

    def _psum(x):
        return jax.lax.psum(x, axis_names) if axis_names else x

    j_seq = masking.apply_mask(mask, u)
    onehot = jax.nn.one_hot(label, cfg.n_classes, dtype=cfg.dtype)
    if weight is None:
        loss_fn = backprop.loss_from_logits
        n_live = jnp.asarray(u.shape[0], cfg.dtype)
    else:
        weight = weight.astype(cfg.dtype)
        loss_fn = lambda lg, oh: weight * backprop.loss_from_logits(lg, oh)  # noqa: E731
        n_live = jnp.sum(weight)
    loss, g = backprop.grads_truncated(
        state.params, j_seq, onehot, f, lengths=length, loss_fn=loss_fn
    )
    loss = _psum(loss)
    g = jax.tree_util.tree_map(_psum, g)
    bsz = jnp.maximum(_psum(n_live), 1.0)
    inv = 1.0 / bsz
    params = backprop.apply_sgd(state.params, g, lr_res, lr_out, inv_batch=inv)
    # streaming sufficient statistics with the *updated* reservoir params
    x = reservoir.run_reservoir(params.p, params.q, j_seq, f=f, lengths=length)
    r = dprr.compute_dprr(x, lengths=length)
    rt = dprr.r_tilde(r)
    # 0/1 weights scale rt once: both the A contraction (onehot . rt) and the
    # B outer product (rt . rt, where w^2 = w) drop dead samples exactly
    rt_acc = rt if weight is None else rt * weight[:, None]
    dA, dB = ridge.accumulate_ab(
        jnp.zeros_like(state.ridge.A), jnp.zeros_like(state.ridge.B), rt_acc, onehot
    )
    new = OnlineState(
        params=params,
        ridge=RidgeState(
            A=state.ridge.A + _psum(dA),
            B=state.ridge.B + _psum(dB),
            count=state.ridge.count + _psum(n_live).astype(state.ridge.count.dtype),
            # B moved (and psums across shards) without rotating L: any live
            # incremental factor is stale now - invalidate it.  Rank-1
            # maintenance lives in online_serve_step, the per-sample path.
            Lt=state.ridge.Lt,
            factor_beta=jnp.zeros_like(state.ridge.factor_beta),
        ),
        step=state.step + 1,
        loss_ema=0.99 * state.loss_ema + 0.01 * loss * inv,
        quant=state.quant,
        loss_fast=state.loss_fast,
        loss_slow=state.loss_slow,
    )
    logits = jnp.matmul(r, params.W.T, precision=DOT_PRECISION) + params.b
    hits = (jnp.argmax(logits, -1) == label).astype(jnp.float32)
    if weight is not None:
        hits = hits * weight
    metrics = {
        "loss": loss * inv,
        "acc": _psum(jnp.sum(hits)) / bsz.astype(jnp.float32),
    }
    return new, metrics


def online_serve_step(
    cfg: DFRConfig,
    mask: Array,
    state: OnlineState,
    u: Array,        # (B, T, n_in) window of streamed samples
    length: Array,   # (B,)
    label: Array,    # (B,) int32
    lr: Array,       # scalar slot learning rate (0 in the frozen phase)
    weight: Array,   # (B,) 0/1 live-sample mask
    accumulate: Array,  # scalar 0/1: accumulate (A, B) this step?
    maintain_factor: "bool | str" = False,  # False | True | 'defer'
    forget: Optional[Array] = None,  # lambda in (0, 1]: decay per sample
    train: bool = True,
    track_state_absmax: bool = False,
    fused: bool = False,
) -> Tuple[OnlineState, Array, Dict[str, Array]]:
    """Fused infer-before-update + train step for the serving path.

    One forward pass serves three consumers (the advantage a fused serving
    step has over separate ``infer``/``step`` calls):

      * the returned ``logits`` are the infer-before-update predictions
        (old parameters - the honest online metric),
      * the truncated-BP gradients reuse the same pass
        (``backprop.grads_truncated_from_aux``: the truncation
        stop_gradients everything the forward produced, so this is exact),
      * the (A, B) statistics reuse ``aux.r`` - gated by ``accumulate``,
        which the stream server sets only in the frozen-reservoir phase
        where the parameters producing ``aux.r`` are by construction the
        post-update parameters.  (Accumulating during the adaptation phase
        would be discarded at the phase boundary anyway - see
        ``reset_statistics``.)

    ``maintain_factor`` (static) compiles in the incremental Cholesky
    engine: every r~ row folded into B is simultaneously rotated into the
    live factor with an O(s^2) ``cholupdate`` (zero-gated rows are exact
    no-ops), keeping  L L^T = B + factor_beta I  current so the next
    refresh is two triangular solves instead of a factorization.  The
    caller must have seeded a live factor (``init_state(factor_beta=...)``)
    - the stream server's ``refresh_mode='incremental'`` invariant.  With
    ``maintain_factor=False`` no factor math is compiled and any live
    factor is invalidated once statistics move.

    ``maintain_factor='defer'`` keeps the factor valid but does NOT rotate
    it; the exact gated rows are returned as ``metrics['rt_rows']`` for the
    caller to fold (``ridge.cholupdate_window_t``) *outside* its
    select/cond plumbing.  This exists for the stream server: folding
    inside its admission/liveness conds keeps the pre-sweep factor alive
    across the rotation loop, which forces XLA to copy the (S, s, s)
    buffer every iteration instead of updating in place - deferring the
    fold past the conds restores the in-place loop (~2.5x per-step at
    S=32, Nx=16).  Numerically identical to the inline fold: dead/tail
    rows are zero-gated no-ops either way.

    ``forget`` (static None, or a traced lambda in (0, 1]) is the
    forgetting-factor retirement: *before each accumulated sample's fold*,
    (A, B) are scaled by lambda and the live factor by sqrt(lambda), the
    exponentially-weighted RLS recursion

        B <- lambda B + r~ r~^T,   A <- lambda A + onehot x r~.

    The regularizing prior decays with everything else (``factor_beta``
    picks up the same lambda^m), so the decomposition stays consistent:
    scaling commutes with the rank-1 rotation, and
    ``Lt^T Lt == B + factor_beta I`` keeps holding - exactly in real
    arithmetic, to fp rounding in practice (the (A, B) side applies
    closed-form lambda powers, the factor side one sqrt(lambda) per row;
    the interleaved property battery pins the tolerance).  Decay is
    applied once per *accumulated live sample* (dead/tail rows and
    adaptation-phase windows decay nothing), so its meaning is independent
    of the serving window size.  The equivalence contract: ``forget=1.0``
    is bit-for-bit the ``forget=None`` path (every scaling is a multiply
    by exactly 1.0), and ``forget=None`` compiles no decay math at all.
    With ``maintain_factor='defer'`` the per-row factor scalings are
    returned as ``metrics['fold_scale']`` (sqrt(lambda) for live rows,
    exactly 1.0 for gated rows) for the caller's
    ``ridge.cholupdate_window_t_decay`` fold.

    ``train`` (static) compiles in the truncated-BP machinery.  With
    ``train=False`` no gradient or SGD math is compiled: the parameters
    pass through untouched and the loss is evaluated as the same truncated
    objective's primal (``backprop.truncated_loss_from_aux``).  This is
    exactly the ``lr = 0`` step up to op scheduling: SGD with a zero
    learning rate subtracts exactly 0 from every (finite-gradient, already
    range-clamped) parameter, so the stream server cond-gates the whole
    backward out of its steady state (every live slot frozen) without
    changing the episode served.

    ``track_state_absmax`` (static) compiles in the int8 calibration
    statistic: ``quant.x_absmax`` picks up the max |x| over the window's
    live boundary states (``aux.x_last``/``aux.x_prev`` - the states the
    shared forward already materializes).  Off (the default) no quant leaf
    moves and no extra math is compiled, keeping the fp32 serving program
    identical to the pre-quantization build.

    ``fused`` (static) routes the shared forward through the fused
    reservoir->DPRR kernel path (``backprop.forward_fused``) that never
    materializes the state sequence.  The truncated gradients, statistics
    and calibration all consume the same ForwardAux fields, so nothing
    downstream changes.  Default False: the fused DPRR reduction reorders
    fp accumulation, and the fp32 serving episode is regression-pinned
    bitwise to the PR-6 golden - opt in per server, not globally.

    Returns (new state, logits (B, Ny), metrics).
    """
    f = cfg.f()
    j_seq = masking.apply_mask(mask, u)
    onehot = jax.nn.one_hot(label, cfg.n_classes, dtype=cfg.dtype)
    fwd = backprop.forward_fused if fused else backprop.forward
    aux = fwd(state.params, j_seq, f, lengths=length)

    w = weight.astype(cfg.dtype)
    loss_fn = lambda lg, oh: w * backprop.loss_from_logits(lg, oh)  # noqa: E731
    n_live = jnp.maximum(jnp.sum(w), 1.0)
    inv = 1.0 / n_live
    if train:
        loss, g = backprop.grads_truncated_from_aux(
            state.params, aux, onehot, f, loss_fn=loss_fn
        )
        params = backprop.apply_sgd(state.params, g, lr, lr, inv_batch=inv)
    else:
        loss = backprop.truncated_loss_from_aux(
            state.params, aux, onehot, f, loss_fn
        )
        params = state.params

    acc = accumulate.astype(cfg.dtype)
    live = w * acc                              # (B,) 0/1 accumulated rows
    rt = dprr.r_tilde(aux.r) * live[:, None]
    if forget is None:
        A_base, B_base = state.ridge.A, state.ridge.B
        decay = None
        rt_acc, oh_acc = rt, onehot
        fold_scale = None
    else:
        lam = jnp.asarray(forget, cfg.dtype)
        m = jnp.sum(live)
        # suffix_t: live rows folded strictly after row t - the later a
        # sample lands, the less it has decayed.  Each row's (A, B)
        # contribution carries lambda^suffix, split sqrt/sqrt between the
        # two accumulate_ab factors; the carried-over statistics decay by
        # the full lambda^m.  lambda=1 makes every power exactly 1.0.
        suffix = jnp.cumsum(live[::-1])[::-1] - live
        half = lam ** (0.5 * suffix)
        rt_acc = rt * half[:, None]
        oh_acc = onehot * half[:, None]
        decay = lam ** m
        A_base, B_base = state.ridge.A * decay, state.ridge.B * decay
        fold_scale = jnp.where(live > 0, jnp.sqrt(lam), jnp.ones_like(live))
    dA, dB = ridge.accumulate_ab(
        jnp.zeros_like(state.ridge.A), jnp.zeros_like(state.ridge.B),
        rt_acc, oh_acc,
    )
    if maintain_factor == "defer":
        # caller folds rt into the factor itself (see docstring)
        Lt = state.ridge.Lt
        factor_beta = state.ridge.factor_beta
    elif maintain_factor:
        # fold the same gated rows into the live factor: one O(s^2) rotation
        # sweep per streamed sample (zero rows are exact no-ops, so dead
        # samples and adaptation-phase windows leave the factor untouched -
        # in lockstep with the gated B accumulation above)
        if forget is None:
            Lt = ridge.cholupdate_window_t(state.ridge.Lt, rt)
        else:
            Lt = ridge.cholupdate_window_t_decay(state.ridge.Lt, rt, fold_scale)
        factor_beta = state.ridge.factor_beta
    else:
        Lt = state.ridge.Lt
        # statistics move without rotating the factor: drop any live factor
        factor_beta = jnp.where(
            acc * jnp.sum(w) > 0,
            jnp.zeros_like(state.ridge.factor_beta),
            state.ridge.factor_beta,
        )
    if forget is not None and maintain_factor:
        # the prior decays with the data (exponentially-weighted RLS), so
        # the factor keeps factoring  B + factor_beta I  exactly
        factor_beta = factor_beta * decay
    if track_state_absmax:
        # int8 calibration: running max |x| over the live boundary states
        # the forward already produced (weight-gated so dead/tail rows are
        # exact no-ops; scales fold from this at refresh boundaries)
        amax = jnp.maximum(
            jnp.max(jnp.abs(aux.x_last) * w[:, None]),
            jnp.max(jnp.abs(aux.x_prev) * w[:, None]),
        ).astype(state.quant.x_absmax.dtype)
        quant = dataclasses.replace(
            state.quant, x_absmax=jnp.maximum(state.quant.x_absmax, amax)
        )
    else:
        quant = state.quant
    new = OnlineState(
        params=params,
        ridge=RidgeState(
            A=A_base + dA,
            B=B_base + dB,
            count=state.ridge.count
            + (acc * jnp.sum(w)).astype(state.ridge.count.dtype),
            Lt=Lt,
            factor_beta=factor_beta,
        ),
        step=state.step + 1,
        loss_ema=0.99 * state.loss_ema + 0.01 * loss * inv,
        quant=quant,
        loss_fast=state.loss_fast,
        loss_slow=state.loss_slow,
    )
    hits = (jnp.argmax(aux.logits, -1) == label).astype(jnp.float32) * w
    metrics = {"loss": loss * inv, "acc": jnp.sum(hits) * inv}
    if maintain_factor == "defer":
        metrics["rt_rows"] = rt
        if forget is not None:
            metrics["fold_scale"] = fold_scale
    return new, aux.logits, metrics


def refresh_output(
    state: OnlineState, beta: Array, method: str = "cholesky_blocked"
) -> OnlineState:
    """Ridge re-solve of the output layer from the streamed (A, B).

    Fast path: when the state carries a live incremental factor for this
    exact ``beta`` (``RidgeState.factor_beta``), the solve is two
    triangular substitutions against L - O(s^2 Ny), no factorization
    (``lax.cond`` executes only the taken branch).  Otherwise the full
    O(s^3) pipeline of ``ridge.ridge_solve`` runs, so a mismatched beta
    (e.g. a regularization sweep over frozen statistics) stays correct.
    """
    beta = jnp.asarray(beta, state.ridge.B.dtype)

    def _from_factor(_):
        return ridge.ridge_solve_from_factor_t(state.ridge.A, state.ridge.Lt)

    def _full(_):
        return ridge.ridge_solve(
            state.ridge.A, ridge.regularize(state.ridge.B, beta), method
        )

    live = (state.ridge.factor_beta > 0) & (state.ridge.factor_beta == beta)
    Wt = jax.lax.cond(live, _from_factor, _full, None)
    params = DFRParams(
        p=state.params.p, q=state.params.q, W=Wt[:, :-1], b=Wt[:, -1]
    )
    return dataclasses.replace(state, params=params)


def refresh_output_batched(state: OnlineState, beta: Array) -> OnlineState:
    """Batched Ridge refresh over a leading member/slot axis.

    One batched Cholesky factors every member's (s, s) system in a single
    XLA program (``ridge.ridge_cholesky_batched``) - the stream server's
    periodic refresh of all live slots is one call, not a slot loop.
    """
    Wt = ridge.ridge_cholesky_batched(
        state.ridge.A, ridge.regularize(state.ridge.B, beta)
    )
    params = DFRParams(
        p=state.params.p, q=state.params.q, W=Wt[..., :, :-1], b=Wt[..., :, -1]
    )
    return dataclasses.replace(state, params=params)


def scatter_readout_rows(
    state: OnlineState, Wt: Array, eligible_rows: Array, rows: Array
) -> OnlineState:
    """Write refreshed readouts ``Wt`` (R, Ny, s) into slot rows ``rows`` of
    a slot-axis state where ``eligible_rows`` (R,) holds; everything else
    (and every non-readout leaf) is untouched - a refresh only ever moves
    (W, b).  ``rows`` must be duplicate-free (``RefreshCohorts`` pads its
    fixed-shape schedules with distinct non-cohort indices, so an
    ineligible pad row writes its own current value back - a no-op)."""
    W_rows = jnp.where(
        eligible_rows[:, None, None], Wt[..., :, :-1], state.params.W[rows]
    )
    b_rows = jnp.where(eligible_rows[:, None], Wt[..., :, -1],
                       state.params.b[rows])
    params = dataclasses.replace(
        state.params,
        W=state.params.W.at[rows].set(W_rows),
        b=state.params.b.at[rows].set(b_rows),
    )
    return dataclasses.replace(state, params=params)


def refresh_output_rows(
    state: OnlineState, beta: Array, rows: Array, eligible_rows: Array
) -> OnlineState:
    """Recompute-mode cohort refresh of a slot-axis state: gather the due
    rows, run the batched (s, s) Cholesky re-factorization over just those,
    scatter the refreshed readouts back.  With ``rows = arange(S)`` and all
    rows eligible this is leaf-for-leaf ``refresh_output_batched``."""
    Wt = ridge.ridge_cholesky_batched(
        state.ridge.A[rows],
        ridge.regularize(state.ridge.B[rows], beta),
    )
    return scatter_readout_rows(state, Wt, eligible_rows, rows)


def refresh_output_factor_rows(
    state: OnlineState, rows: Array, eligible_rows: Array
) -> OnlineState:
    """Incremental-mode cohort refresh of a slot-axis state: the due rows
    carry live factors of B + beta I (maintained rank-1 inside the serve
    step), so the refresh is one batched pair of blocked triangular
    substitutions - O(s^2 Ny) per slot, no factorization.  Beta is baked
    into the live factor at seeding."""
    Wt = ridge.ridge_solve_from_factor_t_batched(
        state.ridge.A[rows], state.ridge.Lt[rows]
    )
    return scatter_readout_rows(state, Wt, eligible_rows, rows)


def fold_quant_rows(
    state: OnlineState, rows: Array, eligible_rows: Array
) -> OnlineState:
    """Fold fresh int8 serving scales for slot rows ``rows`` of a slot-axis
    state where ``eligible_rows`` holds (same scatter contract as
    ``scatter_readout_rows``).

    Runs at ridge-refresh boundaries - the only place W moves in the
    serving steady state, so requantizing there keeps ``Wq * w_scale ~= W``
    without any per-step requantization cost.  ``w_scale`` comes from the
    freshly refreshed readout row, ``x_scale`` from the running
    ``x_absmax`` calibration tracked by ``online_serve_step``.  The scales
    are strictly positive after the first fold, which is what arms the
    server's quantized logits path for that slot.
    """
    from repro.kernels import ops as kops  # local: kernels import core

    q = state.quant
    el = eligible_rows
    W_rows = state.params.W[rows].astype(jnp.float32)       # (R, Ny, Nr)
    w_scale = kops.symmetric_scale(
        jnp.max(jnp.abs(W_rows), axis=(-2, -1)))            # (R,)
    Wq = kops.quantize_symmetric(W_rows, w_scale[:, None, None])
    x_scale = kops.symmetric_scale(q.x_absmax[rows])        # (R,)
    quant = QuantParams(
        Wq=q.Wq.at[rows].set(
            jnp.where(el[:, None, None], Wq, q.Wq[rows])),
        w_scale=q.w_scale.at[rows].set(
            jnp.where(el, w_scale, q.w_scale[rows])),
        x_scale=q.x_scale.at[rows].set(
            jnp.where(el, x_scale, q.x_scale[rows])),
        x_absmax=q.x_absmax,
    )
    return dataclasses.replace(state, quant=quant)


def _state_logical_axes(*leading: str) -> OnlineState:
    """``OnlineState``-shaped pytree of logical-axes tuples: every leaf
    leads with ``leading`` (one name per stacked leading dim), trailing
    dims replicated.  Feed to ``repro.distributed.sharding``."""
    lead = tuple(leading)
    return OnlineState(
        params=DFRParams(
            p=lead, q=lead,
            W=lead + (None, None), b=lead + (None,),
        ),
        ridge=RidgeState(
            A=lead + (None, None), B=lead + (None, None),
            count=lead,
            Lt=lead + (None, None), factor_beta=lead,
        ),
        step=lead,
        loss_ema=lead,
        quant=QuantParams(
            Wq=lead + (None, None),
            w_scale=lead, x_scale=lead, x_absmax=lead,
        ),
        loss_fast=lead,
        loss_slow=lead,
    )


def ensemble_logical_axes() -> OnlineState:
    """Logical-axis pytree of an ensemble ``OnlineState`` for the sharding
    rule table: every leaf leads with the ``member`` axis (sharded across
    devices - members are embarrassingly parallel), trailing dims
    replicated.  Feed to ``repro.distributed.sharding.guarded_shardings``.
    """
    return _state_logical_axes("member")


def slot_logical_axes() -> OnlineState:
    """Logical-axis pytree of a slot-batched ``OnlineState`` (the stream
    server's state tree): every leaf leads with the ``slot`` axis - slots
    are independent streams, embarrassingly parallel across the serving
    mesh (``launch.mesh.make_slot_mesh``)."""
    return _state_logical_axes("slot")


def ensemble_slot_logical_axes() -> OnlineState:
    """Logical-axis pytree for an ensemble-of-slots state (leaves stacked
    ``(S, K, ...)``): ``slot`` leads, ``member`` second, so a combined
    ``("slot", "member")`` serving mesh shards both ways at once and the
    production mesh's uniqueness guard gives ``slot`` the data axes."""
    return _state_logical_axes("slot", "member")


# ---------------------------------------------------------------------------
# Single-stream wrapper (the paper's one-device system)
# ---------------------------------------------------------------------------


class OnlineDFR:
    """Fused online train/infer stepper for a fixed-length stream window."""

    def __init__(self, cfg: DFRConfig, mask: Optional[Array] = None):
        self.cfg = cfg
        if mask is None:
            mask = masking.make_mask(
                jax.random.PRNGKey(cfg.mask_seed), cfg.n_nodes, cfg.n_in, cfg.dtype
            )
        self.mask = mask

    def init(self) -> OnlineState:
        return init_state(self.cfg)

    @partial(jax.jit, static_argnames=("self", "axis_names"))
    def step(
        self,
        state: OnlineState,
        u: Array,
        length: Array,
        label: Array,
        lr_res: Array,
        lr_out: Array,
        axis_names: Sequence[str] = (),
    ) -> Tuple[OnlineState, dict]:
        """One online training step: SGD update + (A, B) accumulation."""
        return online_step(
            self.cfg, self.mask, state, u, length, label, lr_res, lr_out,
            axis_names=axis_names,
        )

    @partial(jax.jit, static_argnames=("self",))
    def infer(self, state: OnlineState, u: Array, length: Array) -> Array:
        """Inference on a window: class predictions (B,)."""
        return online_infer(self.cfg, self.mask, state, u, length)

    @partial(jax.jit, static_argnames=("self", "method"))
    def refresh_output(
        self, state: OnlineState, beta: Array, method: str = "cholesky_blocked"
    ) -> OnlineState:
        """Ridge re-solve of the output layer from the streamed (A, B)."""
        return refresh_output(state, beta, method)

    @partial(jax.jit, static_argnames=("self",))
    def reset_statistics(self, state: OnlineState) -> OnlineState:
        """Restart the (A, B) accumulation (phase switch)."""
        return reset_statistics(state)


# ---------------------------------------------------------------------------
# Population-parallel online ensemble
# ---------------------------------------------------------------------------


class OnlineEnsemble:
    """K independent online DFR members vmapped over the member axis.

    All members share the fixed random mask (so the masked input j(k) is
    computed once per member by the same program) and see the same stream;
    they differ in their (p, q) seeds - member 0 is the exact paper init,
    members 1..K-1 are log-normal-jittered clones (``candidates.
    seed_candidates``).  ``step``/``infer_members`` are one vmapped jitted
    program over the member axis; ``infer`` combines members by averaging
    softmax probabilities (majority-of-evidence vote).

    ``cull`` applies the offline population engine's selection protocol to
    the live ensemble: members are ranked by loss EMA, losers are re-seeded
    near survivors with jittered (p, q) (``candidates.survivor_parents`` /
    ``candidates.jitter_clones``), and the re-seeded slots' Ridge statistics
    are restarted (their features changed, so the old (A, B) are stale -
    the online analogue of the offline engine re-evaluating from scratch).

    A K=1 ensemble is numerically identical to ``OnlineDFR`` step-for-step
    (the parity oracle in tests/test_stream_server.py).
    """

    def __init__(
        self,
        cfg: DFRConfig,
        n_members: int,
        mask: Optional[Array] = None,
        seed: int = 0,
        seed_jitter: float = 0.1,
    ):
        self.cfg = cfg
        self.n_members = int(n_members)
        self.seed = seed
        self.seed_jitter = seed_jitter
        if mask is None:
            mask = masking.make_mask(
                jax.random.PRNGKey(cfg.mask_seed), cfg.n_nodes, cfg.n_in, cfg.dtype
            )
        self.mask = mask

    def init(self, key: Optional[Array] = None) -> OnlineState:
        """Stacked ensemble state: every leaf leads with the K member axis."""
        cfg, k = self.cfg, self.n_members
        if key is None:
            key = jax.random.PRNGKey(self.seed)
        ps, qs = candidates.seed_candidates(
            key, k, cfg.p_init, cfg.q_init, self.seed_jitter, dtype=cfg.dtype
        )
        single = init_state(cfg)
        stacked = jax.tree_util.tree_map(
            lambda leaf: jnp.broadcast_to(leaf, (k, *leaf.shape)), single
        )
        params = DFRParams(p=ps, q=qs, W=stacked.params.W, b=stacked.params.b)
        return dataclasses.replace(stacked, params=params)

    @partial(jax.jit, static_argnames=("self",))
    def step(
        self,
        state: OnlineState,
        u: Array,
        length: Array,
        label: Array,
        lr_res: Array,
        lr_out: Array,
    ) -> Tuple[OnlineState, dict]:
        """All K members train on the shared window in one vmapped program;
        metrics come back per-member, shape (K,)."""
        return jax.vmap(
            lambda st: online_step(
                self.cfg, self.mask, st, u, length, label, lr_res, lr_out
            )
        )(state)

    @partial(jax.jit, static_argnames=("self",))
    def logits_members(self, state: OnlineState, u: Array, length: Array) -> Array:
        """Per-member logits (K, B, Ny)."""
        return jax.vmap(
            lambda st: online_logits(self.cfg, self.mask, st, u, length)
        )(state)

    @partial(jax.jit, static_argnames=("self",))
    def infer_members(self, state: OnlineState, u: Array, length: Array) -> Array:
        """Per-member predictions (K, B) (the K=1 parity surface)."""
        return jnp.argmax(self.logits_members(state, u, length), axis=-1)

    @partial(jax.jit, static_argnames=("self",))
    def infer(self, state: OnlineState, u: Array, length: Array) -> Array:
        """Ensemble predictions (B,): mean of member softmax probabilities.

        For K=1 this reduces to argmax of the single member's logits
        (softmax is monotone per row), preserving OnlineDFR parity.
        """
        probs = jax.nn.softmax(self.logits_members(state, u, length), axis=-1)
        return jnp.argmax(jnp.mean(probs, axis=0), axis=-1)

    @partial(jax.jit, static_argnames=("self",))
    def refresh_output(self, state: OnlineState, beta: Array) -> OnlineState:
        """Batched Ridge refresh of every member (one batched Cholesky)."""
        return refresh_output_batched(state, beta)

    @partial(jax.jit, static_argnames=("self", "survive_frac", "jitter"))
    def cull(
        self,
        state: OnlineState,
        key: Array,
        survive_frac: float = 0.5,
        jitter: float = 0.15,
    ) -> OnlineState:
        """Rank members by loss EMA, re-seed the losers near survivors.

        Survivors keep everything; each culled slot inherits its parent's
        full state, gets jittered (p, q), and restarts its Ridge statistics
        (stale under the moved reservoir parameters).  The restart follows
        ``reset_statistics(factor_beta=...)``: a culled row that inherited a
        *live* incremental factor gets a fresh ``ridge.seed_factor`` seed
        (chol(0 + beta I) = sqrt(beta) I) rather than an all-zero ``Lt``,
        which would be a singular fake factor violating
        ``Lt^T Lt == B + factor_beta I`` and NaN on the next maintained fold.
        """
        parent, keep, _ = candidates.survivor_parents(
            state.loss_ema, survive_frac
        )
        inherited = jax.tree_util.tree_map(lambda leaf: leaf[parent], state)
        new_p, new_q = candidates.jitter_clones(
            key, inherited.params.p, inherited.params.q, keep, jitter
        )
        params = dataclasses.replace(inherited.params, p=new_p, q=new_q)

        def _keep_or_zero(leaf):
            k_mask = keep.reshape((-1,) + (1,) * (leaf.ndim - 1))
            return jnp.where(k_mask, leaf, jnp.zeros_like(leaf))

        zeroed = jax.tree_util.tree_map(_keep_or_zero, inherited.ridge)
        beta_inh = inherited.ridge.factor_beta            # (K,)
        s = inherited.ridge.Lt.shape[-1]
        seeded_Lt = jnp.sqrt(beta_inh)[:, None, None] * jnp.eye(
            s, dtype=inherited.ridge.Lt.dtype
        )
        ridge_state = dataclasses.replace(
            zeroed,
            Lt=jnp.where(keep[:, None, None], inherited.ridge.Lt, seeded_Lt),
            factor_beta=beta_inh,
        )
        return dataclasses.replace(inherited, params=params, ridge=ridge_state)
