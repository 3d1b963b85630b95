"""Continuous-batching stream server: train-while-serve for sensor streams.

This is the serving runtime for the paper's actual deployment scenario
(Sec. 3.1): many independent sensor streams (predictive maintenance, ECG
monitors, ...) each need an online DFR that (a) answers every window from
the parameters it had *before* seeing the labels (infer-before-update, the
honest online metric) and (b) keeps adapting - truncated-bp SGD on
(p, q, W, b) while the reservoir is still settling, then frozen-reservoir
(A, B) accumulation with periodic Ridge refreshes of the output layer.

Mapping to paper Sec. 3.1, per slot:

    window arrives -> fused reservoir -> DPRR -> readout   (inference;
                      optionally the one-kernel path in kernels.streaming)
                   -> truncated-bp SGD update of (p, q, W, b)   [phase 1,
                      while slot_step < phase_steps: Fig. 2's training mode]
                   -> streaming (A, B) accumulation (Eq. 21-22, 38)
                   -> at the phase boundary: reset_statistics (features
                      moved under SGD, so the stats restart - Sec. 3.6's
                      requirement that Ridge sees consistent features)
                   -> every refresh_every server steps: Ridge re-solve of
                      the slot's output layer (Eq. 39-41).  Three refresh
                      policies compose from two orthogonal knobs:

                      * ``refresh_mode='recompute'`` - batched (s, s)
                        Cholesky re-factorization from the accumulated B
                        (the PR-2 path; O(s^3) per slot per round).
                      * ``refresh_mode='incremental'`` - the slot carries a
                        live factor of B + beta I (seeded sqrt(beta) I at
                        admission, rotated forward by O(s^2) rank-1
                        cholupdates inside the SAME fused step as samples
                        accumulate - ``repro.core.ridge`` incremental
                        engine), so the refresh is just two batched
                        triangular solves, never a factorization.
                      * ``refresh_cohorts=C`` - stagger the refresh round
                        over C round-robin slot cohorts
                        (``scheduler.RefreshCohorts``): identical per-slot
                        cadence, but each step refreshes at most ceil(S/C)
                        slots, flattening the p99 latency spike.  C=1 is
                        bit-for-bit the global round.

                   -> sample retirement (``retirement=``): the paper's
                      grow-only (A, B) anchors a slot to every sample it
                      ever saw; a drifting sensor needs the opposite.  Two
                      policies retire old samples *inside the same fused
                      step* (no extra dispatches):

                      * ``'forget'`` - exponentially-weighted RLS: every
                        accumulated sample scales (A, B) by lambda and the
                        live factor by sqrt(lambda) before its fold
                        (exact: scaling commutes with the rank-1
                        rotation).  lambda=1 is bit-for-bit the
                        non-retiring path.
                      * ``'window'`` - a per-slot ring buffer
                        (``core.types.WindowState``) of the last
                        ``retire_window`` retained (r~, onehot) rows; on
                        overwrite the evicted row is subtracted from
                        (A, B) and hyperbolically downdated out of the
                        live factor (``cholupdate_* sign=-1``), with a
                        numerical-safety guard that re-factorizes
                        B + beta I for any slot whose downdate would
                        drive a diagonal non-positive.  A capacity >=
                        the stream length is bit-for-bit the
                        non-retiring path (empty ring rows evict as
                        exact no-ops).

The scaling idea is the same one the token server uses for LM decode
(``repro.runtime.server``), with the shared slot scheduler
(``repro.runtime.scheduler.SlotScheduler``): a fixed number of slots, each
holding one stream's ``OnlineState`` as row s of a single batched state
pytree.  One jitted fixed-shape step advances ALL live slots - per-slot
learning-rate phase, per-sample validity weights for tail windows, dead
slots frozen by a lane mask - so XLA never re-specializes as streams
retire and refill (continuous batching).  Per-slot state isolation is
structural: every lane of the vmapped step reads only its own state row.

Device-resident serving pipeline (PR 5)
---------------------------------------

The paper's 1/13 computation-time win comes from keeping the whole
train-while-infer loop on the accelerator, no per-sample host round trips.
The software analogue is three orthogonal knobs (each independently
regression-tested bit-for-bit against the synchronous host-staged path):

* **Zero-copy request staging** (``staging='device'``, the default): a
  stream's padded payload is uploaded ONCE - staged at ``submit``
  (``core.types.RequestPool`` row), written into its slot row at admission
  via one donated in-place row write - and the per-step ``(S, W, T, n_in)``
  window batch is assembled *on device* by a cursor-indexed gather inside
  the fused jitted step.  The per-step host work drops from rebuilding and
  re-uploading the whole window batch in Python loops to shipping four
  tiny ``(S,)`` control vectors.  The periodic cohort Ridge refresh is
  folded into the same dispatch (``lax.cond``-gated on a traced due flag
  with a fixed-shape padded cohort row set), so a serving step is ONE
  program dispatch, refresh rounds included.  ``staging='host'`` retains
  the PR-4 host-staged batch build (and honors ``cfg.dtype``, which the
  PR-4 path silently upcast to float32).

* **Buffer donation** (``donate=True``, the default): the batched
  ``OnlineState`` / ``WindowState`` trees (the ``(S, s, s)`` ``B``/``Lt``
  leaves dominate) are donated to the step and refresh executables, so XLA
  updates them in place instead of copying the dominant buffers every
  dispatch.  Donation never changes numerics; ``donate=False`` keeps the
  copying PR-4 dispatch for A/B comparison.

* **Async pipelining** (``pipeline_depth=D``): predictions stay on device
  in a lag-``D`` ring; the host's per-step bookkeeping (accuracy,
  completion, retire/refill scatter) for step ``k`` runs while the device
  computes steps ``k+1 .. k+D``.  Only request completion or ``drain()``
  synchronizes.  Slot lifecycle (admission/retirement) is cursor-driven
  and therefore dispatch-time exact: pipelining delays only the *metric*
  bookkeeping, never the serving schedule, so ``pipeline_depth=0`` is
  bit-for-bit ``pipeline_depth=D`` over any episode.  Latency is reported
  honestly: ``latency_percentiles_ms`` separates dispatch time (host
  enqueue, never blocking on device compute) from drain time (the actual
  synchronization cost), so pipelining cannot hide its sync bill.

``bench_stream``'s ``pipeline`` table measures the three knobs against the
PR-4 synchronous host-staged server (see ROADMAP "Landed (PR 5)" for the
committed numbers).

Slot-sharded serving (PR 6)
---------------------------

``devices=n`` shards the slot axis over a 1-D ``("slot",)`` device mesh
(``launch.mesh.make_slot_mesh``; the ``slot`` logical-axis rule in
``distributed.sharding``): device d owns the contiguous slot block
``[d * S/n, (d+1) * S/n)``, fixed for the server's lifetime.  Slots are
independent streams, so the fused pool step runs under ``shard_map`` with
every per-slot operand - batched ``OnlineState``, ``WindowState`` rings,
the staged ``RequestPool``, the ``(S,)`` control vectors, and the padded
refresh-cohort row set (rewritten to shard-local indices by
``RefreshCohorts.due_rows_fixed_sharded``) - partitioned over ``"slot"``
and everything else replicated.  The device-local invariant: the hot path
contains NO cross-device collective; admission resets, the cursor-indexed
window gather, truncated-BP/accumulation, cohort Ridge refresh and sample
retirement all touch only the local block, and a live slot never migrates
between devices.  The ``lax.cond`` gates become per-device predicates
(``jnp.any`` over the local shard) whose untaken branches are exact
identities, so a sharded episode serves the single-device episode's
predictions across every retirement mode and pipeline depth, with states
equal under ``runtime.parity``'s rule: XLA compiles the slot-vmapped step
for the device-local slot count, and a few per-slot reductions then round
in another order (``tests/test_stream_sharded.py``).  Donation, zero-copy
staging and the fused cohort refresh all survive sharding: payload uploads
happen once (the owning device keeps the in-place row write, the others
drop it), and a serving step is still ONE dispatch.  Try it on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (e.g.
``python examples/online_edge.py --devices 8``).

Quantized int8 fast path + step blocking (PR 7)
-----------------------------------------------

Two serving-only accelerations (training, statistics and refresh math stay
fp32 bit-for-bit):

* ``quantize='int8'`` - the serving logits of ARMED slots come from the
  int8 fused kernel (``kernels.streaming.streaming_step_pallas_q8`` /
  its XLA oracle): readout weights and the recurrent reservoir state live
  as int8 codes under per-slot symmetric scales, the reservoir mix, DPRR
  accumulation and readout contract in int8 x int8 -> int32 integer
  arithmetic, and only the final logits dequantize to fp32.  Calibration
  is free: the fused serve step tracks the running reservoir-state absmax
  in ``OnlineState.quant``, and the scales FOLD (requantize W, arm the
  slot) inside the same cohort-refresh branch the Ridge re-solve already
  rides - scale refresh costs zero extra dispatches and tracks every
  retirement mode's weight updates.  Unarmed slots (no refresh boundary
  crossed yet - e.g. during the SGD adaptation phase) serve fp32.  The
  coded readout is ~4x smaller per slot than the fp32 ``(Ny, Nr)`` row
  (BENCH_stream_quant records the measured accuracy band + throughput).

* ``step_block=T`` - multi-sample step blocking: a ``lax.scan`` over the
  fused pool step serves up to T windows per slot in ONE dispatch with one
  stacked refresh-schedule upload and one prediction readback.  The host
  clamps each block so no slot completes mid-block, so admissions (and
  hence the entire continuous-batching schedule) land exactly where the
  unblocked server puts them: a blocked episode reproduces the
  ``step_block=1`` predictions exactly, across retirement modes,
  pipeline depths and device counts.  ``step_block=1`` routes through the
  PR-6 step functions unchanged (bitwise regression-pinned by
  ``tests/golden/stream_fp32_golden.npz``).

Both knobs compose with each other and with slot sharding
(``tests/test_stream_quant.py``, ``tests/test_stream_sharded.py``).
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque
from functools import partial
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import masking, online, ridge
from repro.core.online import (
    OnlineState,
    init_state,
    online_serve_step,
    refresh_output_batched,
    slot_logical_axes,
)
from repro.core.types import Array, DFRConfig, RequestPool, WindowState
from repro.distributed import sharding as shardrules
from repro.kernels import ops
from repro.launch.mesh import make_slot_mesh
from repro.runtime.scheduler import RefreshCohorts, SlotScheduler


@dataclasses.dataclass
class StreamRequest:
    """One sensor stream: N labeled samples served window-by-window."""

    rid: int
    u: np.ndarray             # (N, T, n_in) float32 samples
    length: np.ndarray        # (N,) int32 valid lengths
    label: np.ndarray         # (N,) int32 labels
    preds: List[int] = dataclasses.field(default_factory=list)
    correct: int = 0
    done: bool = False
    submit_t: float = 0.0
    finish_t: float = 0.0
    final_state: Optional[OnlineState] = None   # snapshot at retirement

    @property
    def n_samples(self) -> int:
        return self.u.shape[0]

    @property
    def online_accuracy(self) -> float:
        """Rolling infer-before-update accuracy over the served stream."""
        return self.correct / max(1, len(self.preds))


# ---------------------------------------------------------------------------
# The fixed-shape jitted step (all slots at once)
# ---------------------------------------------------------------------------


def _bcast_to(mask1d: Array, leaf: Array) -> Array:
    return mask1d.reshape((-1,) + (1,) * (leaf.ndim - 1))


def _retire_window_slot(
    U: Array,        # (s, s) transposed live factor
    A: Array,        # (Ny, s)
    B: Array,        # (s, s)
    count: Array,    # scalar int32 retained-sample count
    win: WindowState,  # single-slot ring buffer
    new_rows: Array,   # (W, s) gated r~ rows folded into (A, B) this step
    new_oh: Array,     # (W, Ny) matching label one-hots
    lv: Array,         # (W,) f32 0/1: row actually accumulated this step
) -> Tuple[Array, Array, Array, Array, WindowState, Array]:
    """Sequential sliding-window eviction for one slot (vmapped over S).

    Per accumulated row: the ring slot about to be overwritten is evicted -
    subtracted from (A, B), hyperbolically downdated out of the factor
    (guarded) - then the new row takes its place and the cursor advances.
    Dead rows (lv=0) touch nothing: the evicted row is zero-gated, the
    write and cursor advance are skipped, so tail windows and dead slots
    are exact no-ops.  Returns (U, A, B, count, win, bad): ``bad`` flags a
    guard-skipped downdate - the caller must re-factorize that slot from
    ``B + beta I`` (the factor is finite but stale).
    """
    cap = win.rows.shape[0]

    def fold(t, carry):
        U, A, B, count, rows, ohbuf, pos, bad = carry
        l = lv[t]
        ev_r = rows[pos] * l
        ev_o = ohbuf[pos] * l
        # every real r~ row ends in the constant-1 feature, so a nonzero
        # tail marks a genuine eviction (vs. never-written ring capacity)
        valid = ev_r[-1] > 0.5
        A = A - ev_o[:, None] * ev_r[None, :]
        B = B - jnp.outer(ev_r, ev_r)
        U, ok = ridge.cholupdate_dense_t_guarded(U, ev_r, -1.0)
        bad = bad | ~ok
        count = count - valid.astype(count.dtype)
        write = l > 0
        rows = rows.at[pos].set(jnp.where(write, new_rows[t], rows[pos]))
        ohbuf = ohbuf.at[pos].set(jnp.where(write, new_oh[t], ohbuf[pos]))
        pos = jnp.where(write, (pos + 1) % cap, pos)
        return U, A, B, count, rows, ohbuf, pos, bad

    U, A, B, count, rows, ohbuf, pos, bad = jax.lax.fori_loop(
        0, new_rows.shape[0], fold,
        (U, A, B, count, win.rows, win.onehot, win.pos,
         jnp.zeros((), jnp.bool_)),
    )
    return U, A, B, count, WindowState(rows=rows, onehot=ohbuf, pos=pos), bad


def _step_core(
    cfg: DFRConfig,
    mask: Array,
    states: OnlineState,   # leading slot axis S on every leaf
    fresh: OnlineState,    # single-system state (no S axis): admission reset
    fresh_mask: Array,     # (S,) bool: slots admitted this step
    u: Array,              # (S, W, T, n_in)
    length: Array,         # (S, W) int32
    label: Array,          # (S, W) int32
    weight: Array,         # (S, W) 0/1 live-sample mask (tail windows)
    live: Array,           # (S,) bool live-slot mask
    lr: Array,             # scalar base learning rate
    phase_steps: Array,    # scalar int32: slot steps of reservoir adaptation
    beta: Array,           # scalar ridge beta (window-guard refactorization)
    forget: Array,         # scalar lambda (used when retirement='forget')
    win: Optional[WindowState],  # slot-axis ring buffers (window mode)
    fused_infer: bool = True,
    maintain_factor: bool = False,
    retirement: str = "none",
    quantize: str = "none",
    adapt_ratio: float = 1.2,
    adapt_warmup: int = 4,
    chunk_t: Optional[int] = None,
) -> Tuple[OnlineState, Optional[WindowState], Array, Dict[str, Array]]:
    """One server step: infer-before-update + train for every live slot.

    Returns (new states, predictions (S, W), per-slot metrics).  Dead slots
    compute garbage in their lanes (fixed shapes) and are frozen by the
    ``live`` mask; the host never reads their predictions.  Slot admission
    (resetting row s to the fresh single-system state) happens in-program
    via ``fresh_mask`` so slot churn costs zero extra dispatches.

    The heart is ``online_serve_step`` vmapped over the slot axis: ONE
    forward pass per slot window feeds the infer-before-update predictions,
    the truncated-BP gradients AND the frozen-phase (A, B) accumulation -
    the fusion a pair of separate infer/step calls cannot express.  Because
    the statistics only accumulate in the frozen phase, the phase-boundary
    ``reset_statistics`` of the single-stream protocol is a no-op here
    (phase-1 stats are never written in the first place).

    ``retirement`` (static) compiles in the sample-retirement policy (see
    the module docstring): ``'forget'`` threads the lambda decay through
    the vmapped serve step and the deferred factor fold; ``'window'`` runs
    the per-slot ring-buffer eviction (``_retire_window_slot``) after the
    deferred update fold, then - only when some slot's downdate hit the
    numerical guard - re-factorizes exactly those slots' live factors from
    their retained ``B + beta I`` (one cond-gated batched Cholesky, never
    executed on the clean steady-state path); ``'adaptive'`` runs the
    per-slot loss-EMA breakpoint detector (``online.adaptive_anneal``)
    on the serve step's own loss metric - the ``forget`` operand becomes
    the fire-time lambda, applied through a traced (S,) per-slot forget
    vector only to tripped slots, cond-gated so a silent step is the
    ``retirement='none'`` step on everything but the two detector EMA
    leaves (under ``runtime.parity``'s rule: the loss EMA can round one
    ulp apart, because XLA fuses the forward differently around the
    detector).

    ``quantize='int8'`` (static) serves ARMED slots from the int8 fast
    path (``ops.streaming_logits_slots_q8``: coded reservoir state +
    readout, int8 x int8 -> int32 compute, fp32 dequantized logits) built
    from the slot's PRE-update parameters - the same infer-before-update
    contract as the fp32 paths.  A slot arms when its quantization scales
    first fold at a ridge-refresh boundary (``online.fold_quant_rows``,
    see ``_stream_step_pool_impl``); unarmed slots (``w_scale == 0``)
    serve the fp32 logits, so early-phase accuracy never pays quantization
    noise before calibration exists.  Training, statistics and refreshes
    stay fp32 throughout - only serving logits change.  The serve step
    additionally tracks the running reservoir-state absmax
    (``track_state_absmax``) that calibrates the state scale at the next
    fold.  ``quantize='none'`` compiles the exact PR-6 program.
    """
    f = cfg.f()

    # continuous batching: admitted slots start from the fresh state.  The
    # select copies the whole batched state (the (S, s, s) B leaf dominates),
    # so it is cond-gated: steady-state steps with no admissions skip it.
    def _admit(st):
        return jax.tree_util.tree_map(
            lambda batched, single: jnp.where(
                _bcast_to(fresh_mask, batched), single[None], batched
            ),
            st, fresh,
        )

    states = jax.lax.cond(jnp.any(fresh_mask), _admit, lambda st: st, states)
    if retirement == "window":
        # admitted slots also restart their ring buffer (same cond gating)
        win = jax.lax.cond(
            jnp.any(fresh_mask),
            lambda w: jax.tree_util.tree_map(
                lambda leaf: jnp.where(
                    _bcast_to(fresh_mask, leaf), jnp.zeros_like(leaf), leaf
                ),
                w,
            ),
            lambda w: w,
            win,
        )

    # per-slot learning-rate phase: adapt (p, q, W, b) while the slot is
    # young, then freeze the reservoir for consistent Ridge features; the
    # (A, B) statistics accumulate only in the frozen phase
    in_phase1 = states.step < phase_steps
    lr_slot = jnp.where(in_phase1, lr, 0.0).astype(cfg.dtype)
    acc_slot = jnp.where(in_phase1, 0.0, 1.0).astype(cfg.dtype)

    def _serve_all(train):
        # one vmapped fused serve step over the slot axis; 'defer' folds
        # the factor AFTER the liveness cond below - an inline fold under
        # the conds keeps the pre-sweep factor alive, forcing XLA to copy
        # the (S, s, s) buffer per rotation instead of updating it in
        # place (see online_serve_step docstring)
        def go(operands):
            sts, u_, len_, y_, w_, lr_, a_ = operands
            return jax.vmap(
                lambda st, u_s, len_s, y_s, w_s, lr_s, a_s: online_serve_step(
                    cfg, mask, st, u_s, len_s, y_s, lr_s, w_s, a_s,
                    maintain_factor="defer" if maintain_factor else False,
                    forget=forget if retirement == "forget" else None,
                    train=train,
                    track_state_absmax=(quantize == "int8"),
                )
            )(sts, u_, len_, y_, w_, lr_, a_)
        return go

    # steady state (every live slot past its adaptation phase: lr = 0
    # everywhere) skips the whole truncated-BP backward - SGD with lr 0 is
    # the exact identity on range-clamped parameters, so the branches serve
    # the same episode and the cond only sheds dead compute.  The cond sits
    # OUTSIDE the vmap: vmapping a cond would lower to a select that runs
    # both branches for every lane.
    new_states, logits, metrics = jax.lax.cond(
        jnp.any(in_phase1 & live), _serve_all(True), _serve_all(False),
        (states, u, length, label, weight, lr_slot, acc_slot),
    )

    if fused_infer:
        # route inference through the fused streaming kernel
        # (kernels.streaming: reservoir -> DPRR -> readout in one kernel
        # call, the TPU latency path; its XLA ref is the same math as the
        # shared forward, so on CPU this only adds the extra pass)
        j_seq = masking.apply_mask(mask, u)
        logits = ops.streaming_logits_slots(
            j_seq, length, states.params.p, states.params.q,
            states.params.W, states.params.b, cfg.n_nodes, f=f,
            chunk_t=chunk_t,
        )
    if quantize == "int8":
        # int8 fast path for ARMED slots (scales folded at least once):
        # pre-update coded readout + coded recurrent state, integer
        # reservoir/DPRR/readout compute, fp32 dequantized logits.  Unarmed
        # slots (w_scale == 0: no refresh boundary crossed yet) keep the
        # fp32 logits computed above - the select is per slot lane.
        j_seq = masking.apply_mask(mask, u)
        q_logits = ops.streaming_logits_slots_q8(
            j_seq, length, states.params.p, states.params.q,
            states.quant.Wq, states.quant.w_scale, states.quant.x_scale,
            states.params.b, cfg.n_nodes, f=f, chunk_t=chunk_t,
        )
        armed = states.quant.w_scale > 0
        logits = jnp.where(
            armed[:, None, None], q_logits.astype(logits.dtype), logits
        )
    preds = jnp.argmax(logits, axis=-1)  # (S, W)

    # dead slots keep their state untouched (cond-gated like admission:
    # a fully-live step - the steady state - pays no copy)
    new_states = jax.lax.cond(
        jnp.all(live),
        lambda pair: pair[0],
        lambda pair: jax.tree_util.tree_map(
            lambda n, o: jnp.where(_bcast_to(live, n), n, o), *pair
        ),
        (new_states, states),
    )
    if maintain_factor:
        # deferred rank-1 fold of the window into each slot's live factor
        # (the rows are exactly the gated r~ rows accumulated into B above:
        # dead/tail/adaptation-phase rows are zero, hence exact no-ops)
        rt_rows = metrics.pop("rt_rows")
        if retirement == "forget":
            scales = metrics.pop("fold_scale")
            Lt = jax.vmap(ridge.cholupdate_window_t_decay)(
                new_states.ridge.Lt, rt_rows, scales
            )
        else:
            Lt = jax.vmap(ridge.cholupdate_window_t)(
                new_states.ridge.Lt, rt_rows
            )
        new_states = dataclasses.replace(
            new_states,
            ridge=dataclasses.replace(new_states.ridge, Lt=Lt),
        )
        if retirement == "window":
            # retire the oldest retained sample per accumulated row: evict
            # from (A, B), downdate out of the live factor, refill the ring
            gate = weight * acc_slot[:, None]            # (S, W) 0/1
            oh_rows = jax.nn.one_hot(label, cfg.n_classes, dtype=cfg.dtype)
            Lt, A, B, count, win, bad = jax.vmap(_retire_window_slot)(
                new_states.ridge.Lt, new_states.ridge.A, new_states.ridge.B,
                new_states.ridge.count, win, rt_rows, oh_rows, gate,
            )
            # guard fallback: a clamp-skipped downdate left that slot's
            # factor stale - rebuild it from the retained B + beta I.  The
            # batched factorization is cond-gated on ANY slot flagging, so
            # the clean path (every realistic step) never pays it.
            Lt = jax.lax.cond(
                jnp.any(bad),
                lambda args: jnp.where(
                    bad[:, None, None],
                    jnp.swapaxes(
                        jnp.linalg.cholesky(ridge.regularize(args[1], beta)),
                        -1, -2,
                    ),
                    args[0],
                ),
                lambda args: args[0],
                (Lt, B),
            )
            new_states = dataclasses.replace(
                new_states,
                ridge=dataclasses.replace(
                    new_states.ridge, Lt=Lt, A=A, B=B, count=count
                ),
            )
    if retirement == "adaptive":
        # per-slot drift detection on the serving error rate the serve step
        # already produced: EMAs update for live slots that folded
        # frozen-phase samples; a tripped slot's statistics anneal by the
        # traced (S,) forget vector (lam=1.0 elsewhere), cond-gated on any
        # trip so the silent path stays retirement='none'.  Runs
        # AFTER the factor fold: the anneal scales the post-fold factor
        # consistently (Lt by sqrt(lam), factor_beta by lam).  A tripped
        # int8 slot needs no special handling - its quant scales re-fold
        # (re-arm) at its next refresh boundary like any other refresh.
        update = live & (~in_phase1) & (jnp.sum(weight, axis=1) > 0)
        armed = new_states.step >= phase_steps + jnp.int32(adapt_warmup)
        new_states, _ = online.adaptive_anneal(
            new_states, 1.0 - metrics["acc"], update, armed,
            adapt_ratio, forget,
        )
    return new_states, win, preds, metrics


def _stream_step_impl(
    cfg: DFRConfig,
    mask: Array,
    states: OnlineState,
    fresh: OnlineState,
    fresh_mask: Array,
    u: Array,
    length: Array,
    label: Array,
    weight: Array,
    live: Array,
    lr: Array,
    phase_steps: Array,
    beta: Array,
    forget: Array,
    win: Optional[WindowState],
    fused_infer: bool = True,
    maintain_factor: bool = False,
    retirement: str = "none",
    adapt_ratio: float = 1.2,
    adapt_warmup: int = 4,
    chunk_t: Optional[int] = None,
) -> Tuple[OnlineState, Optional[WindowState], Array, Dict[str, Array]]:
    """Host-staged serving step (the retained PR-4 fallback): the caller
    builds and uploads the padded window batch; see ``_step_core``."""
    return _step_core(
        cfg, mask, states, fresh, fresh_mask, u, length, label, weight,
        live, lr, phase_steps, beta, forget, win,
        fused_infer=fused_infer, maintain_factor=maintain_factor,
        retirement=retirement,
        adapt_ratio=adapt_ratio, adapt_warmup=adapt_warmup,
        chunk_t=chunk_t,
    )


_STEP_STATICS = ("cfg", "fused_infer", "maintain_factor", "retirement",
                 "adapt_ratio", "adapt_warmup", "chunk_t")
_stream_step = jax.jit(_stream_step_impl, static_argnames=_STEP_STATICS)
# donated twin: OnlineState (arg 2) and WindowState (arg 14) update in place
_stream_step_donated = jax.jit(
    _stream_step_impl, static_argnames=_STEP_STATICS, donate_argnums=(2, 14)
)


def _gather_window(
    pool: RequestPool, cursor: Array, live: Array, window: int, dtype
) -> Tuple[Array, Array, Array, Array]:
    """Assemble the per-step (S, W, ...) window batch on device: one
    cursor-indexed ``dynamic_slice`` per slot row of the staged pool.

    Pool capacity is a multiple of ``window`` and live cursors are
    window-aligned and < capacity, so no slice ever clamps; the pad region
    carries the host-staging defaults (u=0, length=1, label=0), making the
    gathered batch bit-identical to the host-built one for live lanes.
    ``weight`` zero-gates tail samples past the stream end and every dead
    lane, exactly like the host path.
    """
    slice_d = jax.vmap(
        lambda row, pos: jax.lax.dynamic_slice_in_dim(row, pos, window, 0)
    )
    u = slice_d(pool.u, cursor)
    length = slice_d(pool.length, cursor)
    label = slice_d(pool.label, cursor)
    idx = cursor[:, None] + jnp.arange(window, dtype=jnp.int32)[None, :]
    weight = ((idx < pool.n[:, None]) & live[:, None]).astype(dtype)
    return u, length, label, weight


def _stream_step_pool_impl(
    cfg: DFRConfig,
    mask: Array,
    states: OnlineState,
    fresh: OnlineState,
    fresh_mask: Array,
    pool: RequestPool,
    cursor: Array,         # (S,) int32 per-slot sample cursor
    live: Array,
    lr: Array,
    phase_steps: Array,
    beta: Array,
    forget: Array,
    win: Optional[WindowState],
    refresh_due: Array,    # scalar bool: cohort refresh folds in this step
    refresh_rows: Array,   # (R,) int32 fixed-shape padded cohort rows
    refresh_ok: Array,     # (R,) bool: genuine cohort member (vs. padding)
    fused_infer: bool = True,
    maintain_factor: bool = False,
    retirement: str = "none",
    refresh_mode: str = "recompute",
    window: int = 1,
    quantize: str = "none",
    adapt_ratio: float = 1.2,
    adapt_warmup: int = 4,
    chunk_t: Optional[int] = None,
) -> Tuple[OnlineState, Optional[WindowState], Array]:
    """Device-resident serving step: cursor-indexed window gather from the
    staged ``RequestPool``, the fused serve step, and the cohort Ridge
    refresh - ONE dispatch for all three.

    The refresh is ``lax.cond``-gated on the traced ``refresh_due`` flag
    with a fixed-shape padded cohort row set (``RefreshCohorts.
    due_rows_fixed``), so refresh rounds cost zero extra dispatches and
    off-rounds skip the refresh compute entirely.  The refresh branch runs
    the exact math of the standalone ``_stream_refresh_rows`` /
    ``_stream_refresh_factor_rows`` entry points on the post-step state,
    preserving the PR-4 step->refresh ordering.

    ``quantize='int8'`` folds the quantization scales of the refreshed
    cohort in the SAME refresh branch (``online.fold_quant_rows``): the
    freshly re-solved readout rows re-quantize immediately, so the int8
    serving path is never staler than one refresh cadence, and scale
    refreshes ride the existing dispatch for free.
    """
    u, length, label, weight = _gather_window(
        pool, cursor, live, window, cfg.dtype
    )
    new_states, win, preds, _ = _step_core(
        cfg, mask, states, fresh, fresh_mask, u, length, label, weight,
        live, lr, phase_steps, beta, forget, win,
        fused_infer=fused_infer, maintain_factor=maintain_factor,
        retirement=retirement, quantize=quantize,
        adapt_ratio=adapt_ratio, adapt_warmup=adapt_warmup,
        chunk_t=chunk_t,
    )

    def _refresh(st: OnlineState) -> OnlineState:
        el = (
            refresh_ok
            & live[refresh_rows]
            & (st.step[refresh_rows] >= phase_steps)
            & (st.ridge.count[refresh_rows] > 0)
        )
        if refresh_mode == "incremental":
            st = online.refresh_output_factor_rows(st, refresh_rows, el)
        else:
            st = online.refresh_output_rows(st, beta, refresh_rows, el)
        if quantize == "int8":
            st = online.fold_quant_rows(st, refresh_rows, el)
        return st

    new_states = jax.lax.cond(
        refresh_due, _refresh, lambda st: st, new_states
    )
    return new_states, win, preds


_POOL_STATICS = ("cfg", "fused_infer", "maintain_factor", "retirement",
                 "refresh_mode", "window", "quantize",
                 "adapt_ratio", "adapt_warmup", "chunk_t")
_stream_step_pool = jax.jit(
    _stream_step_pool_impl, static_argnames=_POOL_STATICS
)
# donated twin: OnlineState (arg 2) and WindowState (arg 12) update in
# place; the pool (arg 5) is NOT donated - it is read-only here and reused
# verbatim by the next step
_stream_step_pool_donated = jax.jit(
    _stream_step_pool_impl, static_argnames=_POOL_STATICS,
    donate_argnums=(2, 12),
)


def _stream_step_pool_block_impl(
    cfg: DFRConfig,
    mask: Array,
    states: OnlineState,
    fresh: OnlineState,
    fresh_mask: Array,
    pool: RequestPool,
    cursor: Array,          # (S,) int32 cursors at the BLOCK start
    live: Array,
    lr: Array,
    phase_steps: Array,
    beta: Array,
    forget: Array,
    win: Optional[WindowState],
    active_b: Array,        # (B,) bool: sub-step t actually runs
    refresh_due_b: Array,   # (B,) bool per-sub-step refresh flags
    refresh_rows_b: Array,  # (B, R) int32 per-sub-step padded cohort rows
    refresh_ok_b: Array,    # (B, R) bool
    fused_infer: bool = True,
    maintain_factor: bool = False,
    retirement: str = "none",
    refresh_mode: str = "recompute",
    window: int = 1,
    quantize: str = "none",
    adapt_ratio: float = 1.2,
    adapt_warmup: int = 4,
    chunk_t: Optional[int] = None,
) -> Tuple[OnlineState, Optional[WindowState], Array]:
    """Multi-sample step blocking: up to B = ``step_block`` consecutive
    pool steps in ONE dispatch, a ``lax.scan`` over the fused serving step.

    Each sub-step is exactly ``_stream_step_pool_impl`` (gather + serve +
    cohort refresh) on an in-carry cursor advanced by ``window`` samples
    per live slot per sub-step; the host ships one stacked refresh
    schedule instead of B control uploads, and pays ONE dispatch + ONE
    prediction readback for the whole block.  The schedule contract that
    makes a blocked episode serve the unblocked one exactly:

      * admission only happens at block starts (``fresh_mask`` is consumed
        by sub-step 0 and zeroed in the carry), and
      * the host clamps the active length so no live slot completes
        mid-block (``StreamServer.step``) - so blocks end at every
        retirement boundary and the slot lifecycle schedule is identical.

    ``active_b`` keeps the executable fixed-shape: clamped blocks run with
    tail sub-steps inactive (a ``lax.cond`` identity - dead sub-steps skip
    the serve compute, not just its effects), so one program serves every
    block length 1..B.  Returns predictions shaped (B, S, W); inactive
    sub-steps yield zeros the host never reads.
    """
    S = live.shape[0]

    def _sub(carry, xs):
        st, w, cur, fm = carry
        act, due, rows, ok = xs

        def _run(oper):
            st, w, cur, fm = oper
            ns, nw, preds = _stream_step_pool_impl(
                cfg, mask, st, fresh, fm, pool, cur, live, lr,
                phase_steps, beta, forget, w, due, rows, ok,
                fused_infer=fused_infer, maintain_factor=maintain_factor,
                retirement=retirement, refresh_mode=refresh_mode,
                window=window, quantize=quantize,
                adapt_ratio=adapt_ratio, adapt_warmup=adapt_warmup,
                chunk_t=chunk_t,
            )
            return ns, nw, preds.astype(jnp.int32)

        def _skip(oper):
            st, w, _, _ = oper
            return st, w, jnp.zeros((S, window), jnp.int32)

        ns, nw, preds = jax.lax.cond(act, _run, _skip, (st, w, cur, fm))
        cur = cur + jnp.where(live & act, window, 0).astype(cur.dtype)
        fm = jnp.zeros_like(fm)   # admissions only at the block start
        return (ns, nw, cur, fm), preds

    (states, win, _, _), preds = jax.lax.scan(
        _sub, (states, win, cursor, fresh_mask),
        (active_b, refresh_due_b, refresh_rows_b, refresh_ok_b),
    )
    return states, win, preds    # preds: (B, S, W)


_stream_step_pool_block = jax.jit(
    _stream_step_pool_block_impl, static_argnames=_POOL_STATICS
)
_stream_step_pool_block_donated = jax.jit(
    _stream_step_pool_block_impl, static_argnames=_POOL_STATICS,
    donate_argnums=(2, 12),
)


# ---------------------------------------------------------------------------
# Slot-sharded serving (PR 6): the same fused pool step, shard_map'd over a
# 1-D ("slot",) device mesh.  Slots are embarrassingly parallel, so every
# per-slot operand (states / ring buffers / staged pool / control vectors /
# the padded refresh-cohort row set, rewritten to shard-LOCAL indices by
# RefreshCohorts.due_rows_fixed_sharded) shards over "slot" and every scalar
# or shared operand replicates - the body contains NO collective: admission,
# the cursor gather, the serve step, cohort refresh and retirement all act
# on the device-local slot block.  The lax.cond gates inside _step_core
# become per-device predicates (jnp.any over the local shard); an untaken
# branch is the exact identity, so the sharded episode is the single-device
# episode under runtime.parity's rule (tests/test_stream_sharded.py holds
# this across device counts, retirement modes and pipeline depths).
# Donation flows through jit(shard_map): out_specs match the donated
# operands' shardings, so the (S/n, s, s) factor buffers still update in
# place per device.
# ---------------------------------------------------------------------------

_SLOT, _REP = P("slot"), P()
# operand order of _stream_step_pool_impl after cfg:
#   mask, states, fresh, fresh_mask, pool, cursor, live, lr, phase_steps,
#   beta, forget, win, refresh_due, refresh_rows, refresh_ok
_POOL_IN_SPECS = (_REP, _SLOT, _REP, _SLOT, _SLOT, _SLOT, _SLOT, _REP,
                  _REP, _REP, _REP, _SLOT, _REP, _SLOT, _SLOT)
_POOL_OUT_SPECS = (_SLOT, _SLOT, _SLOT)      # states, win, preds
# blocked twin: the stacked (B, R) cohort row sets shard their SECOND axis
# (shard-local fixed-width blocks per device, one row set per sub-step);
# the (B,) active/due flags replicate; preds (B, S, W) shard axis 1
_BLOCK_IN_SPECS = (_REP, _SLOT, _REP, _SLOT, _SLOT, _SLOT, _SLOT, _REP,
                   _REP, _REP, _REP, _SLOT, _REP, _REP,
                   P(None, "slot"), P(None, "slot"))
_BLOCK_OUT_SPECS = (_SLOT, _SLOT, P(None, "slot"))
_SHARDED_STEP_CACHE: Dict[Tuple, object] = {}
_SHARDED_WRITE_CACHE: Dict[Mesh, object] = {}


def _sharded_pool_step(mesh: Mesh, cfg: DFRConfig, donate: bool, **statics):
    """jit(shard_map(_stream_step_pool_impl)) for this mesh/config, cached
    module-level so servers (and the bench's device-count sweep) share
    executables.  Donation mirrors the unsharded twin: states (operand 1)
    and win (operand 11) update in place."""
    key = (mesh, cfg, donate, tuple(sorted(statics.items())))
    hit = _SHARDED_STEP_CACHE.get(key)
    if hit is None:
        body = jax.shard_map(
            partial(_stream_step_pool_impl, cfg, **statics),
            mesh=mesh, in_specs=_POOL_IN_SPECS, out_specs=_POOL_OUT_SPECS,
            check_vma=False,
        )
        hit = _SHARDED_STEP_CACHE[key] = jax.jit(
            body, donate_argnums=(1, 11) if donate else ()
        )
    return hit


def _sharded_pool_block_step(
    mesh: Mesh, cfg: DFRConfig, donate: bool, **statics
):
    """jit(shard_map(_stream_step_pool_block_impl)): the step-blocked scan
    with every sub-step acting on the device-local slot block.  The scan
    carries only slot-sharded or replicated values and the body is the
    collective-free pool step, so a blocked sharded episode matches the
    blocked single-device episode (same argument as the unblocked twin)."""
    key = ("block", mesh, cfg, donate, tuple(sorted(statics.items())))
    hit = _SHARDED_STEP_CACHE.get(key)
    if hit is None:
        body = jax.shard_map(
            partial(_stream_step_pool_block_impl, cfg, **statics),
            mesh=mesh, in_specs=_BLOCK_IN_SPECS, out_specs=_BLOCK_OUT_SPECS,
            check_vma=False,
        )
        hit = _SHARDED_STEP_CACHE[key] = jax.jit(
            body, donate_argnums=(1, 11) if donate else ()
        )
    return hit


def _pool_write_sharded_impl(
    pool: RequestPool, i: Array, u: Array, length: Array, label: Array,
    n: Array,
) -> RequestPool:
    """Per-shard body of the sharded admission write: the payload arrives
    replicated, the one device owning global row ``i`` (contiguous blocks
    of S/n slots) writes it into its local block, everyone else drops the
    scatter (out-of-range index + mode='drop') - no collective, and the
    owning device's write is the same in-place donated row write as the
    unsharded path."""
    s_loc = pool.n.shape[0]
    li = i - jax.lax.axis_index("slot") * s_loc
    li = jnp.where((li >= 0) & (li < s_loc), li, s_loc)
    return RequestPool(
        u=pool.u.at[li].set(u, mode="drop"),
        length=pool.length.at[li].set(length, mode="drop"),
        label=pool.label.at[li].set(label, mode="drop"),
        n=pool.n.at[li].set(n, mode="drop"),
    )


def _sharded_pool_write(mesh: Mesh):
    hit = _SHARDED_WRITE_CACHE.get(mesh)
    if hit is None:
        body = jax.shard_map(
            _pool_write_sharded_impl, mesh=mesh,
            in_specs=(_SLOT, _REP, _REP, _REP, _REP, _REP),
            out_specs=_SLOT, check_vma=False,
        )
        hit = _SHARDED_WRITE_CACHE[mesh] = jax.jit(
            body, donate_argnums=(0,)
        )
    return hit


def _pool_write_impl(
    pool: RequestPool, i: Array, u: Array, length: Array, label: Array,
    n: Array,
) -> RequestPool:
    return RequestPool(
        u=pool.u.at[i].set(u),
        length=pool.length.at[i].set(length),
        label=pool.label.at[i].set(label),
        n=pool.n.at[i].set(n),
    )


# always donated: admission writes one slot row into the (dominant) staged
# u buffer in place instead of copying the whole pool per admission
_pool_write = jax.jit(_pool_write_impl, donate_argnums=(0,))


@jax.jit
def _snapshot_slot(states: OnlineState, i: Array) -> OnlineState:
    """Slot row i of the batched state as a single-system state (one
    dispatch for the whole tree; module-level so servers share the cache).
    The gather materializes fresh buffers, so the snapshot stays valid
    after later (donated) steps consume the batched state it was read
    from - the donation-safety contract of ``StreamRequest.final_state``."""
    return jax.tree_util.tree_map(lambda leaf: leaf[i], states)


@partial(jax.jit, static_argnames=())
def _stream_refresh(
    states: OnlineState, beta: Array, eligible: Array
) -> OnlineState:
    """Batched Ridge refresh of the eligible slots (one batched Cholesky).

    ``eligible`` (S,) marks live slots past the phase boundary with at
    least one accumulated sample; others keep their readout (solving a
    zero-stats system would zero a trained W).
    """
    refreshed = refresh_output_batched(states, beta)
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(
            eligible.reshape((-1,) + (1,) * (a.ndim - 1)), a, b
        ),
        refreshed, states,
    )


def _stream_refresh_rows_impl(
    states: OnlineState, beta: Array, eligible: Array, rows: Array
) -> OnlineState:
    """Recompute-mode cohort refresh: gather the due cohort's rows, run the
    batched (s, s) Cholesky re-factorization over just those, scatter the
    refreshed readouts back.  With ``rows = arange(S)`` this is leaf-for-leaf
    identical to ``_stream_refresh`` (the staggering equivalence oracle)."""
    return online.refresh_output_rows(states, beta, rows, eligible[rows])


def _stream_refresh_factor_rows_impl(
    states: OnlineState, eligible: Array, rows: Array
) -> OnlineState:
    """Incremental-mode cohort refresh: the due cohort's slots carry live
    factors of B + beta I (maintained rank-1 inside the serve step), so the
    refresh is one batched pair of blocked triangular substitutions -
    O(s^2 Ny) per slot, no factorization.  Beta is baked into the live
    factor at seeding."""
    return online.refresh_output_factor_rows(states, rows, eligible[rows])


_stream_refresh_rows = jax.jit(_stream_refresh_rows_impl)
_stream_refresh_rows_donated = jax.jit(
    _stream_refresh_rows_impl, donate_argnums=(0,)
)
_stream_refresh_factor_rows = jax.jit(_stream_refresh_factor_rows_impl)
_stream_refresh_factor_rows_donated = jax.jit(
    _stream_refresh_factor_rows_impl, donate_argnums=(0,)
)


# ---------------------------------------------------------------------------
# The server
# ---------------------------------------------------------------------------


class StreamServer:
    """Continuous-batching train-while-serve runtime for DFR streams.

    Fixed shapes everywhere: ``max_streams`` slots, ``window`` samples per
    slot per step, samples padded to ``t_max`` timesteps.  Requests whose
    sample count is not a multiple of ``window`` get a zero-weighted tail
    (exact: dead samples contribute nothing - see ``online_step``).

    Refresh policy (see the module docstring): ``refresh_mode`` picks
    recompute (O(s^3) batched re-factorization) vs incremental (live rank-1
    factor, O(s^2) solves); ``refresh_cohorts`` staggers the round over
    round-robin slot cohorts with identical per-slot cadence.  The defaults
    reproduce the PR-2 global-recompute behavior exactly.

    Retirement policy (drift adaptation, see the module docstring):

      * ``retirement='none'``   - grow-only statistics (the default; the
        PR-3 behavior, bit-for-bit).
      * ``retirement='forget'`` - forgetting factor ``forget`` = lambda in
        (0, 1]: per-sample exponential decay of (A, B, Lt).  The
        equivalence contract: lambda=1 serves bit-for-bit the
        ``retirement='none'`` episode.
      * ``retirement='window'`` - sliding window of the last
        ``retire_window`` retained samples per slot (ring-buffer eviction
        + guarded hyperbolic downdate of the live factor); requires
        ``refresh_mode='incremental'`` (the downdate needs the live
        factor).  The equivalence contract: a capacity >= the stream
        length serves bit-for-bit the ``retirement='none'`` episode.
      * ``retirement='adaptive'`` - per-slot loss-EMA breakpoint detector
        inside the fused step: when a slot's fast loss EMA exceeds
        ``adapt_ratio`` x its slow EMA (past a ``adapt_warmup``-step
        arming period), that slot's ridge statistics are annealed once by
        ``adapt_forget`` (the ``reset_statistics(forget=...)`` semantics)
        and the detector re-arms.  No per-sample decay, no window buffer,
        no extra knobs to hand-tune per stream.  The equivalence
        contract: an episode in which the detector never fires serves
        the ``retirement='none'`` episode (``runtime.parity``'s rule).

    Serving pipeline (PR 5, see the module docstring):

      * ``staging='device'`` (default) - zero-copy request staging: payloads
        upload once, the window batch is gathered on device and the cohort
        refresh folds into the same single dispatch.  ``'host'`` retains
        the PR-4 per-step host batch build.
      * ``donate=True`` (default) - the step/refresh executables update the
        batched state trees in place (never changes numerics).
      * ``pipeline_depth=D`` - overlap host bookkeeping for step k with
        device compute of steps k+1..k+D; predictions ride a lag-D device
        ring drained by ``drain()`` / completion.  D=0 is the synchronous
        PR-4 schedule bit-for-bit.
      * ``pool_capacity`` - pre-size the staged pool (samples per slot row,
        rounded up to a window multiple).  Leave None to let it grow to the
        largest submitted stream (each growth re-specializes the jitted
        gather, so pre-sizing is worth it when stream lengths are known).
      * ``devices=n`` - shard the slot axis over the first n devices
        (``S % n == 0``, ``staging='device'``; see the module docstring's
        slot-sharding section).  Bitwise the devices=1 episode; scales
        served-samples/sec with the device count (BENCH_stream_sharded).

    Quantized serving fast path (PR 7):

      * ``quantize='int8'`` - armed slots serve from int8 codes (readout
        weights + recurrent reservoir state, symmetric per-slot scales;
        int8 x int8 -> int32 reservoir/DPRR/readout compute, fp32
        dequantized logits).  Scales calibrate from the running state
        absmax and fold at ridge-refresh boundaries; a slot serves fp32
        until its first fold (``w_scale == 0``), and training/statistics
        stay fp32 always.  Requires ``staging='device'``.  ~4x smaller
        serving-state readout bytes per slot; accuracy cost measured
        honestly in BENCH_stream_quant.
      * ``step_block=T`` - multi-sample step blocking: up to T consecutive
        serving steps (T windows per slot) fuse into ONE dispatch via a
        ``lax.scan`` over the pool step, amortizing dispatch overhead and
        per-step control uploads.  Blocks clamp so no slot completes
        mid-block, making the blocked episode serve the ``step_block=1``
        episode exactly (same admissions, same refresh schedule, same
        predictions).  Requires ``staging='device'``.  T=1 routes through
        the unchanged PR-6 step functions.

    Auto-configuration (PR 8):

      * ``config='auto'`` - fill the pure-performance knobs the caller
        left unset (``refresh_mode``, ``refresh_cohorts``, ``step_block``)
        from ``runtime.planner``'s calibrated cost model instead of the
        static defaults; the chosen ``Plan`` is exposed as ``self.plan``.
        Explicitly passed knobs always override the planner, and without
        ``config='auto'`` unset knobs resolve to the historical defaults
        (recompute / 1 / 1) - existing call sites are bitwise unchanged.
        The first auto server on a host pays a few seconds of
        micro-calibration, persisted to ``.planner_calibration.json``
        (override via ``REPRO_PLANNER_CAL``) so later servers skip it.
    """

    def __init__(
        self,
        cfg: DFRConfig,
        t_max: int,
        max_streams: int = 8,
        window: int = 4,
        lr: float = 0.2,
        phase_steps: int = 8,
        refresh_every: int = 5,
        beta: float = 1e-2,
        mask: Optional[Array] = None,
        fused_infer: Optional[bool] = None,
        refresh_mode: Optional[str] = None,
        refresh_cohorts: Optional[int] = None,
        retirement: str = "none",
        forget: float = 1.0,
        retire_window: int = 0,
        adapt_forget: float = 0.12,
        adapt_ratio: float = 1.2,
        adapt_warmup: int = 4,
        staging: str = "device",
        pipeline_depth: int = 0,
        donate: bool = True,
        pool_capacity: Optional[int] = None,
        latency_window: int = 4096,
        devices: int = 1,
        quantize: str = "none",
        step_block: Optional[int] = None,
        chunk_t: Optional[int] = None,
        config: Optional[str] = None,
    ):
        # -- config='auto': fill UNSET performance knobs from the calibrated
        # cost-model planner (runtime.planner).  Explicit knobs always win,
        # so any PR-7 call site resolves to bitwise-identical behavior; only
        # the pure-performance knobs (refresh_mode / refresh_cohorts /
        # step_block) are planned - semantic knobs (retirement, quantize,
        # staging, devices) are constraints the planner respects, never
        # choices it makes.
        if config not in (None, "auto"):
            raise ValueError(f"unknown config: {config!r} (None or 'auto')")
        self.plan = None
        if config == "auto":
            from repro.runtime import planner as _planner

            _pl = _planner.Planner(
                cfg.n_nodes, max_streams, window, t_max,
                n_classes=cfg.n_classes, refresh_every=refresh_every,
                retirement=retirement, quantize=quantize, staging=staging,
            )
            self.plan = _pl.search()
            if refresh_mode is None:
                refresh_mode = self.plan.refresh_mode
            if refresh_cohorts is None:
                refresh_cohorts = self.plan.refresh_cohorts
            if step_block is None:
                step_block = self.plan.step_block
            if chunk_t is None:
                chunk_t = self.plan.chunk_t
        # unset knobs without config='auto' keep the historical defaults
        if refresh_mode is None:
            refresh_mode = "recompute"
        if refresh_cohorts is None:
            refresh_cohorts = 1
        if step_block is None:
            step_block = 1
        if refresh_mode not in ("recompute", "incremental"):
            raise ValueError(f"unknown refresh_mode: {refresh_mode!r}")
        if retirement not in ("none", "forget", "window", "adaptive"):
            raise ValueError(f"unknown retirement: {retirement!r}")
        if retirement == "forget" and not 0.0 < forget <= 1.0:
            raise ValueError(f"forget must be in (0, 1], got {forget!r}")
        if retirement == "adaptive":
            if not 0.0 < adapt_forget <= 1.0:
                raise ValueError(
                    f"adapt_forget must be in (0, 1], got {adapt_forget!r}"
                )
            if adapt_ratio <= 1.0:
                raise ValueError(
                    f"adapt_ratio must be > 1, got {adapt_ratio!r}"
                )
            if adapt_warmup < 0:
                raise ValueError(
                    f"adapt_warmup must be >= 0, got {adapt_warmup!r}"
                )
        if retirement == "window":
            if refresh_mode != "incremental":
                raise ValueError(
                    "retirement='window' needs refresh_mode='incremental' "
                    "(the eviction downdates a live factor)"
                )
            if retire_window < 1:
                raise ValueError(
                    f"retirement='window' needs retire_window >= 1, got "
                    f"{retire_window!r}"
                )
        if staging not in ("device", "host"):
            raise ValueError(f"unknown staging: {staging!r}")
        if pipeline_depth < 0:
            raise ValueError(
                f"pipeline_depth must be >= 0, got {pipeline_depth!r}"
            )
        if latency_window < 1:
            raise ValueError(
                f"latency_window must be >= 1, got {latency_window!r}"
            )
        if devices < 1:
            raise ValueError(f"devices must be >= 1, got {devices!r}")
        if quantize not in ("none", "int8"):
            raise ValueError(f"unknown quantize: {quantize!r}")
        if quantize == "int8" and staging != "device":
            raise ValueError(
                "quantize='int8' requires staging='device' (the scale fold "
                "rides the fused cohort refresh of the pool step)"
            )
        if step_block < 1:
            raise ValueError(
                f"step_block must be >= 1, got {step_block!r}"
            )
        if chunk_t is not None and chunk_t < 1:
            raise ValueError(
                f"chunk_t must be None or >= 1, got {chunk_t!r}"
            )
        if step_block > 1 and staging != "device":
            raise ValueError(
                "step_block > 1 requires staging='device' (the blocked scan "
                "gathers every sub-step's window from the staged pool)"
            )
        if devices > 1:
            if staging != "device":
                raise ValueError(
                    "slot sharding (devices > 1) requires staging='device' "
                    "(the host-staged batch build re-uploads per step and "
                    "would serialize through one device)"
                )
            if max_streams % devices:
                raise ValueError(
                    f"max_streams={max_streams} must be divisible by "
                    f"devices={devices} (contiguous equal slot blocks)"
                )
        self.cfg = cfg
        self.t_max = int(t_max)
        self.max_streams = int(max_streams)
        self.window = int(window)
        self.lr = jnp.asarray(lr, cfg.dtype)
        self.phase_steps = jnp.asarray(phase_steps, jnp.int32)
        self.refresh_every = int(refresh_every)
        self.beta = jnp.asarray(beta, cfg.dtype)
        self.refresh_mode = refresh_mode
        self.retirement = retirement
        # adaptive mode re-purposes the ``forget`` operand slot as the
        # fire-time anneal factor (it is unused by 'none'/'window', and the
        # serve step still receives forget=None so no per-sample decay is
        # compiled in) - zero operand-signature changes across all modes
        self.forget = jnp.asarray(
            adapt_forget if retirement == "adaptive" else forget, cfg.dtype
        )
        self.adapt_ratio = float(adapt_ratio)
        self.adapt_warmup = int(adapt_warmup)
        self.retire_window = int(retire_window)
        self.staging = staging
        self.pipeline_depth = int(pipeline_depth)
        self.donate = bool(donate)
        self.quantize = quantize
        self.step_block = int(step_block)
        # Pallas time-chunk size for the fused streaming kernels; None keeps
        # the per-shape heuristic in kernels.ops (also the XLA-backend no-op)
        self.chunk_t = None if chunk_t is None else int(chunk_t)
        self._np_dtype = np.dtype(cfg.dtype)
        self.cohorts = RefreshCohorts(
            self.max_streams, self.refresh_every, refresh_cohorts
        )
        if fused_infer is None:
            # TPU: the one-call fused kernel (kernels.streaming) wins the
            # infer latency; CPU/XLA: reuse the serve step's shared forward
            fused_infer = jax.default_backend() == "tpu"
        self.fused_infer = bool(fused_infer)
        if mask is None:
            mask = masking.make_mask(
                jax.random.PRNGKey(cfg.mask_seed), cfg.n_nodes, cfg.n_in, cfg.dtype
            )
        self.mask = mask

        self.sched = SlotScheduler(self.max_streams)
        self.slot_pos = np.zeros(self.max_streams, np.int64)  # samples consumed
        # incremental mode: admitted slots carry a live factor seeded for the
        # empty system (sqrt(beta) I) - every later sample rotates it rank-1
        single = init_state(
            cfg, factor_beta=beta if refresh_mode == "incremental" else None
        )
        self._fresh_row = single
        self.states: OnlineState = jax.tree_util.tree_map(
            lambda leaf: jnp.broadcast_to(
                leaf, (self.max_streams, *leaf.shape)
            ).copy(),
            single,
        )
        # sliding-window mode: per-slot ring buffers of retained samples
        self.win: Optional[WindowState] = None
        if retirement == "window":
            self.win = jax.tree_util.tree_map(
                lambda leaf: jnp.broadcast_to(
                    leaf, (self.max_streams, *leaf.shape)
                ).copy(),
                WindowState.zeros(
                    self.retire_window, cfg.s, cfg.n_classes, cfg.dtype
                ),
            )
        # device staging: the per-slot request pool (uploads happen once at
        # submit/admit; the jitted step gathers windows by cursor)
        self.pool: Optional[RequestPool] = None
        self._staged: Dict[int, Tuple] = {}
        if self.staging == "device":
            cap = self._round_capacity(pool_capacity or self.window)
            self.pool = RequestPool.zeros(
                self.max_streams, cap, self.t_max, cfg.n_in, cfg.dtype
            )
        # slot sharding (devices > 1): a 1-D ("slot",) mesh owning
        # contiguous blocks of S/devices slots per device.  Every per-slot
        # tree is placed device-local ONCE here (via the 'slot' logical-axis
        # rule in repro.distributed.sharding) and the shard_map'd step keeps
        # it there - a slot never migrates between devices for its lifetime
        # (tests/test_stream_sharded.py's placement property).
        self.devices = int(devices)
        self.mesh: Optional[Mesh] = None
        if self.devices > 1:
            self.mesh = make_slot_mesh(self.devices)

            def _place(tree, axes):
                return jax.device_put(
                    tree,
                    shardrules.guarded_shardings(
                        jax.eval_shape(lambda: tree), axes, mesh=self.mesh
                    ),
                )

            self.states = _place(self.states, slot_logical_axes())
            if self.win is not None:
                self.win = _place(self.win, WindowState.slot_axes())
            self.pool = _place(self.pool, RequestPool.slot_axes())
            rep = NamedSharding(self.mesh, P())
            self.mask = jax.device_put(self.mask, rep)
            self._fresh_row = jax.device_put(self._fresh_row, rep)
        self._admitted_this_step: List[int] = []
        # steady-state control vectors change rarely: cache their device
        # copies so a typical step uploads only the (S,) cursor (the
        # refresh schedule cycles through refresh_every phases, the live /
        # fresh masks only move on admission/retirement)
        self._mask_cache: Dict[bytes, Array] = {}
        self._due_cache: Dict[int, Tuple[Array, Array, Array]] = {}
        self._due_block_cache: Dict[Tuple, Tuple] = {}
        self.global_step = 0
        self._autotuner = None  # optional WarmPoolAutotuner (attach_autotuner)
        # async pipeline: (device preds, per-slot bookkeeping meta) entries,
        # drained once more than pipeline_depth steps are in flight
        self._inflight: Deque[Tuple[Array, List[Tuple]]] = deque()
        # bounded latency records (ring buffers): total per-step wall time,
        # plus the honest split into non-blocking dispatch vs blocking drain
        self.step_times_s: Deque[float] = deque(maxlen=latency_window)
        self.dispatch_times_s: Deque[float] = deque(maxlen=latency_window)
        self.drain_times_s: Deque[float] = deque(maxlen=latency_window)

    # -- request lifecycle -------------------------------------------------------

    def _round_capacity(self, n: int) -> int:
        """Pool rows are window-aligned so cursor slices never clamp."""
        return max(self.window, -(-int(n) // self.window) * self.window)

    def _stage_request(self, req: StreamRequest) -> None:
        """Pad + upload the stream's full payload ONCE (submit-time): the
        per-step path never touches the sample arrays again."""
        cap = self._round_capacity(req.n_samples)
        if cap > self.pool.capacity:
            self._grow_pool(cap)
        cap = self.pool.capacity
        u = np.zeros((cap, self.t_max, self.cfg.n_in), self._np_dtype)
        u[: req.n_samples] = req.u
        length = np.ones((cap,), np.int32)
        length[: req.n_samples] = req.length
        label = np.zeros((cap,), np.int32)
        label[: req.n_samples] = req.label
        self._staged[id(req)] = (
            jnp.asarray(u), jnp.asarray(length), jnp.asarray(label),
            jnp.asarray(req.n_samples, jnp.int32), cap,
        )

    def _grow_pool(self, cap: int) -> None:
        """Grow every slot row to ``cap`` samples (new longest stream).
        Pad values match the staging defaults; shapes change, so the jitted
        gather step re-specializes once per growth."""
        pad = cap - self.pool.capacity
        self.pool = RequestPool(
            u=jnp.pad(self.pool.u, ((0, 0), (0, pad), (0, 0), (0, 0))),
            length=jnp.pad(self.pool.length, ((0, 0), (0, pad)),
                           constant_values=1),
            label=jnp.pad(self.pool.label, ((0, 0), (0, pad))),
            n=self.pool.n,
        )
        if self.mesh is not None:
            # growth pads the (replicated-direction) capacity axis; re-pin
            # the grown pool to its canonical slot sharding (rare path)
            self.pool = jax.device_put(
                self.pool,
                shardrules.guarded_shardings(
                    jax.eval_shape(lambda: self.pool),
                    RequestPool.slot_axes(), mesh=self.mesh,
                ),
            )

    def attach_autotuner(self, tuner) -> None:
        """Attach a ``repro.runtime.autotuner.WarmPoolAutotuner``: after
        every ``step()`` the tuner applies any hyperparameter hot swaps due
        at a cohort refresh boundary and (at its own low rate) runs one
        background (p, q, beta) tuning round.  A tuner that never swaps
        leaves the served episode bit-for-bit unchanged."""
        if tuner.server is not self:
            raise ValueError("tuner was constructed for a different server")
        self._autotuner = tuner

    def submit(self, req: StreamRequest) -> None:
        if req.u.shape[1] != self.t_max:
            raise ValueError(
                f"stream {req.rid}: samples padded to T={req.u.shape[1]}, "
                f"server expects t_max={self.t_max}"
            )
        req.submit_t = time.perf_counter()
        if self.staging == "device":
            self._stage_request(req)
        self.sched.submit(req)

    def _on_admit(self, i: int, req: StreamRequest) -> None:
        """Mark slot row i for the in-program fresh-state reset and write
        the staged payload into its pool row (one donated in-place write)."""
        self.slot_pos[i] = 0
        self._admitted_this_step.append(i)
        if self.staging == "device":
            staged = self._staged.pop(id(req), None)
            if staged is None or staged[4] != self.pool.capacity:
                # the pool grew (or the entry predates a growth): re-stage
                # against the current capacity - rare, costs one upload
                self._stage_request(req)
                staged = self._staged.pop(id(req))
            u, length, label, n, _ = staged
            write = (_sharded_pool_write(self.mesh) if self.mesh is not None
                     else _pool_write)
            self.pool = write(
                self.pool, jnp.asarray(i, jnp.int32), u, length, label, n
            )

    def _snapshot_row(self, i: int) -> OnlineState:
        """Copy of slot i's state (the retiring stream's final model).  On
        a slot mesh the copy is read from the owning device's shard:
        indexing the sharded batch itself gathers every device's (S/n, s, s)
        block for each retirement."""
        if self.mesh is None:
            return _snapshot_slot(self.states, jnp.asarray(i))
        owner, row = divmod(i, self.states.step.shape[0] // self.devices)
        dev = self.mesh.devices.flat[owner]
        local = jax.tree_util.tree_map(
            lambda leaf: next(sh.data for sh in leaf.addressable_shards
                              if sh.device == dev),
            self.states,
        )
        return _snapshot_slot(local, np.int32(row))

    def _cached_mask(self, mask_np: np.ndarray) -> Array:
        """Device copy of a small (S,) bool control mask, cached by value."""
        key = mask_np.tobytes()
        hit = self._mask_cache.get(key)
        if hit is None:
            if len(self._mask_cache) > 64:   # bounded (masks cycle)
                self._mask_cache.clear()
            hit = self._mask_cache[key] = jnp.asarray(mask_np)
        return hit

    def _cached_due(self, step: int) -> Tuple[Array, Array, Array]:
        """Device copy of the fixed-shape refresh schedule for this step's
        phase (cycles with period ``refresh_every``: cached once each)."""
        phase = step % self.refresh_every
        hit = self._due_cache.get(phase)
        if hit is None:
            if self.devices > 1:
                # shard-local row indices, one fixed-width block per device
                # (the P('slot') in_spec hands each device its own block)
                due, rows, ok = self.cohorts.due_rows_fixed_sharded(
                    step, self.devices
                )
            else:
                due, rows, ok = self.cohorts.due_rows_fixed(step)
            hit = self._due_cache[phase] = (
                jnp.asarray(due), jnp.asarray(rows), jnp.asarray(ok)
            )
        return hit

    def _cached_due_block(
        self, start: int, b_active: int
    ) -> Tuple[Array, Array, Array, Array]:
        """Stacked refresh schedule for a step block: the per-sub-step
        (due, rows, ok) triples for steps ``start .. start + B - 1`` plus
        the (B,) active flags for a clamped block.  The schedule cycles
        with period ``refresh_every`` (like ``_cached_due``), so the
        device copies are cached by (phase, active length)."""
        key = (start % self.refresh_every, b_active)
        hit = self._due_block_cache.get(key)
        if hit is None:
            B = self.step_block
            dues, rows, oks = [], [], []
            for t in range(B):
                if self.devices > 1:
                    d, r, o = self.cohorts.due_rows_fixed_sharded(
                        start + t, self.devices
                    )
                else:
                    d, r, o = self.cohorts.due_rows_fixed(start + t)
                dues.append(np.asarray(d))
                rows.append(np.asarray(r))
                oks.append(np.asarray(o))
            active = np.arange(B) < b_active
            # inactive tail sub-steps are cond-skipped anyway; zeroing
            # their due flags keeps the cached schedule canonical
            hit = self._due_block_cache[key] = (
                jnp.asarray(active),
                jnp.asarray(np.stack(dues).astype(bool) & active),
                jnp.asarray(np.stack(rows)),
                jnp.asarray(np.stack(oks)),
            )
        return hit

    # -- the serving loop --------------------------------------------------------

    def step(self) -> None:
        """One global step: admit, advance every live slot one window via
        the fused fixed-shape dispatch, book-keep at lag ``pipeline_depth``.

        ``staging='device'`` gathers the window batch on device from the
        staged pool (the host ships only (S,)-sized control vectors) and
        folds any due cohort refresh into the same dispatch;
        ``staging='host'`` retains the PR-4 build-pad-upload loop and the
        separate refresh dispatch.  Predictions enter the in-flight ring;
        entries deeper than ``pipeline_depth`` are drained (the only
        blocking device read), so depth 0 is fully synchronous.
        """
        t_start = time.perf_counter()
        self._admitted_this_step.clear()
        self.sched.admit(self._on_admit)
        S, W, T = self.max_streams, self.window, self.t_max
        live = np.zeros((S,), bool)
        fresh_mask = np.zeros((S,), bool)
        fresh_mask[self._admitted_this_step] = True
        slots = list(self.sched.live())
        meta: List[Tuple] = []
        for i, req in slots:
            lo = int(self.slot_pos[i])
            n = min(W, req.n_samples - lo)
            live[i] = True
            meta.append((0, i, req, lo, n))

        # step blocking: clamp the block so no live slot completes inside
        # it - blocks then end at every retirement boundary, so admission
        # timing (and with it the whole slot lifecycle schedule) matches
        # the step_block=1 episode exactly
        b_active = 1
        if self.step_block > 1 and slots:
            b_active = self.step_block
            for _t, i, req, lo, n in meta:
                b_active = min(b_active, -(-(req.n_samples - lo) // W))
            b_active = max(1, b_active)
            for t in range(1, b_active):
                for i, req in slots:
                    lo = int(self.slot_pos[i]) + t * W
                    n = min(W, req.n_samples - lo)
                    meta.append((t, i, req, lo, n))

        step_kw = self._step_kw()
        if self.staging == "device":
            pool_kw = dict(
                refresh_mode=self.refresh_mode, window=W,
                quantize=self.quantize, **step_kw,
            )
            operands = self._pool_operands(fresh_mask, live)
            if self.step_block > 1:
                active, due_b, rows_b, ok_b = self._cached_due_block(
                    self.global_step + 1, b_active
                )
                if self.mesh is not None:
                    step_fn = _sharded_pool_block_step(
                        self.mesh, self.cfg, self.donate, **pool_kw
                    )
                    self.states, self.win, preds = step_fn(
                        *operands, active, due_b, rows_b, ok_b
                    )
                else:
                    step_fn = (_stream_step_pool_block_donated if self.donate
                               else _stream_step_pool_block)
                    self.states, self.win, preds = step_fn(
                        self.cfg, *operands, active, due_b, rows_b, ok_b,
                        **pool_kw,
                    )
                self.global_step += b_active
            else:
                step_fn, args, kw = self._pool_step_call(operands, pool_kw)
                self.states, self.win, preds = step_fn(*args, **kw)
                self.global_step += 1
        else:
            # PR-4 host staging: rebuild + upload the padded window batch
            # (in cfg.dtype - the PR-4 code hardcoded float32 here, silently
            # upcasting non-f32 configs)
            u = np.zeros((S, W, T, self.cfg.n_in), self._np_dtype)
            length = np.ones((S, W), np.int32)  # dead samples: len 1, w 0
            label = np.zeros((S, W), np.int32)
            weight = np.zeros((S, W), self._np_dtype)
            for _t, i, req, lo, n in meta:
                u[i, :n] = req.u[lo:lo + n]
                length[i, :n] = req.length[lo:lo + n]
                label[i, :n] = req.label[lo:lo + n]
                weight[i, :n] = 1.0
            step_fn = _stream_step_donated if self.donate else _stream_step
            self.states, self.win, preds, _ = step_fn(
                self.cfg, self.mask, self.states, self._fresh_row,
                jnp.asarray(fresh_mask),
                jnp.asarray(u), jnp.asarray(length), jnp.asarray(label),
                jnp.asarray(weight), jnp.asarray(live), self.lr,
                self.phase_steps, self.beta, self.forget, self.win, **step_kw,
            )
            self.global_step += 1
            due = self.cohorts.due_slots(self.global_step)
            if due is not None:
                eligible = self._refresh_eligible(jnp.asarray(live))
                if len(due) < self.max_streams:
                    cohort = np.zeros((self.max_streams,), bool)
                    cohort[due] = True
                    eligible = eligible & jnp.asarray(cohort)
                rows = jnp.asarray(due, jnp.int32)
                if self.refresh_mode == "incremental":
                    fn = (_stream_refresh_factor_rows_donated if self.donate
                          else _stream_refresh_factor_rows)
                    self.states = fn(self.states, eligible, rows)
                else:
                    fn = (_stream_refresh_rows_donated if self.donate
                          else _stream_refresh_rows)
                    self.states = fn(self.states, self.beta, eligible, rows)

        # dispatch-time bookkeeping: the slot lifecycle is cursor-driven
        # (independent of prediction values), so retirement/refill never
        # waits on the device - only the metric bookkeeping rides the ring.
        # Meta is sub-step-major, so a blocked step's cursor advances
        # accumulate in schedule order and a slot retires exactly at its
        # block's end (the clamp guarantees no earlier completion).
        for _t, i, req, lo, n in meta:
            self.slot_pos[i] += n
            if self.slot_pos[i] >= req.n_samples:
                req.final_state = self._snapshot_row(i)
                self.sched.retire(i)   # continuous batching: slot refills
        self._inflight.append((preds, meta))
        if self._autotuner is not None:
            self._autotuner.on_step()
        self.dispatch_times_s.append(time.perf_counter() - t_start)
        while len(self._inflight) > self.pipeline_depth:
            self._drain_one()
        self.step_times_s.append(time.perf_counter() - t_start)

    def _step_kw(self) -> Dict:
        return dict(
            fused_infer=self.fused_infer,
            maintain_factor=(self.refresh_mode == "incremental"),
            retirement=self.retirement,
            adapt_ratio=self.adapt_ratio,
            adapt_warmup=self.adapt_warmup,
            chunk_t=self.chunk_t,
        )

    def _pool_operands(self, fresh_mask: np.ndarray, live: np.ndarray):
        return (
            self.mask, self.states, self._fresh_row,
            self._cached_mask(fresh_mask), self.pool,
            jnp.asarray(self.slot_pos.astype(np.int32)),
            self._cached_mask(live), self.lr, self.phase_steps,
            self.beta, self.forget, self.win,
        )

    def _pool_step_call(self, operands: Tuple, pool_kw: Dict):
        """(jitted program, args, kwargs) of one unblocked device-staged
        step with the refresh schedule of the next global step."""
        due, rows, ok = self._cached_due(self.global_step + 1)
        if self.mesh is not None:
            step_fn = _sharded_pool_step(
                self.mesh, self.cfg, self.donate, **pool_kw
            )
            return step_fn, (*operands, due, rows, ok), {}
        step_fn = (_stream_step_pool_donated if self.donate
                   else _stream_step_pool)
        return step_fn, (self.cfg, *operands, due, rows, ok), pool_kw

    def step_program_text(self) -> str:
        """Optimized HLO of the unblocked device-staged step program at the
        server's current shapes: Pallas kernels appear as
        ``tpu_custom_call``, cross-device traffic as collectives (the
        slot-sharded step has none)."""
        if self.staging != "device":
            raise ValueError("step_program_text needs staging='device'")
        idle = np.zeros((self.max_streams,), bool)
        pool_kw = dict(refresh_mode=self.refresh_mode, window=self.window,
                       quantize=self.quantize, **self._step_kw())
        step_fn, args, kw = self._pool_step_call(
            self._pool_operands(idle, idle), pool_kw)
        return step_fn.lower(*args, **kw).compile().as_text()

    def _drain_one(self) -> None:
        """Materialize the oldest in-flight step's predictions (the only
        blocking device read) and run its per-sample bookkeeping."""
        preds, meta = self._inflight.popleft()
        t0 = time.perf_counter()
        preds_np = np.asarray(preds)   # blocks: the served predictions
        self.drain_times_s.append(time.perf_counter() - t0)
        for t, i, req, lo, n in meta:
            # blocked steps return (B, S, W); unblocked return (S, W)
            block = preds_np[t] if preds_np.ndim == 3 else preds_np
            for k in range(n):
                pred = int(block[i, k])
                req.preds.append(pred)
                req.correct += int(pred == int(req.label[lo + k]))
            if lo + n >= req.n_samples:
                req.done = True
                req.finish_t = time.perf_counter()

    def drain(self) -> None:
        """Synchronize: flush every in-flight pipeline entry (predictions,
        accuracy, completion flags).  Idempotent; called automatically by
        ``run_until_drained``."""
        while self._inflight:
            self._drain_one()

    def _refresh_eligible(self, live: Array) -> Array:
        """Live slots past the phase boundary with accumulated samples."""
        return (
            live
            & (self.states.step >= self.phase_steps)
            & (self.states.ridge.count > 0)
        )

    def run_until_drained(
        self, max_steps: int = 100000, strict: bool = False
    ) -> List[StreamRequest]:
        """Serve until every stream completes (then flush the pipeline).

        If ``max_steps`` elapses with streams still live or queued, the
        truncation is never silent: a ``RuntimeWarning`` reports how many
        streams were left undrained (``strict=True`` raises instead).
        """
        steps = 0
        while self.sched.active() and steps < max_steps:
            self.step()
            steps += 1
        self.drain()
        if self.sched.active():
            undrained = len(self.sched.live()) + len(self.sched.queue)
            msg = (
                f"run_until_drained stopped at max_steps={max_steps} with "
                f"{undrained} stream(s) still live or queued"
            )
            if strict:
                raise RuntimeError(msg)
            warnings.warn(msg, RuntimeWarning, stacklevel=2)
        return self.sched.completed

    # -- diagnostics ---------------------------------------------------------------

    @property
    def completed(self) -> List[StreamRequest]:
        return self.sched.completed

    def latency_percentiles_ms(self) -> Dict[str, float]:
        """p50/p99 of the per-step wall time, split honestly for pipelining.

        ``p50_ms``/``p99_ms``: total wall time of ``step()`` (dispatch plus
        whatever draining that step performed), measured from ``step()``
        entry - so it includes admission and, on the host-staged path, the
        per-step batch build (which PR-4's timing excluded: its numbers are
        not directly comparable to these).  ``dispatch_*``: the non-blocking host
        portion (admit, control vectors, program enqueue).  ``drain_*``:
        the blocking device reads - the synchronization cost that async
        pipelining defers but must still pay, reported per drained entry so
        a deep pipeline cannot hide it.  All records ride bounded ring
        buffers (``latency_window`` entries), so long-lived servers don't
        grow without bound.

        A ring with no records reports ``NaN`` for its percentiles - a
        server that never stepped (or a depth-0 pipeline that never
        drained) is "no measurement", which must stay distinguishable from
        a genuine sub-resolution 0.0 ms reading.
        """
        out: Dict[str, float] = {}
        for prefix, rec in (("", self.step_times_s),
                            ("dispatch_", self.dispatch_times_s),
                            ("drain_", self.drain_times_s)):
            if rec:
                t = np.asarray(rec) * 1e3
                p50, p99 = (float(np.percentile(t, 50)),
                            float(np.percentile(t, 99)))
            else:
                p50 = p99 = float("nan")
            out[f"{prefix}p50_ms"] = p50
            out[f"{prefix}p99_ms"] = p99
        return out
