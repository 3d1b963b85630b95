"""Warm-pool background hyperparameter autotuner for the stream server.

The offline population engine (``repro.core.population``) finds good
(p, q) once, before serving; a long-lived stream server then holds those
hyperparameters forever, even when the streams it serves turn out to favor
different dynamics.  This module closes that loop at serving time:

  * Each refresh *cohort* of the server owns a small persistent candidate
    population over the (p, q, beta) triple - a *warm pool*: it survives
    across tuning rounds, so every round continues the search instead of
    restarting it.
  * At a low rate (every ``interval`` server steps) one live slot per
    cohort is visited round-robin.  The cohort's population - member 0
    pinned to that slot's live (p, q, beta), the incumbent - is evaluated
    on the slot's *recent retained windows* (the host-side request arrays
    the server already holds; no device traffic): ridge-refit readout on a
    fit split, NRMSE fitness on the most-recent validation split, one
    jitted program per round (``_evaluate_triples``).
  * The population is then culled CMA-ES-style
    (``candidates.survivor_parents`` + ``candidates.adapted_clones`` with
    D=3): survivors pass through verbatim, culled slots re-seed from the
    rank-weighted survivor covariance in log space.
  * When the round's winner beats the incumbent by ``margin`` (relative
    NRMSE), a hot swap is scheduled for that slot and applied just after
    the slot's next cohort *refresh boundary*: the winner's (p, q) rows
    scatter into the live slot tree, the readout warm-starts from the
    winner's ridge solve on the recent windows, and the Ridge statistics
    re-seed exactly like ``reset_statistics(factor_beta=beta)`` - A = B =
    0, count = 0, a fresh live factor ``sqrt(beta) I`` - so the
    incremental invariant ``Lt^T Lt == B + beta I`` survives the swap
    bit-exactly.  Any int8 serving scales for the slot disarm
    (``w_scale = 0``) and re-fold at its next refresh like a freshly
    admitted slot; the adaptive-retirement detector EMAs re-seed.

Scope notes: the beta dimension of the search only has a lasting effect
under ``refresh_mode='incremental'`` (the live factor carries the per-slot
beta; recompute-mode refreshes re-apply the server-wide beta).  Swaps are
applied between fused steps on the host thread, so they compose with slot
sharding, step blocking and int8 serving without touching the jitted step
programs; an attached tuner that never swaps leaves the served episode
bit-for-bit identical (the tuner only *reads* server state otherwise).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dprr, masking, ridge
from repro.kernels import ops as kops
from repro.core.candidates import (
    P_LOG_RANGE,
    Q_LOG_RANGE,
    adapted_clones,
    seed_candidates,
    survivor_parents,
)
from repro.core.online import OnlineState
from repro.core.types import (DOT_PRECISION, Array, DFRConfig, QuantParams,
                              RidgeState)

# beta search box (log10): spans the typical cfg.betas sweep
BETA_LOG_RANGE = (-4.0, 0.0)


# ---------------------------------------------------------------------------
# One-program candidate evaluation with per-member beta
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg",))
def _evaluate_triples(
    cfg: DFRConfig,
    mask: Array,
    ps: Array,       # (K,)
    qs: Array,       # (K,)
    betas: Array,    # (K,) per-member ridge beta (traced, not a sweep)
    fit_u: Array,    # (B, T, n_in)
    fit_len: Array,  # (B,)
    y_fit: Array,    # (B, Ny) one-hot
    val_u: Array,
    val_len: Array,
    y_val: Array,
) -> Tuple[Array, Array, Array]:
    """Evaluate K (p, q, beta) triples in one XLA program.

    Unlike ``population.evaluate_population`` (which sweeps the static
    ``cfg.betas`` grid for every member), beta here is a *traced* (K,)
    vector - the autotuner adapts it continuously, and baking it into the
    static config would recompile every round.  Returns ``(nrmse, acc,
    Wt)`` with Wt (K, Ny, s) the ridge readout fitted on the fit split.

    Features come from the fused training forward (``kernels.ops.
    train_forward``): the reservoir scan and the DPRR accumulation run in
    one pass with the (B, T, Nx) state sequence never materialized, so a
    tuning round's activation memory is O(Nx^2) per member-sample instead
    of O(T Nx) - the same production path ``population.refine_population``
    trains through.
    """
    f = cfg.f()

    def feats(p, q, u, lengths):
        j_seq = masking.apply_mask(mask, u)
        r, _, _, _ = kops.train_forward(j_seq, lengths, p, q,
                                        cfg.n_nodes, f=f)
        return r

    vfeats = jax.vmap(feats, in_axes=(0, 0, None, None))
    rt_fit = dprr.r_tilde(vfeats(ps, qs, fit_u, fit_len))    # (K, B, s)
    rt_val = dprr.r_tilde(vfeats(ps, qs, val_u, val_len))    # (K, Bv, s)

    s = rt_fit.shape[-1]
    A = jnp.einsum("by,kbs->kys", y_fit, rt_fit,
                   precision=DOT_PRECISION)                  # (K, Ny, s)
    Bm = jnp.einsum("kbs,kbt->kst", rt_fit, rt_fit,
                    precision=DOT_PRECISION)                 # (K, s, s)
    Breg = Bm + betas[:, None, None] * jnp.eye(s, dtype=Bm.dtype)
    C = jnp.linalg.cholesky(Breg)
    Wt = jax.vmap(
        lambda c, a: jax.scipy.linalg.cho_solve((c, True), a.T).T
    )(C, A)                                                  # (K, Ny, s)

    pred = jnp.einsum("kbs,kys->kby", rt_val, Wt,
                      precision=DOT_PRECISION)               # (K, Bv, Ny)
    var = jnp.mean(jnp.square(y_val - jnp.mean(y_val))) + 1e-12
    err = pred - y_val[None]
    nrmse = jnp.sqrt(jnp.mean(err * err, axis=(1, 2)) / var)
    nrmse = jnp.where(jnp.isfinite(nrmse), nrmse, jnp.inf)
    hits = jnp.argmax(pred, -1) == jnp.argmax(y_val, -1)[None]
    acc = jnp.mean(hits.astype(jnp.float32), axis=1)
    return nrmse, acc, Wt


# ---------------------------------------------------------------------------
# The hot swap: winner rows into the live slot tree
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("maintain_factor",), donate_argnums=(0,))
def _swap_slot_row(
    states: OnlineState,
    row: Array,        # scalar int32 slot index
    p_new: Array,      # scalars
    q_new: Array,
    W_new: Array,      # (Ny, Nr)
    b_new: Array,      # (Ny,)
    beta_new: Array,   # scalar
    maintain_factor: bool,
) -> OnlineState:
    """Scatter one winner into slot ``row`` of the slot-batched state.

    (p, q) and the warm-start readout replace the row's parameters; the
    Ridge statistics re-seed exactly like ``reset_statistics(
    factor_beta=beta_new)``: A = B = 0, count = 0 and (incremental mode) a
    fresh live factor ``sqrt(beta) I``, preserving ``Lt^T Lt == B +
    factor_beta I``.  The slot's step counter survives (its lifecycle
    phase does not restart); int8 codes disarm (``w_scale = 0`` - fp32
    serving until the next refresh re-folds) and the adaptive-retirement
    detector EMAs re-seed.
    """
    pr = states.params
    dt = pr.W.dtype
    params = dataclasses.replace(
        pr,
        p=pr.p.at[row].set(p_new.astype(pr.p.dtype)),
        q=pr.q.at[row].set(q_new.astype(pr.q.dtype)),
        W=pr.W.at[row].set(W_new.astype(dt)),
        b=pr.b.at[row].set(b_new.astype(dt)),
    )
    rs = states.ridge
    s = rs.Lt.shape[-1]
    if maintain_factor:
        Lt_row = ridge.seed_factor(s, beta_new, rs.Lt.dtype)
        fb_row = beta_new.astype(rs.factor_beta.dtype)
    else:
        Lt_row = jnp.zeros((s, s), rs.Lt.dtype)
        fb_row = jnp.zeros((), rs.factor_beta.dtype)
    ridge_state = RidgeState(
        A=rs.A.at[row].set(0.0),
        B=rs.B.at[row].set(0.0),
        count=rs.count.at[row].set(0),
        Lt=rs.Lt.at[row].set(Lt_row),
        factor_beta=rs.factor_beta.at[row].set(fb_row),
    )
    q8 = states.quant
    quant = QuantParams(
        Wq=q8.Wq.at[row].set(jnp.zeros_like(q8.Wq[row])),
        w_scale=q8.w_scale.at[row].set(0.0),
        x_scale=q8.x_scale.at[row].set(0.0),
        x_absmax=q8.x_absmax.at[row].set(0.0),
    )
    return dataclasses.replace(
        states,
        params=params,
        ridge=ridge_state,
        quant=quant,
        loss_fast=states.loss_fast.at[row].set(0.0),
        loss_slow=states.loss_slow.at[row].set(0.0),
    )


# ---------------------------------------------------------------------------
# Per-cohort warm pool
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _CohortPool:
    """Persistent candidate population of one refresh cohort."""

    p: np.ndarray       # (K,)
    q: np.ndarray       # (K,)
    beta: np.ndarray    # (K,)
    visit: int = 0      # round-robin cursor over the cohort's slots
    rounds: int = 0
    swaps: int = 0


@dataclasses.dataclass
class _PendingSwap:
    slot: int
    rid: int            # request id the evaluation belonged to
    p: float
    q: float
    beta: float
    W: np.ndarray       # (Ny, Nr)
    b: np.ndarray       # (Ny,)


class WarmPoolAutotuner:
    """Background (p, q, beta) re-optimization for a live ``StreamServer``.

    Attach with ``server.attach_autotuner(tuner)``; the server then drives
    ``on_step()`` after every fused step.  See the module docstring for
    the algorithm; knobs:

      * ``population``  - warm-pool size K per cohort (incumbent included).
      * ``history``     - retained samples evaluated per round (fixed, so
        the evaluation program compiles once); a slot is only visited once
        it has consumed at least this many samples.
      * ``interval``    - server steps between tuning rounds.
      * ``val_frac``    - most-recent fraction of the history used as the
        validation split (fitness is val NRMSE, so candidates are selected
        for the *newest* regime - the drift-tracking objective).
      * ``margin``      - relative NRMSE improvement the winner must show
        over the incumbent before a swap is scheduled.
      * ``jitter``      - isotropic floor of the CMA-ES-style survivor
        covariance used to re-seed culled candidates.
    """

    def __init__(
        self,
        server,
        population: int = 8,
        history: int = 32,
        interval: int = 4,
        val_frac: float = 0.25,
        margin: float = 0.05,
        survive_frac: float = 0.5,
        jitter: float = 0.2,
        seed: int = 0,
    ):
        if population < 2:
            raise ValueError(f"population must be >= 2, got {population!r}")
        if history < 8:
            raise ValueError(f"history must be >= 8, got {history!r}")
        if not 0.0 < val_frac < 1.0:
            raise ValueError(f"val_frac must be in (0, 1), got {val_frac!r}")
        self.server = server
        self.population = int(population)
        self.history = int(history)
        self.interval = max(1, int(interval))
        self.val_frac = float(val_frac)
        self.margin = float(margin)
        self.survive_frac = float(survive_frac)
        self.jitter = float(jitter)
        self._key = jax.random.PRNGKey(seed)
        self._pools: Dict[int, _CohortPool] = {}
        self._pending: Dict[int, _PendingSwap] = {}
        self._steps_seen = 0
        self._last_seen_step = int(server.global_step)
        self.swaps_applied = 0
        self.rounds_run = 0

    # -- server hook -------------------------------------------------------------

    def on_step(self) -> None:
        """Called by the server after each fused step: apply any pending
        swaps whose cohort refresh boundary just fired, then (every
        ``interval`` steps) run one tuning round."""
        # steps the last dispatch advanced through (blocked dispatches
        # advance several schedule phases at once); track unconditionally
        # so a swap scheduled later never sees a stale boundary window
        lo, hi = self._last_seen_step, self.server.global_step
        self._last_seen_step = hi
        fired = set()
        for step in range(lo + 1, hi + 1):
            c = self.server.cohorts.due_cohort(step)
            if c is not None:
                fired.add(c)
        self._apply_due_swaps(fired)
        self._steps_seen += 1
        if self._steps_seen % self.interval == 0:
            self._tune_round()

    # -- swap application --------------------------------------------------------

    def _apply_due_swaps(self, fired) -> None:
        """Apply pending swaps immediately *after* the owning cohort's
        refresh fired (the boundary): the slot then serves the warm-start
        readout for a full refresh period before its next re-solve folds
        statistics accumulated purely on the post-swap regime."""
        if not self._pending or not fired:
            return
        srv = self.server
        live = dict(srv.sched.live())
        for slot in list(self._pending):
            pend = self._pending[slot]
            if srv.cohorts.cohort_of_slot[slot] not in fired:
                continue
            del self._pending[slot]
            req = live.get(slot)
            if req is None or req.rid != pend.rid:
                continue  # the stream retired; the evaluation is stale
            srv.states = _swap_slot_row(
                srv.states,
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(pend.p, jnp.float32),
                jnp.asarray(pend.q, jnp.float32),
                jnp.asarray(pend.W),
                jnp.asarray(pend.b),
                jnp.asarray(pend.beta, jnp.float32),
                maintain_factor=(srv.refresh_mode == "incremental"),
            )
            self.swaps_applied += 1

    # -- tuning round ------------------------------------------------------------

    def _pool_for(self, cohort: int, p0: float, q0: float, b0: float
                  ) -> _CohortPool:
        pool = self._pools.get(cohort)
        if pool is None:
            self._key, sub = jax.random.split(self._key)
            k = self.population
            ps, qs = seed_candidates(sub, k, p0, q0, jitter=self.jitter)
            self._key, sub = jax.random.split(self._key)
            lo, hi = BETA_LOG_RANGE
            betas = b0 * np.exp(
                np.asarray(jax.random.normal(sub, (k,))) * self.jitter
            )
            betas[0] = b0
            betas = np.clip(betas, 10.0 ** lo, 10.0 ** hi)
            pool = self._pools[cohort] = _CohortPool(
                p=np.asarray(ps, np.float64),
                q=np.asarray(qs, np.float64),
                beta=betas.astype(np.float64),
            )
        return pool

    def _eligible_slots(self, cohort: int) -> List[Tuple[int, object]]:
        srv = self.server
        out = []
        warm = (int(np.asarray(srv.phase_steps)) + 1) * srv.window
        for slot, req in srv.sched.live():
            if srv.cohorts.cohort_of_slot[slot] != cohort:
                continue
            if srv.slot_pos[slot] >= max(self.history, warm):
                out.append((slot, req))
        return out

    def _tune_round(self) -> None:
        srv = self.server
        for cohort in range(srv.cohorts.n_cohorts):
            slots = self._eligible_slots(cohort)
            if not slots:
                continue
            pool = self._pools.get(cohort)
            visit = pool.visit if pool is not None else 0
            slot, req = slots[visit % len(slots)]
            self._tune_slot(cohort, slot, req)

    def _tune_slot(self, cohort: int, slot: int, req) -> None:
        srv = self.server
        cfg = srv.cfg
        # incumbent triple from the live slot row (tiny host reads, low rate)
        p0 = float(np.asarray(srv.states.params.p[slot]))
        q0 = float(np.asarray(srv.states.params.q[slot]))
        if srv.refresh_mode == "incremental":
            b0 = float(np.asarray(srv.states.ridge.factor_beta[slot]))
            if b0 <= 0:
                b0 = float(np.asarray(srv.beta))
        else:
            b0 = float(np.asarray(srv.beta))
        pool = self._pool_for(cohort, p0, q0, b0)
        pool.visit += 1
        pool.rounds += 1
        self.rounds_run += 1
        # pin the incumbent probe: member 0 is always the live triple
        pool.p[0], pool.q[0], pool.beta[0] = p0, q0, b0

        # the slot's most recent `history` consumed samples (host arrays)
        hi = int(srv.slot_pos[slot])
        lo = hi - self.history
        u = np.asarray(req.u[lo:hi], np.float32)
        length = np.asarray(req.length[lo:hi], np.int32)
        label = np.asarray(req.label[lo:hi], np.int32)
        n_val = max(1, int(round(self.history * self.val_frac)))
        n_fit = self.history - n_val
        y = np.eye(cfg.n_classes, dtype=np.float32)[label]

        nrmse, acc, Wt = _evaluate_triples(
            cfg, srv.mask,
            jnp.asarray(pool.p, np.float32), jnp.asarray(pool.q, np.float32),
            jnp.asarray(pool.beta, np.float32),
            jnp.asarray(u[:n_fit]), jnp.asarray(length[:n_fit]),
            jnp.asarray(y[:n_fit]),
            jnp.asarray(u[n_fit:]), jnp.asarray(length[n_fit:]),
            jnp.asarray(y[n_fit:]),
        )
        fitness = np.asarray(nrmse, np.float64)
        win = int(np.argmin(fitness))
        if (np.isfinite(fitness[win]) and win != 0
                and fitness[win] < fitness[0] * (1.0 - self.margin)):
            Wt_win = np.asarray(Wt[win])
            self._pending[slot] = _PendingSwap(
                slot=slot, rid=req.rid,
                p=float(pool.p[win]), q=float(pool.q[win]),
                beta=float(pool.beta[win]),
                W=Wt_win[:, :-1], b=Wt_win[:, -1],
            )
            pool.swaps += 1

        # evolve the warm pool: CMA-ES-style cull in (p, q, beta) log space
        parent, keep, _ = survivor_parents(
            jnp.asarray(fitness), self.survive_frac
        )
        parent = np.asarray(parent)
        coords = np.stack([pool.p[parent], pool.q[parent], pool.beta[parent]])
        self._key, sub = jax.random.split(self._key)
        new = np.asarray(adapted_clones(
            sub, jnp.asarray(coords, np.float32), jnp.asarray(keep),
            jitter=self.jitter,
            ranges=(P_LOG_RANGE, Q_LOG_RANGE, BETA_LOG_RANGE),
        ), np.float64)
        pool.p, pool.q, pool.beta = new[0], new[1], new[2]

    # -- diagnostics -------------------------------------------------------------

    def stats(self) -> Dict[str, int]:
        return {
            "rounds_run": self.rounds_run,
            "swaps_applied": self.swaps_applied,
            "swaps_pending": len(self._pending),
            "cohort_pools": len(self._pools),
        }
