"""Core dataclasses for the modular Delayed Feedback Reservoir (DFR).

The modular DFR model (paper Eq. 14):

    x(k)_n = p * f(j(k)_n + x(k-1)_n) + q * x(k)_{n-1}

with the loop-wrap convention x(k)_0 := x(k-1)_{Nx} (the feedback loop is a
ring of virtual nodes), masking j(k) = M @ u(k), and the DPRR readout

    r = vec( sum_k x(k) [x(k-1), 1]^T ),   r_tilde = [r, 1].

Only two reservoir parameters (p, q) plus the output layer (W, b) are
trainable; the mask M is fixed random, as in the paper.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

#: Matmul precision of the DFR's float32 math: masking, reservoir, DPRR
#: features, readouts, the ridge statistics (A, B) and the solves against
#: them.  On the TPU an f32 dot at default precision rounds its operands to
#: bf16, and the ridge solve over s = Nx^2 + Nx + 1 features turns that
#: rounding into lost accuracy.  A CPU computes f32 dots in f32 either way.
DOT_PRECISION = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Nonlinearities f for the modular DFR block.  The paper's evaluation uses
# f(x) = alpha * x (recommended in [11]); Mackey-Glass and tanh are provided
# for the analog-DFR reference path and ablations.
# ---------------------------------------------------------------------------

def f_linear(z: Array, alpha: float = 1.0) -> Array:
    return alpha * z


def f_tanh(z: Array, alpha: float = 1.0) -> Array:
    return jnp.tanh(alpha * z)


def f_mackey_glass(z: Array, mg_p: float = 2.0) -> Array:
    """Mackey-Glass style saturation f(z) = z / (1 + z^p) (paper Eq. 3)."""
    return z / (1.0 + jnp.abs(z) ** mg_p)


NONLINEARITIES: dict[str, Callable[..., Array]] = {
    "linear": f_linear,
    "tanh": f_tanh,
    "mackey_glass": f_mackey_glass,
}


@functools.lru_cache(maxsize=None)
def cached_nonlinearity(nonlinearity: str, alpha: float) -> Callable[[Array], Array]:
    """The bound nonlinearity ``z -> f(z, alpha)`` as a *stable* callable.

    Every jitted entry point that takes ``f`` as a static argument
    (``run_reservoir``, ``ops.reservoir_states``, ``ops.streaming_logits*``,
    the backprop paths) keys its compilation cache on the callable's
    identity.  ``DFRConfig.f()`` used to build a fresh lambda per call, so
    any call site outside a jit trace silently recompiled the same program
    on every invocation.  The lru_cache makes repeated requests for the
    same (nonlinearity, alpha) return the *same object*, turning those
    retraces into cache hits (regression-tested via ``jit._cache_size()``).
    """
    fn = NONLINEARITIES[nonlinearity]
    if nonlinearity == "mackey_glass":
        return fn  # ignores alpha; default mg_p=2.0 (matches the old lambda)
    return functools.partial(fn, alpha=alpha)


@dataclasses.dataclass(frozen=True)
class DFRConfig:
    """Static configuration of a modular DFR classifier."""

    n_in: int                      # #V  input channels
    n_classes: int                 # #C  output classes
    n_nodes: int = 30              # Nx  virtual nodes (paper uses 30)
    nonlinearity: str = "linear"   # f;  paper evaluation uses linear
    alpha: float = 1.0             # f scale (folded into p for linear f)
    p_init: float = 0.01           # paper Sec. 4.1
    q_init: float = 0.01           # paper Sec. 4.1
    epochs: int = 25               # paper Sec. 4.1
    lr: float = 1.0                # paper Sec. 4.1
    # LR is multiplied by 0.1 at these epochs (reservoir / output layer):
    res_lr_drop_epochs: Tuple[int, ...] = (5, 10, 15, 20)
    out_lr_drop_epochs: Tuple[int, ...] = (10, 15, 20)
    betas: Tuple[float, ...] = (1e-6, 1e-4, 1e-2, 1e0)  # ridge reg. sweep
    mask_seed: int = 0
    dtype: Any = jnp.float32

    @property
    def n_rep(self) -> int:
        """N_r: DPRR feature count = Nx * (Nx + 1)."""
        return self.n_nodes * (self.n_nodes + 1)

    @property
    def s(self) -> int:
        """s = Nx^2 + Nx + 1 (paper Eq. 20): ridge system size."""
        return self.n_nodes * self.n_nodes + self.n_nodes + 1

    def f(self) -> Callable[[Array], Array]:
        """The config's nonlinearity as a stable (identity-cached) callable,
        safe to pass as a static jit argument from non-traced call sites."""
        return cached_nonlinearity(self.nonlinearity, float(self.alpha))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DFRParams:
    """Trainable parameters of the DFR system (a pytree)."""

    p: Array      # scalar reservoir gain on the nonlinear branch
    q: Array      # scalar reservoir gain on the ring branch
    W: Array      # (Ny, Nr) output weights
    b: Array      # (Ny,)    output bias

    def tree_flatten(self):
        return (self.p, self.q, self.W, self.b), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @classmethod
    def init(cls, cfg: DFRConfig) -> "DFRParams":
        dt = cfg.dtype
        return cls(
            p=jnp.asarray(cfg.p_init, dt),
            q=jnp.asarray(cfg.q_init, dt),
            W=jnp.zeros((cfg.n_classes, cfg.n_rep), dt),
            b=jnp.zeros((cfg.n_classes,), dt),
        )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantParams:
    """Per-model int8 serving state for the quantized inference fast path.

    Symmetric (zero-point-free) int8 quantization of the two serving-path
    operands: the readout weights and the reservoir state.  Training and
    the ridge statistics stay fp32 - this state only feeds the
    ``quantize='int8'`` serving kernel (``kernels.ops.streaming_logits_q8``).

    Scales are *folded* at ridge-refresh boundaries (where W changes
    anyway, see ``online.fold_quant_rows``): ``w_scale``/``Wq`` from the
    freshly refreshed readout, ``x_scale`` from the running reservoir
    amplitude calibration ``x_absmax`` tracked during fp32 serving.
    ``w_scale == 0`` means "not yet armed" - the server keeps serving fp32
    logits for that slot until the first refresh folds live scales.

    Wq:       (Ny, Nr) int8  quantized readout codes (W ~= Wq * w_scale).
    w_scale:  scalar f32     readout scale, 0 until first fold.
    x_scale:  scalar f32     reservoir-state scale, 0 until first fold.
    x_absmax: scalar f32     running max |x| seen while serving (calibration).
    """

    Wq: Array
    w_scale: Array
    x_scale: Array
    x_absmax: Array

    def tree_flatten(self):
        return (self.Wq, self.w_scale, self.x_scale, self.x_absmax), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @classmethod
    def zeros(cls, n_classes: int, n_rep: int) -> "QuantParams":
        """Codes and scales; scales stay fp32 even under a bf16 config -
        the quantization *bookkeeping* is part of the fp32 statistics."""
        return cls(
            Wq=jnp.zeros((n_classes, n_rep), jnp.int8),
            w_scale=jnp.zeros((), jnp.float32),
            x_scale=jnp.zeros((), jnp.float32),
            x_absmax=jnp.zeros((), jnp.float32),
        )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RidgeState:
    """Streaming sufficient statistics for Ridge regression (paper Eq. 21-22).

    A = E R~^T      (Ny, s)
    B = R~ R~^T     (s, s)   (beta * I added at solve time)

    Both are sums over samples, hence associative: they accumulate online
    one sample at a time (the paper's edge system) and reduce across data
    shards with a single psum (this framework's at-scale extension).

    ``Lt``/``factor_beta`` carry the *incremental* Cholesky engine
    (``repro.core.ridge.cholupdate_*``): when ``factor_beta > 0``, ``Lt``
    is the live factor, stored *transposed* (upper-triangular U = L^T with
    L L^T = B + factor_beta * I), kept current by O(s^2) rank-1 rotations
    as samples stream in, so a Ridge refresh is just two triangular
    substitutions instead of an O(s^3) factorization.  Transposed because
    the rotation sweep touches one factor column per step, and column k of
    L is row k of U - contiguous in row-major storage, where the strided
    column walk wastes a cache line per element (see
    ``ridge.cholupdate_dense_t``).  ``factor_beta <= 0`` (the ``zeros``
    default) means no live factor - refreshes re-factorize from B.  The
    factor is *not* an associative sum, so it never psums across shards:
    paths that accumulate (A, B) without rotating it (``online_step``)
    invalidate it.
    """

    A: Array
    B: Array
    count: Array  # number of accumulated samples (scalar)
    Lt: Array           # (s, s) transposed live factor (garbage unless live)
    factor_beta: Array  # scalar; > 0 marks Lt live for that regularization

    def tree_flatten(self):
        return (self.A, self.B, self.count, self.Lt, self.factor_beta), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @classmethod
    def zeros(cls, s: int, n_classes: int, dtype=jnp.float32) -> "RidgeState":
        return cls(
            A=jnp.zeros((n_classes, s), dtype),
            B=jnp.zeros((s, s), dtype),
            count=jnp.zeros((), jnp.int32),
            Lt=jnp.zeros((s, s), dtype),
            factor_beta=jnp.zeros((), dtype),
        )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class WindowState:
    """Fixed-shape ring buffer of the last ``capacity`` *retained* samples
    for sliding-window retirement (one per stream slot; the stream server
    batches a leading slot axis onto every leaf).

    ``rows[pos]`` is the next eviction victim: when a new sample is
    retained with the buffer full, the overwritten row is subtracted back
    out of (A, B) and hyperbolically downdated out of the live Cholesky
    factor - the runtime path that turns the growing-memory incremental
    engine into a drift-tracking one.  Zero rows mark never-written
    capacity: every r~ row ends in the constant-1 feature
    (``dprr.r_tilde``), so ``rows[i, -1] == 0`` <=> slot i is empty, and
    evicting an empty row is an exact no-op everywhere (subtracting zeros,
    downdating by the zero vector) - no separate validity mask is needed,
    and a capacity >= the stream length is bit-for-bit the non-retiring
    path.

    rows:   (capacity, s)  retained r~ rows, ring order.
    onehot: (capacity, Ny) the matching label one-hots (A's other factor).
    pos:    scalar int32 write cursor (next slot to evict/overwrite).
    """

    rows: Array
    onehot: Array
    pos: Array

    def tree_flatten(self):
        return (self.rows, self.onehot, self.pos), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @classmethod
    def zeros(cls, capacity: int, s: int, n_classes: int,
              dtype=jnp.float32) -> "WindowState":
        return cls(
            rows=jnp.zeros((capacity, s), dtype),
            onehot=jnp.zeros((capacity, n_classes), dtype),
            pos=jnp.zeros((), jnp.int32),
        )

    @classmethod
    def slot_axes(cls) -> "WindowState":
        """Logical-axes pytree for the slot-batched ring buffers (leaves
        stacked ``(S, ...)``): ``slot`` leads, everything else replicated.
        Feed to ``repro.distributed.sharding.guarded_shardings`` - each
        slot's ring lives wholly on the device that owns the slot (the
        eviction loop is per-slot, never cross-slot)."""
        return cls(
            rows=("slot", None, None),
            onehot=("slot", None, None),
            pos=("slot",),
        )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RequestPool:
    """Device-resident staged stream payloads, one row per serving slot.

    The stream server's zero-copy request staging (``StreamServer``,
    ``staging='device'``): a stream's padded samples are uploaded ONCE -
    staged at ``submit``, written into the slot row at admission - and the
    per-step ``(S, W, T, n_in)`` window batch is assembled on device by a
    cursor-indexed gather inside the fused jitted step.  The host never
    rebuilds or re-uploads a sample after admission.

    Capacity is padded to a multiple of the serving window so every
    cursor-aligned ``dynamic_slice`` stays in bounds without clamping; the
    pad rows carry the same defaults the host-staging path uses for dead
    samples (``u=0``, ``length=1``, ``label=0``), which keeps the gathered
    batch bit-identical to host staging.

    u:      (S, C, T, n_in) staged samples, ``cfg.dtype``.
    length: (S, C) int32 valid lengths (1 on pad rows).
    label:  (S, C) int32 labels (0 on pad rows).
    n:      (S,)   int32 true sample count per slot row.
    """

    u: Array
    length: Array
    label: Array
    n: Array

    def tree_flatten(self):
        return (self.u, self.length, self.label, self.n), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.u.shape[1]

    @classmethod
    def zeros(cls, n_slots: int, capacity: int, t_max: int, n_in: int,
              dtype=jnp.float32) -> "RequestPool":
        return cls(
            u=jnp.zeros((n_slots, capacity, t_max, n_in), dtype),
            length=jnp.ones((n_slots, capacity), jnp.int32),
            label=jnp.zeros((n_slots, capacity), jnp.int32),
            n=jnp.zeros((n_slots,), jnp.int32),
        )

    @classmethod
    def slot_axes(cls) -> "RequestPool":
        """Logical-axes pytree for the staged pool: ``slot`` leads every
        leaf, so each device of a slot-sharded serving mesh holds only its
        own slots' staged payloads and the cursor-indexed window gather
        inside the sharded step never leaves the device."""
        return cls(
            u=("slot", None, None, None),
            length=("slot", None),
            label=("slot", None),
            n=("slot",),
        )


@dataclasses.dataclass(frozen=True)
class RegressionBatch:
    """A padded batch of input series with continuous targets.

    The population engine (``repro.core.population``) optimizes NRMSE on
    batches of this shape for sequence-regression tasks (e.g. the NARMA10
    benchmark in ``repro.data.timeseries.make_narma10``).

    u:       (B, T_max, n_in) float inputs, zero padded past `length`.
    length:  (B,) int32 true lengths  (1 <= length <= T_max).
    y:       (B, n_out) float regression targets (one vector per sequence).
    """

    u: Array
    length: Array
    y: Array

    @property
    def batch(self) -> int:
        return self.u.shape[0]

    @property
    def t_max(self) -> int:
        return self.u.shape[1]


@dataclasses.dataclass(frozen=True)
class TimeSeriesBatch:
    """A padded batch of variable-length multivariate time series.

    u:       (B, T_max, n_in) float inputs, zero padded past `length`.
    length:  (B,) int32 true lengths  (1 <= length <= T_max).
    label:   (B,) int32 class ids.
    """

    u: Array
    length: Array
    label: Array

    @property
    def batch(self) -> int:
        return self.u.shape[0]

    @property
    def t_max(self) -> int:
        return self.u.shape[1]
