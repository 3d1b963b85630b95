"""Public jit'd wrappers around the Pallas kernels, with dispatch.

Backend selection per call:
  * ``backend='tpu'``       - compile the Pallas kernel for TPU (production).
  * ``backend='interpret'`` - run the kernel body in Python on CPU (tests).
  * ``backend='xla'``       - pure-jnp fallback (this container's default;
                              identical math via repro.kernels.ref).
  * ``backend=None``        - auto: 'tpu' on TPU hosts else 'xla'.

All wrappers own the padding/layout contracts documented on the kernels, so
callers deal only in logical shapes.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import reservoir as core_res
from repro.kernels import ref as kref
from repro.kernels.dprr import dprr_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.reservoir import reservoir_pallas
from repro.kernels.ridge_solve import ridge_solve_blocked, cholesky_blocked
from repro.kernels.streaming import (streaming_step_pallas,
                                     streaming_step_pallas_q8)
from repro.kernels.train import train_forward_pallas, train_forward_scan


def _auto_backend(backend: Optional[str]) -> str:
    if backend is not None:
        return backend
    return "tpu" if jax.default_backend() == "tpu" else "xla"


# ---------------------------------------------------------------------------
# Symmetric int8 quantization primitives (the serving fast path's contract;
# same convention as optim.compression's gradient codec: scale = absmax/127
# with an epsilon floor, codes clipped to +-127, zero-point-free)
# ---------------------------------------------------------------------------


def symmetric_scale(absmax: jax.Array, eps: float = 1e-12) -> jax.Array:
    """Symmetric int8 scale from an absolute maximum: ``max(|v|)/127``.

    The epsilon floor keeps an all-zero operand (e.g. a zero-range
    reservoir window) quantizing to all-zero codes instead of NaNs -
    dequantization then reproduces the zeros exactly."""
    return jnp.maximum(absmax.astype(jnp.float32), eps) / 127.0


def quantize_symmetric(v: jax.Array, scale: jax.Array) -> jax.Array:
    """fp -> int8 codes: ``clip(round(v / scale), -127, 127)``."""
    return jnp.clip(
        jnp.round(v.astype(jnp.float32) / scale), -127, 127
    ).astype(jnp.int8)


def dequantize_symmetric(q: jax.Array, scale: jax.Array,
                         dtype=jnp.float32) -> jax.Array:
    """int8 codes -> fp: ``q * scale``."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pad_stream_batch(j_seq: jax.Array, lengths: jax.Array, n_pad: int,
                      chunk_t: int):
    """Pad (B, T, Nx) inputs to the fused streaming kernels' tiles: nodes
    to n_pad, time to chunk_t, and B above 8 to a multiple of 8
    (``streaming.batch_block``).  Pad rows get length 0, so they run
    frozen and their logits are sliced off."""
    jp = _pad_to(_pad_to(j_seq, 2, n_pad), 1, chunk_t)
    lens = lengths.astype(jnp.int32)
    if jp.shape[0] > 8:
        jp, lens = _pad_to(jp, 0, 8), _pad_to(lens, 0, 8)
    return jp, lens


def _ring_padded(q: jax.Array, nx: int, n_pad: int):
    """Ring-padded (L, qpow) for the reservoir/streaming kernels: zero-pad
    to n_pad and mirror the true last node into the last padded lane so the
    in-kernel ring wrap ``x_prev[:, -1:]`` reads node Nx-1 (see
    kernels/reservoir.py docstring)."""
    Lq = core_res.ring_matrix(q, nx, jnp.float32)
    qpow = core_res.ring_powers(q, nx, jnp.float32)
    Lp = jnp.zeros((n_pad, n_pad), jnp.float32).at[:nx, :nx].set(Lq)
    Lp = Lp.at[n_pad - 1, :nx].set(Lq[nx - 1])
    qp = jnp.zeros((n_pad,), jnp.float32).at[:nx].set(qpow)
    qp = qp.at[n_pad - 1].set(qpow[nx - 1])
    return Lp, qp


# ---------------------------------------------------------------------------
# DPRR features
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_nodes", "block_t", "backend"))
def dprr_features(
    x: jax.Array,          # (B, T, Nx) reservoir states
    lengths: jax.Array,    # (B,) int32
    n_nodes: int,
    *,
    block_t: int = 256,
    backend: Optional[str] = None,
) -> jax.Array:
    """Batched DPRR r vectors: (B, Nx*(Nx+1)), kernel-accelerated."""
    backend = _auto_backend(backend)
    b, t, nx = x.shape
    assert nx == n_nodes
    n_pad = max(128, -(-nx // 128) * 128)
    xp = _pad_to(_pad_to(x, 2, n_pad), 1, block_t)

    if backend == "xla":
        acc = jax.vmap(lambda xi, li: kref.dprr_ref(xi, li, n_nodes))(
            xp, lengths
        )
    else:
        interp = backend == "interpret"
        acc = jax.vmap(
            lambda xi, li: dprr_pallas(
                xi, li, n_nodes, block_t=block_t, interpret=interp
            )
        )(xp, lengths.astype(jnp.int32))
    outer = acc[:, :n_nodes, :n_nodes].reshape(b, n_nodes * n_nodes)
    sums = acc[:, :n_nodes, n_nodes]
    return jnp.concatenate([outer, sums], axis=-1)


# ---------------------------------------------------------------------------
# Reservoir states
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("n_nodes", "f", "block_b", "chunk_t", "backend")
)
def reservoir_states(
    j_seq: jax.Array,      # (B, T, Nx) masked inputs
    lengths: jax.Array,    # (B,)
    p: jax.Array,
    q: jax.Array,
    n_nodes: int,
    *,
    f: Callable[[jax.Array], jax.Array] = lambda z: z,
    block_b: int = 8,
    chunk_t: int = 128,
    backend: Optional[str] = None,
) -> jax.Array:
    """Batched reservoir states X (B, T, Nx), kernel-accelerated."""
    backend = _auto_backend(backend)
    b, t, nx = j_seq.shape
    if backend == "xla":
        return core_res.run_reservoir(p, q, j_seq, f=f, lengths=lengths)

    n_pad = max(128, -(-nx // 128) * 128)
    jp = _pad_to(_pad_to(_pad_to(j_seq, 2, n_pad), 1, chunk_t), 0, block_b)
    bp, tp = jp.shape[0], jp.shape[1]
    Lp, qp = _ring_padded(q, nx, n_pad)
    x0 = jnp.zeros((bp, n_pad), jnp.float32)
    lens = _pad_to(lengths.astype(jnp.int32), 0, block_b)
    xs = reservoir_pallas(
        jp, x0, Lp, qp, lens, p, q,
        f=f, block_b=block_b, chunk_t=chunk_t,
        interpret=(backend == "interpret"),
    )
    return xs[:b, :t, :nx]


# ---------------------------------------------------------------------------
# Fused training forward (reservoir -> DPRR aux, no materialized X)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("n_nodes", "f", "block_b", "chunk_t", "backend")
)
def train_forward(
    j_seq: jax.Array,      # (B, T, Nx) or (T, Nx) masked inputs
    lengths: Optional[jax.Array],  # (B,) int32 (or None = full length)
    p: jax.Array,
    q: jax.Array,
    n_nodes: int,
    *,
    f: Callable[[jax.Array], jax.Array] = lambda z: z,
    block_b: int = 8,
    chunk_t: Optional[int] = None,
    backend: Optional[str] = None,
) -> tuple:
    """Fused training forward: ``(r, x_last, x_prev, j_last)`` in logical
    shapes, with the state sequence X never materialized (see
    kernels.train).  These are exactly the data-dependent ``ForwardAux``
    fields of ``core.backprop`` — the truncated-BP production path
    (``backprop.forward_fused`` wraps this in the custom-VJP layer).

    ``chunk_t=None`` sizes the sequential time chunk to the window (capped
    at 128) like ``streaming_logits``; ``block_b`` tiles the batch axis of
    the Pallas grid.  The XLA backend ignores both (its single fused scan
    has no tiling).
    """
    backend = _auto_backend(backend)
    nx = j_seq.shape[-1]
    assert nx == n_nodes
    if backend == "xla" or j_seq.ndim == 2:
        # the Pallas grid is batched; the unbatched (T, Nx) form only
        # occurs on host-side call sites, which the scan serves directly
        return train_forward_scan(j_seq, lengths, p, q, f=f)

    b, t = j_seq.shape[0], j_seq.shape[1]
    if lengths is None:
        lengths = jnp.full((b,), t, jnp.int32)
    if chunk_t is None:
        chunk_t = min(128, -(-t // 8) * 8)
    n_pad = max(128, -(-nx // 128) * 128)
    jp = _pad_to(_pad_to(_pad_to(j_seq.astype(jnp.float32), 2, n_pad),
                         1, chunk_t), 0, block_b)
    Lp, qp = _ring_padded(q, nx, n_pad)
    lens = _pad_to(jnp.clip(lengths.astype(jnp.int32), 0, t), 0, block_b)
    acc, x_last, x_prev, j_last = train_forward_pallas(
        jp, Lp, qp, lens, p, q, nx,
        f=f, block_b=block_b, chunk_t=chunk_t,
        interpret=(backend == "interpret"),
    )
    dt = j_seq.dtype
    outer = acc[:b, :nx, :nx].reshape(b, nx * nx)
    sums = acc[:b, :nx, nx]
    r = jnp.concatenate([outer, sums], axis=-1).astype(dt)
    return (r, x_last[:b, :nx].astype(dt), x_prev[:b, :nx].astype(dt),
            j_last[:b, :nx].astype(dt))


# ---------------------------------------------------------------------------
# Fused streaming step (reservoir -> DPRR -> readout, one kernel call)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("n_nodes", "f", "chunk_t", "backend")
)
def streaming_logits(
    j_seq: jax.Array,      # (B, T, Nx) masked inputs
    lengths: jax.Array,    # (B,) int32
    p: jax.Array,
    q: jax.Array,
    W: jax.Array,          # (Ny, Nr) readout weights
    b: jax.Array,          # (Ny,) readout bias
    n_nodes: int,
    *,
    f: Callable[[jax.Array], jax.Array] = lambda z: z,
    chunk_t: Optional[int] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Batched readout logits (B, Ny) in one fused kernel call.

    The serving path's infer-before-update: reservoir scan, DPRR
    accumulation and the readout contraction run back to back with the
    recurrent state and the accumulator tile resident in VMEM - the state
    sequence X is never materialized (see kernels.streaming).

    ``chunk_t=None`` sizes the sequential time chunk to the window (capped
    at 128), so short serving windows don't pay for zero-padded kernel
    steps; pass an explicit value to pin the chunking.
    """
    backend = _auto_backend(backend)
    if backend == "xla":
        return kref.streaming_logits_ref(j_seq, lengths, p, q, W, b, f=f)

    bsz, t, nx = j_seq.shape
    assert nx == n_nodes
    if chunk_t is None:
        chunk_t = min(128, -(-t // 8) * 8)
    ny = W.shape[0]
    n_pad = max(128, -(-nx // 128) * 128)
    ny_pad = max(8, -(-ny // 8) * 8)
    jp, lens = _pad_stream_batch(j_seq, lengths, n_pad, chunk_t)
    Lp, qp = _ring_padded(q, nx, n_pad)
    # readout tile w3 in the accumulator's (i, j) layout: dot-product block
    # at [:nx, :nx], sum block down the ones column j = nx
    Wblk = W[:, : nx * nx].reshape(ny, nx, nx).astype(jnp.float32)
    Wsum = W[:, nx * nx :].astype(jnp.float32)
    w3 = jnp.zeros((ny_pad, n_pad, n_pad), jnp.float32)
    w3 = w3.at[:ny, :nx, :nx].set(Wblk)
    w3 = w3.at[:ny, :nx, nx].set(Wsum)
    out = streaming_step_pallas(
        jp, Lp, qp, lens, p, q, w3, nx,
        f=f, chunk_t=chunk_t, interpret=(backend == "interpret"),
    )
    return out[:bsz, :ny] + b


@functools.partial(
    jax.jit, static_argnames=("n_nodes", "f", "chunk_t", "backend")
)
def streaming_logits_slots(
    j_seq: jax.Array,      # (S, B, T, Nx) masked inputs, slot axis leading
    lengths: jax.Array,    # (S, B) int32
    p: jax.Array,          # (S,) per-slot reservoir gains
    q: jax.Array,          # (S,)
    W: jax.Array,          # (S, Ny, Nr) per-slot readout weights
    b: jax.Array,          # (S, Ny)
    n_nodes: int,
    *,
    f: Callable[[jax.Array], jax.Array] = lambda z: z,
    chunk_t: Optional[int] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Slot-axis batched ``streaming_logits``: (S, B, Ny) in one dispatch.

    The stream server's fused-infer path serves S independent slots, each
    with its own (p, q, W, b); this wrapper owns the slot-axis batching
    contract (one vmapped program over the fused kernel dispatch) so the
    serving loop issues a single call instead of vmapping the public
    single-system API at every call site.

    Under the slot-sharded server (``StreamServer(devices=n)``) this runs
    *inside* ``shard_map``, so S here is the device-LOCAL slot count
    (global S / n) and the vmap stays collective-free - per-slot batching
    composes with slot sharding with no change to this wrapper."""
    return jax.vmap(
        lambda j_s, len_s, p_s, q_s, W_s, b_s: streaming_logits(
            j_s, len_s, p_s, q_s, W_s, b_s, n_nodes,
            f=f, chunk_t=chunk_t, backend=backend,
        )
    )(j_seq, lengths, p, q, W, b)


@functools.partial(
    jax.jit, static_argnames=("n_nodes", "f", "chunk_t", "backend")
)
def streaming_logits_q8(
    j_seq: jax.Array,      # (B, T, Nx) masked inputs (any float dtype)
    lengths: jax.Array,    # (B,) int32
    p: jax.Array,          # scalar reservoir gain
    q: jax.Array,          # scalar ring gain (quantized into ring codes here)
    Wq: jax.Array,         # (Ny, Nr) int8 readout codes
    w_scale: jax.Array,    # scalar f32 readout scale (0 = unarmed)
    x_scale: jax.Array,    # scalar f32 reservoir-state scale (0 = unarmed)
    b: jax.Array,          # (Ny,) fp readout bias (stays fp)
    n_nodes: int,
    *,
    f: Callable[[jax.Array], jax.Array] = lambda z: z,
    chunk_t: Optional[int] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Quantized fused serving logits (B, Ny): the int8 fast path.

    Owns the whole code/scale prep so callers deal only in ``QuantParams``
    leaves: the ring matrix is built fp32 (ring-padded exactly like the
    fp32 kernel) and coded per call with its own scale - it depends only on
    the frozen ``q``, so XLA hoists the coding out of the serving loop -
    while the readout codes arrive pre-folded from the refresh boundary.

    Unarmed scales (0, i.e. no refresh has folded codes yet) are replaced
    by 1.0 so the program stays NaN-free; the serving caller must discard
    those slots' logits (``StreamServer`` selects fp32 logits until the
    slot arms).  Inputs are cast to fp32: the quantized path defines its
    own precision end to end, so bf16 configs feed it unchanged.
    """
    backend = _auto_backend(backend)
    bsz, t, nx = j_seq.shape
    assert nx == n_nodes
    if chunk_t is None:
        chunk_t = min(128, -(-t // 8) * 8)
    ny = Wq.shape[0]
    n_pad = max(128, -(-nx // 128) * 128)
    ny_pad = max(8, -(-ny // 8) * 8)
    jp, lens = _pad_stream_batch(j_seq.astype(jnp.float32), lengths, n_pad,
                                 chunk_t)
    Lp, qp = _ring_padded(q, nx, n_pad)
    sL = symmetric_scale(jnp.max(jnp.abs(Lp)))
    Lq8 = quantize_symmetric(Lp, sL)
    sx = jnp.where(x_scale > 0, x_scale, 1.0).astype(jnp.float32)
    sw = jnp.where(w_scale > 0, w_scale, 1.0).astype(jnp.float32)
    # readout codes in the accumulator's (i, j) layout (the int8 twin of
    # the fp32 w3 tile): dot-product block at [:nx, :nx], sums at j = nx
    Wblk = Wq[:, : nx * nx].reshape(ny, nx, nx)
    Wsum = Wq[:, nx * nx:]
    w3q = jnp.zeros((ny_pad, n_pad, n_pad), jnp.int8)
    w3q = w3q.at[:ny, :nx, :nx].set(Wblk)
    w3q = w3q.at[:ny, :nx, nx].set(Wsum)
    scales = jnp.stack([p.astype(jnp.float32), sx, sL, sw])
    if backend == "xla":
        out = kref.streaming_q8_sim(jp, Lq8, qp, lens, w3q, scales, nx, f=f)
    else:
        out = streaming_step_pallas_q8(
            jp, Lq8, qp, lens, w3q, scales, nx,
            f=f, chunk_t=chunk_t, interpret=(backend == "interpret"),
        )
    return out[:bsz, :ny] + b.astype(jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("n_nodes", "f", "chunk_t", "backend")
)
def streaming_logits_slots_q8(
    j_seq: jax.Array,      # (S, B, T, Nx) masked inputs, slot axis leading
    lengths: jax.Array,    # (S, B) int32
    p: jax.Array,          # (S,) per-slot reservoir gains
    q: jax.Array,          # (S,)
    Wq: jax.Array,         # (S, Ny, Nr) int8 per-slot readout codes
    w_scale: jax.Array,    # (S,) f32
    x_scale: jax.Array,    # (S,) f32
    b: jax.Array,          # (S, Ny)
    n_nodes: int,
    *,
    f: Callable[[jax.Array], jax.Array] = lambda z: z,
    chunk_t: Optional[int] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Slot-axis batched ``streaming_logits_q8``: (S, B, Ny) f32 in one
    dispatch - the int8 twin of ``streaming_logits_slots``, same
    slot-local contract under the sharded server (S is device-local inside
    ``shard_map``, no collectives)."""
    return jax.vmap(
        lambda j_s, len_s, p_s, q_s, Wq_s, ws_s, xs_s, b_s:
        streaming_logits_q8(
            j_s, len_s, p_s, q_s, Wq_s, ws_s, xs_s, b_s, n_nodes,
            f=f, chunk_t=chunk_t, backend=backend,
        )
    )(j_seq, lengths, p, q, Wq, w_scale, x_scale, b)


# ---------------------------------------------------------------------------
# Ridge solve
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block", "backend"))
def ridge_solve(
    A: jax.Array,
    B: jax.Array,
    *,
    block: int = 256,
    backend: Optional[str] = None,
) -> jax.Array:
    """W~ = A B^{-1} via blocked Cholesky + TRSMs, kernel-accelerated."""
    backend = _auto_backend(backend)
    if backend == "xla":
        return kref.ridge_solve_ref(A, B)
    return ridge_solve_blocked(A, B, block=block, interpret=(backend == "interpret"))


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "backend"),
)
def flash_attention(
    q: jax.Array,   # (B, H, Tq, D)
    k: jax.Array,   # (B, KV, Tk, D)
    v: jax.Array,   # (B, KV, Tk, D)
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 512,
    block_k: int = 1024,
    backend: Optional[str] = None,
) -> jax.Array:
    backend = _auto_backend(backend)
    if backend == "xla":
        return kref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, block_q=block_q,
        block_k=block_k, interpret=(backend == "interpret"),
    )


@functools.partial(jax.jit, static_argnames=("block", "backend"))
def cholesky(
    B: jax.Array, *, block: int = 256, backend: Optional[str] = None
) -> jax.Array:
    backend = _auto_backend(backend)
    if backend == "xla":
        return kref.chol_ref(B)
    return cholesky_blocked(B, block=block, interpret=(backend == "interpret"))


@functools.partial(jax.jit, static_argnames=("sign", "backend"))
def cholupdate_window(
    L: jax.Array,          # (s, s) or (K, s, s) live lower factor(s)
    X: jax.Array,          # (W, s) or (K, W, s) sample rows, stream order
    *,
    sign: float = 1.0,
    backend: Optional[str] = None,
) -> jax.Array:
    """Rank-1 rotate a window of sample rows into live Cholesky factor(s).

    Padding contract: s pads to the 128-lane tile with an identity diagonal
    on the factor and zero sample columns - zero rotations are exact no-ops,
    so the logical block is bit-equivalent to the unpadded sweep.
    """
    backend = _auto_backend(backend)
    batched = L.ndim == 3
    if backend == "xla":
        from repro.core import ridge as core_ridge

        if batched:
            return jax.vmap(
                lambda l, x: core_ridge.cholupdate_window(l, x, sign)
            )(L, X)
        return core_ridge.cholupdate_window(L, X, sign)

    from repro.core.ridge import pad_factor_identity
    from repro.kernels.cholupdate import cholupdate_block, cholupdate_block_batched

    s = L.shape[-1]
    n_pad = max(128, -(-s // 128) * 128)
    pad = n_pad - s
    if pad:
        L = pad_factor_identity(L, pad)
        X = _pad_to(X, X.ndim - 1, n_pad)
    interp = backend == "interpret"
    if batched:
        out = cholupdate_block_batched(L, X, sign=sign, interpret=interp)
        return out[:, :s, :s]
    out = cholupdate_block(L, X, sign=sign, interpret=interp)
    return out[:s, :s]
