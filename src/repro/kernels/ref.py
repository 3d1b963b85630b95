"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Each function mirrors one kernel's contract exactly (same padding, same
masking semantics) using only jax.numpy - no Pallas, no loops over scalars.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import dprr as core_dprr
from repro.core import reservoir as core_res
from repro.core.types import DOT_PRECISION


def dprr_ref(x: jax.Array, length: jax.Array, n_nodes: int) -> jax.Array:
    """Oracle of kernels.dprr.dprr_pallas: (T_pad, n_pad) -> (n_pad, n_pad)."""
    t_pad, n_pad = x.shape
    row = jnp.arange(t_pad)[:, None]
    col = jnp.arange(n_pad)[None, :]
    x1 = jnp.where((row < length) & (col < n_nodes), x, 0.0)
    x0 = jnp.pad(x, ((1, 0), (0, 0)))[:-1]
    x0_aug = jnp.where(col < n_nodes, x0, jnp.where(col == n_nodes, 1.0, 0.0))
    return x1.T @ x0_aug


def chol_ref(a: jax.Array) -> jax.Array:
    """Oracle of kernels.cholesky.chol_block."""
    return jnp.linalg.cholesky(a)


def trsm_lower_t_ref(a: jax.Array, L: jax.Array) -> jax.Array:
    """Oracle of kernels.cholesky.trsm_lower_t: X L^T = a."""
    return jax.scipy.linalg.solve_triangular(L, a.T, lower=True).T


def trsm_lower_ref(d: jax.Array, L: jax.Array) -> jax.Array:
    """Oracle of kernels.cholesky.trsm_lower: X L = d."""
    return jax.scipy.linalg.solve_triangular(L.T, d.T, lower=False).T


def ridge_solve_ref(A: jax.Array, B: jax.Array) -> jax.Array:
    """Oracle of kernels.ridge_solve.ridge_solve_blocked: A B^{-1}."""
    C = jnp.linalg.cholesky(B)
    D = jax.scipy.linalg.solve_triangular(C, A.T, lower=True)
    return jax.scipy.linalg.solve_triangular(C.T, D, lower=False).T


def flash_attention_ref(
    q: jax.Array,   # (B, H, Tq, D)
    k: jax.Array,   # (B, KV, Tk, D)
    v: jax.Array,   # (B, KV, Tk, D)
    *,
    causal: bool = True,
    window: int = 0,
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    """Oracle of kernels.flash_attention (dense masked softmax)."""
    b, h, tq, d = q.shape
    _, kv, tk, _ = k.shape
    g = h // kv
    scale = softmax_scale if softmax_scale is not None else d**-0.5
    qg = q.reshape(b, kv, g, tq, d).astype(jnp.float32)
    s = jnp.einsum("bkgtd,bksd->bkgts", qg, k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(tq)[:, None]
    k_pos = jnp.arange(tk)[None, :]
    mask = jnp.ones((tq, tk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgts,bksd->bkgtd", p, v.astype(jnp.float32))
    return out.reshape(b, h, tq, d).astype(q.dtype)


def streaming_logits_ref(
    j_seq: jax.Array,      # (B, T, Nx) masked inputs (logical shapes)
    lengths: jax.Array,    # (B,)
    p: jax.Array,
    q: jax.Array,
    W: jax.Array,          # (Ny, Nr)
    b: jax.Array,          # (Ny,)
    f: Callable[[jax.Array], jax.Array] = lambda z: z,
) -> jax.Array:
    """Oracle of kernels.streaming.streaming_step_pallas (+ bias): the
    unfused reservoir -> DPRR -> readout composition on logical shapes."""
    x = core_res.run_reservoir(p, q, j_seq, f=f, lengths=lengths)
    r = core_dprr.compute_dprr(x, lengths=lengths)
    return r @ W.T + b


def streaming_q8_sim(
    j_seq: jax.Array,      # (B, T_pad, n_pad) f32 masked inputs, zero padded
    Lq: jax.Array,         # (n_pad, n_pad) int8 ring-matrix codes (scale sL)
    qpow: jax.Array,       # (n_pad,) f32 ring powers (fp32 path, not coded)
    lengths: jax.Array,    # (B,) int32
    w3q: jax.Array,        # (ny_pad, n_pad, n_pad) int8 readout codes
    scales: jax.Array,     # (4,) f32: [p, sx, sL, sw] (all > 0)
    n_nodes: int,
    f: Callable[[jax.Array], jax.Array] = lambda z: z,
) -> jax.Array:
    """Oracle of kernels.streaming.streaming_step_pallas_q8: the quantized
    fused step's *exact* integer math on padded shapes.

    The int8 contract (shared bit-for-bit with the kernel - integer
    arithmetic is exact, so op order doesn't matter):

      * the recurrent state lives as int8 codes ``xq`` with scale ``sx``
        (dequantize, apply the fp32 nonlinearity, requantize - the
        nonlinearity and the ring wrap stay fp32, everything else is
        integer),
      * the reservoir mix is an int8 x int8 -> int32 dot against the coded
        ring matrix (scale ``sL``), dequantized by ``sx * sL``,
      * dead steps freeze in the *code* domain (bitwise no-op, matching the
        fp32 kernel's freeze),
      * the DPRR accumulator is int32 over code outer products; the ones
        column carries the integer constant 1 (exact), so its dequant
        scale is ``sx`` where the x-columns carry ``sx^2``,
      * the readout dequantizes the accumulator per column and contracts
        in fp32 against the dequantized int8 readout tile (scale ``sw``) -
        the "fp32 dequantized logits" half of the contract.

    Overflow headroom: reservoir dot <= 127^2 * n_pad, DPRR accumulator
    <= 127^2 * T per cell - both orders of magnitude inside int32.
    Returns raw logits (B, ny_pad), bias not yet added.
    """
    _, t_pad, n_pad = j_seq.shape
    ny_pad = w3q.shape[0]
    p, sx, sL, sw = scales[0], scales[1], scales[2], scales[3]
    col = jnp.arange(n_pad)
    LqT = Lq.astype(jnp.int8).T

    def one(jb, length):
        def step(carry, inp):
            xq_prev, acc = carry
            j_k, k = inp
            x_prev = xq_prev.astype(jnp.float32) * sx
            a = p * f(j_k + x_prev)
            aq = jnp.clip(jnp.round(a / sx), -127, 127).astype(jnp.int8)
            y = jax.lax.dot_general(
                aq[None, :], LqT,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )[0]
            x_k = y.astype(jnp.float32) * (sx * sL) + x_prev[-1] * qpow
            xq_k = jnp.clip(jnp.round(x_k / sx), -127, 127).astype(jnp.int32)
            live = k < length
            xq_k = jnp.where(live, xq_k, xq_prev)
            x1m = jnp.where((col < n_nodes) & live, xq_k, 0)
            x0_aug = jnp.where(col < n_nodes, xq_prev,
                               jnp.where(col == n_nodes, 1, 0))
            acc = acc + jax.lax.dot_general(
                x1m.astype(jnp.int8)[:, None], x0_aug.astype(jnp.int8)[:, None],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            return (xq_k, acc), None

        carry0 = (jnp.zeros((n_pad,), jnp.int32),
                  jnp.zeros((n_pad, n_pad), jnp.int32))
        (_, acc), _ = jax.lax.scan(
            step, carry0, (jb, jnp.arange(t_pad, dtype=jnp.int32))
        )
        # per-column dequant: x columns carry sx^2, the ones column sx
        colscale = jnp.where(col == n_nodes, sx, sx * sx)
        racc = acc.astype(jnp.float32) * colscale[None, :]
        w = w3q.reshape(ny_pad, n_pad * n_pad).astype(jnp.float32) * sw
        return racc.reshape(n_pad * n_pad) @ w.T

    return jax.vmap(one)(j_seq, lengths.astype(jnp.int32))


def train_forward_ref(
    j_seq: jax.Array,      # (B, T_pad, n_pad) f32 masked inputs, zero padded
    L: jax.Array,          # (n_pad, n_pad) ring matrix, zero padded + mirrored
    qpow: jax.Array,       # (n_pad,) f32 ring powers
    lengths: jax.Array,    # (B,) int32
    p: jax.Array,
    n_nodes: int,
    f: Callable[[jax.Array], jax.Array] = lambda z: z,
    *,
    chunk_t: int,
):
    """Oracle of kernels.train.train_forward_pallas on padded shapes.

    Mirrors the kernel's op sequence exactly (same dots on the same padded
    operands, same masking, same boundary latch order), so the
    interpret-mode kernel agrees with it bit for bit: a per-step scan
    yields the state, the latches and each step's DPRR pair (x(k) masked,
    [x(k-1), 1]); the accumulator then folds each ``chunk_t``-step chunk
    with one contraction per sample over the chunk axis, chunk after
    chunk, as the kernel does.  A dead chunk, which the kernel skips,
    holds only zero rows x(k) here and adds exactly zero.  Returns
    ``(acc, x_last, x_prev, j_last)`` in the kernel's padded layout.
    """
    b, t_pad, n_pad = j_seq.shape
    Lt = L.T
    col = jnp.arange(n_pad)[None, :]

    def one(jb, length):
        def step(carry, inp):
            x_prev, x_bnd, j_bnd = carry
            j_k, k = inp
            a = p.astype(jnp.float32) * f(j_k + x_prev)
            x_k = jax.lax.dot(
                a, Lt, preferred_element_type=jnp.float32
            ) + x_prev[:, -1:] * qpow[None, :]
            is_bnd = k == length - 1
            x_bnd = jnp.where(is_bnd, x_prev, x_bnd)
            j_bnd = jnp.where(is_bnd, j_k, j_bnd)
            live = k < length
            x_k = jnp.where(live, x_k, x_prev)
            x1m = jnp.where((col < n_nodes) & live, x_k, 0.0)
            x0_aug = jnp.where(
                col < n_nodes, x_prev, jnp.where(col == n_nodes, 1.0, 0.0)
            )
            return (x_k, x_bnd, j_bnd), (x1m[0], x0_aug[0])

        z_row = jnp.zeros((1, n_pad), jnp.float32)
        (x_last, x_bnd, j_bnd), pairs = jax.lax.scan(
            step, (z_row, z_row, z_row),
            (jb[:, None, :], jnp.arange(t_pad, dtype=jnp.int32)),
        )
        return pairs, x_last[0], x_bnd[0], j_bnd[0]

    (x1s, x0s), x_last, x_prev, j_last = jax.vmap(one)(
        j_seq, lengths.astype(jnp.int32))
    stacks = (x1s.reshape(b, t_pad // chunk_t, chunk_t, n_pad),
              x0s.reshape(b, t_pad // chunk_t, chunk_t, n_pad))

    def fold(acc, chunk):
        x1, x0 = chunk
        return acc + jax.lax.dot_general(
            x1, x0, dimension_numbers=(((0,), (0,)), ((), ())),
            precision=DOT_PRECISION, preferred_element_type=jnp.float32,
        ), None

    # one sample at a time (lax.map, not vmap): the unbatched dot the
    # kernel runs per sample
    acc = jax.lax.map(
        lambda c: jax.lax.scan(
            fold, jnp.zeros((n_pad, n_pad), jnp.float32), c)[0],
        stacks)
    return acc, x_last, x_prev, j_last


def reservoir_ref(
    j_seq: jax.Array,      # (B, T_pad, n_pad)
    x0: jax.Array,         # (B, n_pad)
    lengths: jax.Array,    # (B,)
    p: jax.Array,
    q: jax.Array,
    n_nodes: int,
    f: Callable[[jax.Array], jax.Array] = lambda z: z,
) -> jax.Array:
    """Oracle of kernels.reservoir.reservoir_pallas (true-node lanes only).

    Runs the core scan on the unpadded node slice and re-pads with zeros
    (+ the replicated ring lane, see kernels.reservoir docstring).
    """
    n_pad = j_seq.shape[-1]
    x = core_res.run_reservoir(
        p, q, j_seq[..., :n_nodes], x0[..., :n_nodes], f=f, lengths=lengths
    )
    out = jnp.pad(x, ((0, 0), (0, 0), (0, n_pad - n_nodes)))
    # replicate the ring lane as the kernel does
    return out.at[..., -1].set(x[..., n_nodes - 1])
