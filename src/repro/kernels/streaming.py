"""Pallas TPU kernel: fused single-window streaming step (paper Sec. 3.1).

The latency-critical operation of the serving path is infer-before-update:
for each freshly arrived window the system must produce predictions from
the *current* parameters before the training update touches them.  The
two-kernel composition (``kernels.reservoir`` then ``kernels.dprr``) round-
trips the full state sequence X (B, T, Nx) through HBM between the two
calls; this kernel fuses the whole read path

    reservoir scan -> DPRR accumulation -> readout logits

into ONE ``pallas_call``: the recurrent state block (block_b, n_pad) and
the per-sample DPRR accumulator tiles (block_b, n_pad, n_pad) live in VMEM
scratch for the whole time loop, so X is never materialized anywhere - HBM
traffic is one read of the masked inputs J plus one (ny_pad,) logits write
per sample.  That is the TPU analogue of the paper's FPGA dataflow, where
the reservoir, DPRR and output MACs are wired back to back with no DRAM in
between.

Grid: (batch_blocks, time_chunks), the layout of ``kernels.train``; time
is the minor (sequential) dimension so the scratch carries across chunks,
re-initialized at chunk 0 of every batch block.  A batch block is either
the whole batch (B <= 8: the serving window) or 8 rows, so every block's
last two dimensions are ones the chip's (8, 128) tiling accepts
(``ops.streaming_logits`` pads B to a multiple of 8 above that; pad rows
have length 0 and come out as zero logits).  The readout weights arrive
pre-laid-out as a (ny_pad, n_pad, n_pad) tile w3 matching the
accumulator's layout (``ops.streaming_logits`` builds it): w3[y, i, j] =
W[y, i*Nx + j] for the dot-product block and w3[y, i, Nx] = W[y, Nx^2 + i]
for the sum block, so each sample's logits are one (1, n_pad^2) x
(n_pad^2, ny_pad) MXU contraction of its flattened accumulator.  The bias
is added by the wrapper.

The fp32 kernel's dots run at ``Precision.HIGHEST``: the chip's default
f32 matmul rounds its operands to bf16, and the recurrence would carry
that rounding through every step of the window.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))   # contract the last dims: a @ b.T


def batch_block(b: int) -> int:
    """Rows per grid block: the whole batch up to 8, else 8 (B % 8 == 0)."""
    return b if b <= 8 else 8


def _readout_rows(acc, w, out_ref, precision):
    """Per-sample logits: flattened (1, n_pad^2) accumulator x w^T."""
    n_pad = acc.shape[-1]
    for r in range(acc.shape[0]):
        flat = acc[r].reshape(1, n_pad * n_pad)
        out_ref[r:r + 1, :] = jax.lax.dot_general(
            flat, w, _NT, precision=precision,
            preferred_element_type=jnp.float32,
        )


def _streaming_kernel(
    j_ref,       # (chunk_t, block_b, n_pad) masked inputs
    L_ref,       # (n_pad, n_pad) ring matrix (zero padded, ring lane mirrored)
    qpow_ref,    # (1, n_pad) ring powers
    len_ref,     # (block_b, 1) int32 valid lengths
    pq_ref,      # (1, 2) f32: [p, q] (q folded into L/qpow)
    w3_ref,      # (ny_pad, n_pad, n_pad) readout tile
    out_ref,     # (block_b, ny_pad) logits (written at the last time chunk)
    state,       # VMEM scratch (block_b, n_pad) recurrent state
    acc,         # VMEM scratch (block_b, n_pad, n_pad) DPRR accumulators
    *,
    f: Callable[[jax.Array], jax.Array],
    chunk_t: int,
    n_nodes: int,
):
    tc = pl.program_id(1)
    n_pad = state.shape[-1]

    @pl.when(tc == 0)
    def _init():
        state[...] = jnp.zeros_like(state)   # x(0) = 0 (paper Sec. 2.2)
        acc[...] = jnp.zeros_like(acc)

    p = pq_ref[0, 0]
    L = L_ref[...]
    qpow = qpow_ref[...]
    lens = len_ref[...]                           # (block_b, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, n_pad), 1)

    def step(t, _):
        x_prev = state[...]
        j_k = j_ref[t, :, :]                      # (block_b, n_pad)
        a = p * f(j_k + x_prev)
        x_k = jax.lax.dot_general(
            a, L, _NT, precision=_HI, preferred_element_type=jnp.float32
        ) + x_prev[:, -1:] * qpow
        live = tc * chunk_t + t < lens
        x_k = jnp.where(live, x_k, x_prev)        # freeze past valid length
        # DPRR contribution of step k: x(k) . [x(k-1), 1]^T per sample,
        # masked to the true nodes; a frozen (dead) step contributes
        # exactly zero, matching compute_dprr's row masking.
        x1m = jnp.where((col < n_nodes) & live, x_k, 0.0)
        x0_aug = jnp.where(
            col < n_nodes, x_prev, jnp.where(col == n_nodes, 1.0, 0.0)
        )
        acc[...] += x1m[:, :, None] * x0_aug[:, None, :]
        state[...] = x_k
        return 0

    jax.lax.fori_loop(0, chunk_t, step, 0)

    @pl.when(tc == pl.num_programs(1) - 1)
    def _readout():
        w = w3_ref[...].reshape(w3_ref.shape[0], n_pad * n_pad)
        _readout_rows(acc, w, out_ref, _HI)


def _streaming_kernel_q8(
    j_ref,       # (chunk_t, block_b, n_pad) masked inputs (fp32)
    Lq_ref,      # (n_pad, n_pad) int8 ring-matrix codes (scale sL)
    qpow_ref,    # (1, n_pad) f32 ring powers (the ring wrap stays fp32)
    len_ref,     # (block_b, 1) int32 valid lengths
    scal_ref,    # (1, 4) f32: [p, sx, sL, sw]
    w3q_ref,     # (ny_pad, n_pad, n_pad) int8 readout codes (scale sw)
    out_ref,     # (block_b, ny_pad) f32 logits (written at the last chunk)
    state,       # VMEM scratch (block_b, n_pad) int32 state *codes*
    acc,         # VMEM scratch (block_b, n_pad, n_pad) int32 DPRR codes
    *,
    f: Callable[[jax.Array], jax.Array],
    chunk_t: int,
    n_nodes: int,
):
    """Int8 variant of ``_streaming_kernel``: the reservoir mix and the DPRR
    accumulation run on symmetric int8 codes with int32 accumulation; only
    the nonlinearity, the ring wrap and the final readout dequant are fp32.
    Exact-math contract shared with ``ref.streaming_q8_sim`` (the oracle) -
    integer arithmetic carries no rounding, so the two agree bitwise on the
    codes and to fp rounding on the dequantized logits."""
    tc = pl.program_id(1)
    n_pad = state.shape[-1]

    @pl.when(tc == 0)
    def _init():
        state[...] = jnp.zeros_like(state)
        acc[...] = jnp.zeros_like(acc)

    p = scal_ref[0, 0]
    sx = scal_ref[0, 1]
    sL = scal_ref[0, 2]
    Lq = Lq_ref[...]
    qpow = qpow_ref[...]
    lens = len_ref[...]                           # (block_b, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, n_pad), 1)

    def step(t, _):
        xq_prev = state[...]                      # (block_b, n_pad) codes
        x_prev = xq_prev.astype(jnp.float32) * sx
        j_k = j_ref[t, :, :]
        a = p * f(j_k + x_prev)
        aq = jnp.clip(jnp.round(a / sx), -127, 127).astype(jnp.int8)
        y = jax.lax.dot_general(aq, Lq, _NT, preferred_element_type=jnp.int32)
        x_k = y.astype(jnp.float32) * (sx * sL) + x_prev[:, -1:] * qpow
        xq_k = jnp.clip(jnp.round(x_k / sx), -127, 127).astype(jnp.int32)
        live = tc * chunk_t + t < lens
        xq_k = jnp.where(live, xq_k, xq_prev)     # freeze in the code domain
        x1m = jnp.where((col < n_nodes) & live, xq_k, 0)
        x0_aug = jnp.where(
            col < n_nodes, xq_prev, jnp.where(col == n_nodes, 1, 0)
        )
        acc[...] += x1m[:, :, None] * x0_aug[:, None, :]
        state[...] = xq_k
        return 0

    jax.lax.fori_loop(0, chunk_t, step, 0)

    @pl.when(tc == pl.num_programs(1) - 1)
    def _readout():
        sw = scal_ref[0, 3]
        # dequantize per accumulator column (x columns sx^2, ones column sx)
        colscale = jnp.where(
            col == n_nodes, sx, sx * sx).astype(jnp.float32)
        racc = acc[...].astype(jnp.float32) * colscale[:, None, :]
        w = w3q_ref[...].reshape(
            w3q_ref.shape[0], n_pad * n_pad).astype(jnp.float32) * sw
        _readout_rows(racc, w, out_ref, _HI)


def _fused_call(kernel, state_dtype, j_seq, mats, lengths, scalars, w3, *,
                chunk_t, interpret):
    """Shared grid/BlockSpec plumbing of the fp32 and int8 fused steps."""
    b, t_pad, n_pad = j_seq.shape
    ny_pad = w3.shape[0]
    block_b = batch_block(b)
    assert t_pad % chunk_t == 0, (t_pad, chunk_t)
    assert b % block_b == 0, (b, block_b)
    jt = jnp.swapaxes(j_seq, 0, 1)  # (T, B, N): time-major for the grid
    L, qpow = mats
    return pl.pallas_call(
        kernel,
        grid=(b // block_b, t_pad // chunk_t),
        in_specs=[
            pl.BlockSpec((chunk_t, block_b, n_pad), lambda bb, tc: (tc, bb, 0)),
            pl.BlockSpec((n_pad, n_pad), lambda bb, tc: (0, 0)),
            pl.BlockSpec((1, n_pad), lambda bb, tc: (0, 0)),
            pl.BlockSpec((block_b, 1), lambda bb, tc: (bb, 0)),
            pl.BlockSpec(scalars.shape, lambda bb, tc: (0, 0)),
            pl.BlockSpec((ny_pad, n_pad, n_pad), lambda bb, tc: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, ny_pad), lambda bb, tc: (bb, 0)),
        out_shape=jax.ShapeDtypeStruct((b, ny_pad), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_b, n_pad), state_dtype),
            pltpu.VMEM((block_b, n_pad, n_pad), state_dtype),
        ],
        interpret=interpret,
    )(jt, L, qpow.reshape(1, -1), lengths.astype(jnp.int32).reshape(-1, 1),
      scalars, w3)


def streaming_step_pallas(
    j_seq: jax.Array,     # (B, T_pad, n_pad) f32; node padding must be zero
    L: jax.Array,         # (n_pad, n_pad) ring matrix, zero padded + mirrored
    qpow: jax.Array,      # (n_pad,)
    lengths: jax.Array,   # (B,) int32
    p: jax.Array,         # scalar
    q: jax.Array,         # scalar
    w3: jax.Array,        # (ny_pad, n_pad, n_pad) readout tile
    n_nodes: int,
    *,
    f: Callable[[jax.Array], jax.Array] = lambda z: z,
    chunk_t: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Returns raw logits (B, ny_pad) (bias not yet added).

    B must be at most 8 or a multiple of 8 (``batch_block``).  Same
    ring-padding contract as ``kernels.reservoir.reservoir_pallas``:
    L/qpow are built for the padded node count with the true last node
    mirrored into the last padded lane (``ops.streaming_logits`` does this),
    so the in-kernel ring wrap ``x_prev[:, -1:]`` reads node Nx-1.
    """
    n_pad = j_seq.shape[-1]
    assert n_pad % 128 == 0 and n_nodes < n_pad
    kernel = functools.partial(
        _streaming_kernel, f=f, chunk_t=chunk_t, n_nodes=n_nodes
    )
    pq = jnp.stack([p.astype(jnp.float32), q.astype(jnp.float32)]).reshape(1, 2)
    return _fused_call(kernel, jnp.float32, j_seq, (L, qpow), lengths, pq,
                       w3, chunk_t=chunk_t, interpret=interpret)


def streaming_step_pallas_q8(
    j_seq: jax.Array,     # (B, T_pad, n_pad) f32; node padding must be zero
    Lq: jax.Array,        # (n_pad, n_pad) int8 ring-matrix codes
    qpow: jax.Array,      # (n_pad,) f32
    lengths: jax.Array,   # (B,) int32
    w3q: jax.Array,       # (ny_pad, n_pad, n_pad) int8 readout codes
    scales: jax.Array,    # (4,) f32: [p, sx, sL, sw] (all > 0)
    n_nodes: int,
    *,
    f: Callable[[jax.Array], jax.Array] = lambda z: z,
    chunk_t: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Quantized fused step: returns raw fp32 logits (B, ny_pad).

    Same grid/padding contract as ``streaming_step_pallas``; the VMEM
    residents shrink to int32 code tiles and the hot ring-mix dot runs on
    int8 operands.  ``ops.streaming_logits_q8`` owns the code/scale prep
    (ring codes from the fp32 ring matrix, readout codes from
    ``QuantParams``).
    """
    n_pad = j_seq.shape[-1]
    assert n_pad % 128 == 0 and n_nodes < n_pad
    kernel = functools.partial(
        _streaming_kernel_q8, f=f, chunk_t=chunk_t, n_nodes=n_nodes
    )
    return _fused_call(
        kernel, jnp.int32, j_seq,
        (Lq.astype(jnp.int8), qpow.astype(jnp.float32)), lengths,
        scales.astype(jnp.float32).reshape(1, 4), w3q.astype(jnp.int8),
        chunk_t=chunk_t, interpret=interpret,
    )
