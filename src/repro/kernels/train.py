"""Pallas TPU kernel: fused training-path forward (reservoir -> DPRR aux).

The training hot paths (population refinement, the serve step's truncated-BP
branch, the warm-pool autotuner's candidate scoring) need exactly four
things from a forward pass — the DPRR feature vector ``r`` plus the three
truncation boundary tensors ``x(T)``, ``x(T-1)``, ``j(T)`` — yet the unfused
composition (``kernels.reservoir`` then ``kernels.dprr``, or the core
``run_reservoir`` scan then ``compute_dprr``) materializes the full state
sequence X (B, T, Nx) in HBM between the two passes just so the reduction
and the boundary gathers can re-read it.  That is precisely the recursive
memory expansion the paper's truncated backpropagation exists to eliminate
(Sec. 3.4, Table 7: the FPGA keeps only x(T-1), x(T)).

This kernel is the serving kernel's training twin (``kernels.streaming``):
one ``pallas_call`` runs the whole time loop with the recurrent state block
(block_b, n_pad) and the per-sample DPRR accumulator tiles
(block_b, n_pad, n_pad) resident in VMEM, and instead of contracting the
accumulator against readout weights it emits the accumulator itself plus
the truncation boundary rows:

    per sample:  acc    (n_pad, n_pad)   DPRR accumulator (r in tile layout)
                 x_last (n_pad,)         x(T)   — final frozen state
                 x_prev (n_pad,)         x(T-1) — state *entering* step T
                 j_last (n_pad,)         j(T)   — input row of step T

X never exists anywhere: per-sample activation memory is O(Nx^2) for the
accumulator and O(Nx) for the state/boundary rows, independent of T —
mirroring the FPGA dataflow where the DPRR MACs are wired directly to the
reservoir ring and only the two boundary states are latched for training.

Accumulator fold: each time step stores its live-masked row x(k) and its
augmented row [x(k-1), 1] into two VMEM stacks of one time chunk
(chunk_t * block_b, n_pad), step-major; after the chunk's time loop, each
sample's accumulator takes one MXU contraction over the chunk axis,
``acc[b] += X1_b^T . X0_b``, its rows read at stride block_b.  So the
accumulator costs one (chunk_t, n_pad)^T x (chunk_t, n_pad) matmul per
sample and chunk instead of a (block_b, n_pad, n_pad) vector outer-product
update every step; the stacks are bounded by the chunk, not by T.

Boundary capture: step k = length-1 is recognized inside the time loop
(``k_global == length - 1``) and latches (x_prev, j_k) into VMEM scratch
rows before the state update, so ``x_prev`` is exactly the ``forward()``
gather ``x[length-2]`` (zero when length == 1, because the latched value is
then the initial state).  Dead steps (k >= length) freeze the state and
stack a zero row x(k), so they add nothing to the accumulator, matching
``compute_dprr``'s row masking.

Grid: (batch_blocks, time_chunks), time minor/sequential so the scratch
carries across chunks (re-initialized at chunk 0 of every batch block).
Dead chunks are skipped: a scalar-prefetch operand gives each batch block
its live chunk count ceil(max(lengths) / chunk_t), the time loop and the
fold run only in chunks below it, and the input index of a dead chunk is
clamped to the block's last live one, so no new DMA starts.  Every step of
a skipped chunk has k >= every length in its block: it would freeze the
state, stack only zero rows x(k) (a fold that adds exactly zero) and never
latch the boundary, so the outputs are bit for bit those of the full grid
(for finite states).  The init at chunk 0 and the emit at the last chunk
stay outside the gate.
The ring-mix dot and the fold run at ``Precision.HIGHEST``: the chip's
default f32 matmul rounds its operands to bf16, which the recurrence would
carry through every step.  Same ring-padding contract as the other kernels
(``ops._ring_padded``):
L/qpow are built for the padded node count with the true last node
mirrored into the last padded lane so the in-kernel ring wrap
``x_prev[:, -1:]`` reads node Nx-1.

``train_forward_scan`` is the XLA fallback with the same fusion: an outer
``lax.scan`` over fixed-size time chunks carries (state, accumulator,
boundary latches); each outer step runs the recurrence for one chunk and
folds its DPRR contributions into the accumulator with a single K=chunk
contraction.  Chunks that provably precede every sample's boundary take a
mask-free fast path (``lax.cond``), so the steady-state inner step is
exactly the bare ring recurrence.  Per-sample activation memory is
O(Nx^2 + chunk*Nx) — bounded by the fixed chunk, independent of T — so
the no-X property holds on every backend.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import reservoir as core_res
from repro.core.types import DOT_PRECISION


def _train_forward_kernel(
    nlive_ref,   # SMEM (batch_blocks,) int32: time chunks holding a live step
    j_ref,       # (chunk_t, block_b, n_pad) masked inputs for this block
    L_ref,       # (n_pad, n_pad) ring matrix (zero padded, ring lane mirrored)
    qpow_ref,    # (1, n_pad) ring powers
    len_ref,     # (block_b, 1) int32 valid lengths
    pq_ref,      # (1, 2) f32: [p, q] (q folded into L/qpow)
    acc_ref,     # out (block_b, n_pad, n_pad) DPRR accumulators
    xlast_ref,   # out (block_b, n_pad) x(T)
    xprev_ref,   # out (block_b, n_pad) x(T-1)
    jlast_ref,   # out (block_b, n_pad) j(T)
    state,       # VMEM scratch (block_b, n_pad) recurrent state
    acc,         # VMEM scratch (block_b, n_pad, n_pad) DPRR accumulators
    bnd_x,       # VMEM scratch (block_b, n_pad) boundary latch x(T-1)
    bnd_j,       # VMEM scratch (block_b, n_pad) boundary latch j(T)
    x1s,         # VMEM scratch (chunk_t * block_b, n_pad) rows x(k), masked
    x0s,         # VMEM scratch (chunk_t * block_b, n_pad) rows [x(k-1), 1]
    *,
    f: Callable[[jax.Array], jax.Array],
    chunk_t: int,
    n_nodes: int,
):
    bb = pl.program_id(0)
    tc = pl.program_id(1)
    block_b, n_pad = state.shape

    @pl.when(tc == 0)
    def _init():
        state[...] = jnp.zeros_like(state)   # x(0) = 0 (paper Sec. 2.2)
        acc[...] = jnp.zeros_like(acc)
        bnd_x[...] = jnp.zeros_like(bnd_x)
        bnd_j[...] = jnp.zeros_like(bnd_j)

    p = pq_ref[0, 0]
    Lt = L_ref[...].T
    qpow = qpow_ref[...]
    lens = len_ref[...]                           # (block_b, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, n_pad), 1)
    ones_col = jnp.where(col == n_nodes, 1.0, 0.0)

    def step(t, _):
        x_prev = state[...]
        j_k = j_ref[t, :, :]                      # (block_b, n_pad)
        a = p * f(j_k + x_prev)
        x_k = jax.lax.dot(a, Lt, precision=DOT_PRECISION,
                          preferred_element_type=jnp.float32) \
            + x_prev[:, -1:] * qpow
        k_global = tc * chunk_t + t
        # latch the truncation boundary BEFORE the state update: at the
        # last live step, x_prev is x(T-1) and j_k is j(T)
        is_bnd = k_global == lens - 1
        bnd_x[...] = jnp.where(is_bnd, x_prev, bnd_x[...])
        bnd_j[...] = jnp.where(is_bnd, j_k, bnd_j[...])
        live = k_global < lens
        x_k = jnp.where(live, x_k, x_prev)        # freeze past valid length
        # the DPRR pair of step k, x(k) and [x(k-1), 1], masked to the true
        # nodes: a frozen (dead) step stacks a zero row x(k), so it adds
        # exactly zero in the fold, matching compute_dprr's row masking
        rows = pl.ds(pl.multiple_of(t * block_b, block_b), block_b)
        x1s[rows, :] = jnp.where((col < n_nodes) & live, x_k, 0.0)
        x0s[rows, :] = jnp.where(col < n_nodes, x_prev, ones_col)
        state[...] = x_k
        return 0

    # a chunk past the block's longest length would only freeze the state
    # and add zeros: skip it (its input index is clamped, so no DMA either)
    @pl.when(tc < nlive_ref[bb])
    def _run():
        jax.lax.fori_loop(0, chunk_t, step, 0)
        # fold the chunk: acc[i] += X1_i^T . X0_i over the chunk's steps,
        # sample i's rows at stride block_b in the step-major stacks
        for i in range(block_b):
            rows = pl.ds(i, chunk_t, stride=block_b)
            acc[i] += jax.lax.dot_general(
                x1s[rows, :], x0s[rows, :],
                dimension_numbers=(((0,), (0,)), ((), ())),
                precision=DOT_PRECISION, preferred_element_type=jnp.float32,
            )

    @pl.when(tc == pl.num_programs(1) - 1)
    def _emit():
        acc_ref[...] = acc[...]
        xlast_ref[...] = state[...]
        xprev_ref[...] = bnd_x[...]
        jlast_ref[...] = bnd_j[...]


def train_forward_pallas(
    j_seq: jax.Array,     # (B, T_pad, n_pad) f32; node padding must be zero
    L: jax.Array,         # (n_pad, n_pad) ring matrix, zero padded + mirrored
    qpow: jax.Array,      # (n_pad,)
    lengths: jax.Array,   # (B,) int32
    p: jax.Array,         # scalar
    q: jax.Array,         # scalar
    n_nodes: int,
    *,
    f: Callable[[jax.Array], jax.Array] = lambda z: z,
    block_b: int = 8,
    chunk_t: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused training forward on padded shapes.

    Returns ``(acc, x_last, x_prev, j_last)`` with shapes
    ``(B, n_pad, n_pad)``, ``(B, n_pad)`` x3.  ``ops.train_forward`` owns
    the padding, the length sort and the accumulator -> r conversion.
    Each batch block runs only its first ceil(max(lengths) / chunk_t)
    time chunks; the outputs are those of the full grid.
    """
    b, t_pad, n_pad = j_seq.shape
    assert t_pad % chunk_t == 0, (t_pad, chunk_t)
    assert b % block_b == 0, (b, block_b)
    assert n_pad % 128 == 0 and n_nodes < n_pad
    jt = jnp.swapaxes(j_seq, 0, 1)  # (T, B, N): time-major for the grid

    kernel = functools.partial(
        _train_forward_kernel, f=f, chunk_t=chunk_t, n_nodes=n_nodes
    )
    pq = jnp.stack([p.astype(jnp.float32), q.astype(jnp.float32)]).reshape(1, 2)
    lens = lengths.astype(jnp.int32)
    # per batch block: the time chunks that hold a live step of any row
    n_live = -(-jnp.max(lens.reshape(-1, block_b), axis=1) // chunk_t)

    def j_index(bb, tc, n_live):
        # a dead chunk re-reads the block's last live one: no new DMA
        return (jnp.minimum(tc, jnp.maximum(n_live[bb] - 1, 0)), bb, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b // block_b, t_pad // chunk_t),
        in_specs=[
            pl.BlockSpec((chunk_t, block_b, n_pad), j_index),
            pl.BlockSpec((n_pad, n_pad), lambda bb, tc, n_live: (0, 0)),
            pl.BlockSpec((1, n_pad), lambda bb, tc, n_live: (0, 0)),
            pl.BlockSpec((block_b, 1), lambda bb, tc, n_live: (bb, 0)),
            pl.BlockSpec((1, 2), lambda bb, tc, n_live: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, n_pad, n_pad),
                         lambda bb, tc, n_live: (bb, 0, 0)),
            pl.BlockSpec((block_b, n_pad), lambda bb, tc, n_live: (bb, 0)),
            pl.BlockSpec((block_b, n_pad), lambda bb, tc, n_live: (bb, 0)),
            pl.BlockSpec((block_b, n_pad), lambda bb, tc, n_live: (bb, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b, n_pad), jnp.float32),
            pltpu.VMEM((block_b, n_pad, n_pad), jnp.float32),
            pltpu.VMEM((block_b, n_pad), jnp.float32),
            pltpu.VMEM((block_b, n_pad), jnp.float32),
            pltpu.VMEM((chunk_t * block_b, n_pad), jnp.float32),
            pltpu.VMEM((chunk_t * block_b, n_pad), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, n_pad, n_pad), jnp.float32),
            jax.ShapeDtypeStruct((b, n_pad), jnp.float32),
            jax.ShapeDtypeStruct((b, n_pad), jnp.float32),
            jax.ShapeDtypeStruct((b, n_pad), jnp.float32),
        ],
        interpret=interpret,
    )(n_live, jt, L, qpow.reshape(1, -1), lens.reshape(-1, 1), pq)


#: time steps folded per accumulator contraction in the XLA fallback — a
#: bounded (T-independent) window, NOT a full-T materialization.  64 keeps
#: the per-chunk stack at 64*Nx floats per sample while turning the
#: accumulator update into one K=64 GEMM per chunk instead of 64 reads and
#: writes of the (Nx, Nx+1) carry (the per-step version doubled the HBM
#: traffic of the baseline and lost wall-clock on CPU at Nx=16).
SCAN_CHUNK = 64


def train_forward_scan(
    j_seq: jax.Array,               # (B, T, Nx) or (T, Nx) masked inputs
    lengths: Optional[jax.Array],   # (B,) int32, or scalar, or None
    p: jax.Array,
    q: jax.Array,
    *,
    f: Callable[[jax.Array], jax.Array] = lambda z: z,
    chunk: int = SCAN_CHUNK,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """XLA twin of the fused kernel on logical shapes: a chunked lax.scan.

    The outer scan carries the recurrent state, the f32 DPRR accumulators
    (the (Nx, Nx) outer-product sum and the (Nx,) state sum — kept
    separate so no ones-column ever has to be concatenated) and the two
    boundary latches; each outer step runs ``chunk`` reservoir updates in
    an inner scan and folds their DPRR contributions into the
    accumulators with one contraction over the chunk axis.  The x(k) /
    x(k-1) pairing is expressed as shifted slices of the chunk-local
    stack plus one rank-1 term for the chunk's first step, so the fold
    allocates no shifted copy.  A chunk that ends strictly before every
    sample's boundary (k + chunk < min(lengths)) takes a ``lax.cond``
    fast path whose inner step is the bare ring recurrence — no
    live/boundary compares or wheres — so for long sequences the masking
    cost is confined to the boundary- and padding-holding chunks.
    Per-sample activation memory is O(Nx^2 + chunk*Nx) — bounded by the
    fixed chunk, independent of T: the full state sequence X is never
    stacked.  Returns logical ``(r, x_last, x_prev, j_last)``.
    """
    batched = j_seq.ndim == 3
    jt = jnp.swapaxes(j_seq, 0, 1) if batched else j_seq  # (T, [B,] Nx)
    t_len = jt.shape[0]
    n_nodes = jt.shape[-1]
    dt = jt.dtype
    if lengths is None:
        lengths = jnp.full(j_seq.shape[:-2], t_len, jnp.int32)
    L = core_res.ring_matrix(q, n_nodes, dt)
    qpow = core_res.ring_powers(q, n_nodes, dt)
    Lt = L.T

    # zero-pad T to a chunk multiple: padded steps have k >= lengths for
    # every sample, so the state freezes, the live mask zeroes their DPRR
    # rows and the boundary latch (k == length-1 < T) can never fire —
    # the pad is exactly dead compute, never a value change
    chunk = max(1, min(int(chunk), t_len))
    n_chunks = -(-t_len // chunk)
    if n_chunks * chunk != t_len:
        pad = jnp.zeros((n_chunks * chunk - t_len, *jt.shape[1:]), dt)
        jt = jnp.concatenate([jt, pad], axis=0)
    jc = jt.reshape(n_chunks, chunk, *jt.shape[1:])
    steps_idx = jnp.arange(chunk, dtype=jnp.int32)

    x0 = jnp.zeros_like(jt[0])
    out0 = jnp.zeros((*x0.shape, n_nodes), jnp.float32)
    sum0 = jnp.zeros(x0.shape, jnp.float32)
    carry0 = (x0, jnp.zeros((), jnp.int32), out0, sum0, x0,
              jnp.zeros_like(jt[0]))

    eq = "cbn,cbm->bnm" if batched else "cn,cm->nm"

    def fold(out_a, sum_a, x_in, xs, x1m):
        # sum_k x(k).x(k-1)^T over the chunk: the shifted pairing is
        # slices of the same stack (x1m[k] pairs with xs[k-1]) plus the
        # chunk-seam term x1m[0].x_in^T; the state sum rides separately
        out_a = out_a + jnp.einsum(eq, x1m[1:], xs[:-1].astype(jnp.float32),
                                   precision=DOT_PRECISION)
        out_a = out_a + (x1m[0][..., :, None]
                         * x_in.astype(jnp.float32)[..., None, :])
        return out_a, sum_a + x1m.sum(axis=0)

    def chunk_step(carry, j_chunk):
        x_in, k0, out_a, sum_a, x_bnd, j_bnd = carry

        def fast(operand):
            # every step of the chunk is live for every sample and no
            # boundary can latch: the bare ring recurrence, mask-free
            x_in, out_a, sum_a, x_bnd, j_bnd = operand

            def step(x_prev, j_k):
                a = p * f(j_k + x_prev)
                x_k = (jnp.matmul(a, Lt, precision=DOT_PRECISION)
                       + x_prev[..., -1:] * qpow)
                return x_k, x_k

            x_out, xs = jax.lax.scan(step, x_in, j_chunk)
            out_a, sum_a = fold(out_a, sum_a, x_in, xs,
                                xs.astype(jnp.float32))
            return x_out, out_a, sum_a, x_bnd, j_bnd

        def slow(operand):
            x_in, out_a, sum_a, x_bnd, j_bnd = operand

            def step(c2, j_k):
                x_prev, k, x_bnd, j_bnd = c2
                a = p * f(j_k + x_prev)
                x_k = (jnp.matmul(a, Lt, precision=DOT_PRECISION)
                       + x_prev[..., -1:] * qpow)
                is_bnd = k == lengths - 1
                live = k < lengths
                if batched:
                    is_bnd, live = is_bnd[..., None], live[..., None]
                x_bnd = jnp.where(is_bnd, x_prev, x_bnd)
                j_bnd = jnp.where(is_bnd, j_k, j_bnd)
                x_k = jnp.where(live, x_k, x_prev)
                return (x_k, k + 1, x_bnd, j_bnd), x_k

            (x_out, _, x_bnd, j_bnd), xs = jax.lax.scan(
                step, (x_in, k0, x_bnd, j_bnd), j_chunk
            )
            ks = k0 + steps_idx
            if batched:
                live_c = (ks[:, None] < lengths[None, :])[..., None]
            else:
                live_c = (ks < lengths)[..., None]
            x1m = jnp.where(live_c, xs, jnp.zeros((), dt)).astype(jnp.float32)
            out_a, sum_a = fold(out_a, sum_a, x_in, xs, x1m)
            return x_out, out_a, sum_a, x_bnd, j_bnd

        # fast iff the whole chunk is strictly before every boundary
        # (k0 + chunk - 1 < lengths - 1 for all samples); the predicate
        # never touches vmapped member params, so cond survives vmap
        pred = k0 + chunk < jnp.min(lengths)
        x_out, out_a, sum_a, x_bnd, j_bnd = jax.lax.cond(
            pred, fast, slow, (x_in, out_a, sum_a, x_bnd, j_bnd)
        )
        return (x_out, k0 + chunk, out_a, sum_a, x_bnd, j_bnd), None

    (x_last, _, out_a, sum_a, x_bnd, j_bnd), _ = jax.lax.scan(
        chunk_step, carry0, jc
    )
    outer = out_a.reshape(*out_a.shape[:-2], n_nodes * n_nodes)
    r = jnp.concatenate([outer, sum_a], axis=-1).astype(dt)
    return r, x_last, x_bnd, j_bnd
