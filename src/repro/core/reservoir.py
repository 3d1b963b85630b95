"""Modular DFR reservoir forward pass (paper Eq. 14) in JAX.

Recurrence (with ring wrap x(k)_0 := x(k-1)_{Nx}):

    a(k)_n  = p * f(j(k)_n + x(k-1)_n)          # nonlinear branch
    x(k)_n  = a(k)_n + q * x(k)_{n-1}           # ring accumulation

The ring accumulation is a first-order linear recurrence along the node axis.
On an FPGA the paper pipelines the node loop; on TPU we exploit the closed
form

    x(k) = L(q) @ a(k) + q^{1..Nx} * x(k-1)_{Nx}

where L(q)[n, i] = q^(n-i) for i <= n (lower triangular).  One reservoir step
is therefore a small (Nx x Nx) GEMM batched over samples - an MXU-friendly
reorganization of the same dataflow (see DESIGN.md 'Hardware adaptation').

Two implementations are provided:
  * ``reservoir_step_naive`` - the per-node sequential reference (faithful to
    the paper's order of operations, used as the oracle),
  * ``run_reservoir`` - time-scan over the GEMM step (production path; the
    Pallas kernel in ``repro.kernels.reservoir`` fuses chunks of it).

The legacy *digital DFR* of Eq. (8)-(9) (exp(-theta) Euler step of the
Mackey-Glass delay ODE) is included as ``run_reservoir_legacy`` because the
paper compares against it (grid-search baselines run on the same modular
model, but Eq. 8-9 defines the pre-modular system).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.types import DOT_PRECISION, Array


def ring_matrix(q: Array, n_nodes: int, dtype=jnp.float32) -> Array:
    """L(q)[n, i] = q^(n-i) for i <= n else 0;  shape (Nx, Nx)."""
    n = jnp.arange(n_nodes)
    expo = n[:, None] - n[None, :]
    low = expo >= 0
    # q ** expo with masked negative exponents (avoid nan for q == 0)
    powed = jnp.where(low, jnp.abs(q) ** jnp.maximum(expo, 0), 0.0)
    sign = jnp.where(q < 0, jnp.where((expo % 2) == 1, -1.0, 1.0), 1.0)
    return (jnp.where(low, powed * sign, 0.0)).astype(dtype)


def ring_powers(q: Array, n_nodes: int, dtype=jnp.float32) -> Array:
    """[q^1, q^2, ..., q^Nx] - carries x(k-1)_{Nx} around the ring."""
    expo = jnp.arange(1, n_nodes + 1)
    powed = jnp.abs(q) ** expo
    sign = jnp.where(q < 0, jnp.where((expo % 2) == 1, -1.0, 1.0), 1.0)
    return (powed * sign).astype(dtype)


def reservoir_step_naive(
    p: Array, q: Array, f: Callable[[Array], Array], j_k: Array, x_prev: Array
) -> Array:
    """One time step, sequential over nodes (paper-faithful reference).

    j_k, x_prev: (Nx,) -> x_k: (Nx,)
    """
    n_nodes = x_prev.shape[-1]
    a = p * f(j_k + x_prev)  # (Nx,) nonlinear branch, depends on k-1 only

    def body(n, carry):
        x_k, ring = carry
        val = a[n] + q * ring
        x_k = x_k.at[n].set(val)
        return (x_k, val)

    x0 = jnp.zeros_like(x_prev)
    ring0 = x_prev[n_nodes - 1]  # x(k)_0 := x(k-1)_{Nx}
    x_k, _ = jax.lax.fori_loop(0, n_nodes, body, (x0, ring0))
    return x_k


def reservoir_step(
    p: Array,
    q: Array,
    f: Callable[[Array], Array],
    j_k: Array,
    x_prev: Array,
    L: Optional[Array] = None,
    qpow: Optional[Array] = None,
) -> Array:
    """One time step in GEMM form, batched over leading dims.

    j_k, x_prev: (..., Nx) -> x_k: (..., Nx)
    """
    n_nodes = x_prev.shape[-1]
    if L is None:
        L = ring_matrix(q, n_nodes, x_prev.dtype)
    if qpow is None:
        qpow = ring_powers(q, n_nodes, x_prev.dtype)
    a = p * f(j_k + x_prev)
    ring_in = x_prev[..., -1:]  # x(k-1)_{Nx}
    return jnp.matmul(a, L.T, precision=DOT_PRECISION) + ring_in * qpow


@partial(jax.jit, static_argnames=("f", "with_lengths"))
def run_reservoir(
    p: Array,
    q: Array,
    j_seq: Array,
    x0: Optional[Array] = None,
    *,
    f: Callable[[Array], Array] = lambda z: z,
    lengths: Optional[Array] = None,
    with_lengths: bool = False,
) -> Array:
    """Run the reservoir over a full (batched) masked input sequence.

    j_seq: (T, Nx) or (B, T, Nx)  ->  states X with matching layout
    (T, Nx) or (B, T, Nx).

    If ``lengths`` is given (B,), the state is frozen once k >= length so that
    X[b, length-1] is the final state x(T) for every sample (padding cannot
    perturb it).  The reservoir state is initialized to zero (paper Sec. 2.2).
    """
    batched = j_seq.ndim == 3
    jt = jnp.swapaxes(j_seq, 0, 1) if batched else j_seq  # (T, [B,] Nx)
    n_nodes = jt.shape[-1]
    if x0 is None:
        # derive from the input so shard_map varying axes are inherited
        x0 = jnp.zeros_like(jt[0])
    L = ring_matrix(q, n_nodes, jt.dtype)
    qpow = ring_powers(q, n_nodes, jt.dtype)

    def step(carry, inp):
        x_prev, k = carry
        j_k = inp
        x_k = reservoir_step(p, q, f, j_k, x_prev, L, qpow)
        if lengths is not None:
            live = (k < lengths)[..., None] if batched else (k < lengths)
            x_k = jnp.where(live, x_k, x_prev)
        return (x_k, k + 1), x_k

    (_, _), xs = jax.lax.scan(step, (x0, jnp.zeros((), jnp.int32)), jt)
    return jnp.swapaxes(xs, 0, 1) if batched else xs


def run_reservoir_legacy(
    eta: Array,
    gamma: Array,
    theta: float,
    j_seq: Array,
    f: Callable[[Array, Array], Array],
) -> Array:
    """Pre-modular digital DFR, Eq. (8)-(9):

        x(k)_1 = x(k-1)_{Nx} e^-theta + (1-e^-theta) f(x(k-1)_1, j(k)_1)
        x(k)_n = x(k)_{n-1}  e^-theta + (1-e^-theta) f(x(k-1)_n, j(k)_n)

    Provided for the baseline comparison; f(x, j) = eta * mg(x + gamma j).
    Same linear-recurrence structure with decay e^-theta playing q's role.
    """
    decay = jnp.exp(-jnp.asarray(theta, j_seq.dtype))
    n_nodes = j_seq.shape[-1]
    L = ring_matrix(decay, n_nodes, j_seq.dtype)
    qpow = ring_powers(decay, n_nodes, j_seq.dtype)

    def step(x_prev, j_k):
        a = (1.0 - decay) * f(x_prev, j_k)
        x_k = (jnp.matmul(a, L.T, precision=DOT_PRECISION) if a.ndim > 1
               else jnp.matmul(L, a, precision=DOT_PRECISION))
        x_k = x_k + x_prev[..., -1:] * qpow
        return x_k, x_k

    x0 = jnp.zeros(j_seq.shape[1:] if j_seq.ndim == 2 else j_seq.shape[2:], j_seq.dtype)
    if j_seq.ndim == 3:  # (B, T, Nx)
        jt = jnp.swapaxes(j_seq, 0, 1)
        x0 = jnp.zeros((j_seq.shape[0], n_nodes), j_seq.dtype)
        _, xs = jax.lax.scan(step, x0, jt)
        return jnp.swapaxes(xs, 0, 1)
    _, xs = jax.lax.scan(step, x0, j_seq)
    return xs
