"""Dot-Product Reservoir Representation (DPRR), paper Sec. 2.3.

    r_{(i-1)Nx+j} = sum_{k=1..T} x(k)_i x(k-1)_j      (Eq. 27)
    r_{Nx^2 + i}  = sum_{k=1..T} x(k)_i               (Eq. 28)
    with x(0) = 0.

Equivalently  R = X1^T @ X0~  where X1 = X[1..T] (T, Nx) and
X0~ = [X[0..T-1], 1] (T, Nx+1) - i.e. the DPRR **is** a GEMM.  The FPGA
implementation accumulates it element-wise; on TPU we feed the MXU (the
Pallas kernel ``repro.kernels.dprr`` fuses the shift/append with the
T-blocked matmul accumulation).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.types import DOT_PRECISION, Array


def shifted_states(x: Array) -> Array:
    """X0 = [0, x(1), ..., x(T-1)]: the x(k-1) stream with x(0) = 0.

    x: (..., T, Nx) -> (..., T, Nx)
    """
    pad = [(0, 0)] * (x.ndim - 2) + [(1, 0), (0, 0)]
    return jnp.pad(x, pad)[..., :-1, :]


@partial(jax.jit, static_argnames=())
def compute_dprr(x: Array, lengths: Optional[Array] = None) -> Array:
    """DPRR vector r of a state sequence.

    x: (T, Nx) or (B, T, Nx) reservoir states.
    lengths: optional (B,) valid lengths; padded steps contribute nothing.

    Returns r: (Nx*(Nx+1),) or (B, Nx*(Nx+1)), laid out as the flattened
    (Nx, Nx) dot-product block followed by the Nx sum block - matching the
    paper's index convention r_{(i-1)Nx+j}, r_{Nx^2+i}.
    """
    n_nodes = x.shape[-1]
    x0 = shifted_states(x)
    if lengths is not None:
        t = jnp.arange(x.shape[-2])
        live = (t[None, :] < lengths[:, None]).astype(x.dtype)  # (B, T)
        x1m = x * live[..., None]
    else:
        x1m = x
    # R[i, j] = sum_k x(k)_i x(k-1)_j   -> contraction over time on the MXU
    outer = jnp.einsum("...ki,...kj->...ij", x1m, x0, precision=DOT_PRECISION)
    sums = jnp.sum(x1m, axis=-2)  # (..., Nx)
    flat = outer.reshape(*outer.shape[:-2], n_nodes * n_nodes)
    return jnp.concatenate([flat, sums], axis=-1)


def r_tilde(r: Array) -> Array:
    """r~ = [r, 1] (paper Eq. 16), batched over leading dims."""
    ones = jnp.ones((*r.shape[:-1], 1), r.dtype)
    return jnp.concatenate([r, ones], axis=-1)


def dprr_truncated_coefficients(x_last: Array, x_prev: Array) -> Array:
    """Gradient coefficients of r w.r.t. x(T) used by truncated backprop.

    d r_{(n-1)Nx+j} / d x(T)_n = x(T-1)_j ;  d r_{Nx^2+n} / d x(T)_n = 1.
    Returns (Nx, Nx+1): row n = [x(T-1), 1] (paper Eq. 33's pairing).
    """
    n_nodes = x_last.shape[-1]
    del x_last  # present for signature symmetry / batching clarity
    row = jnp.concatenate([x_prev, jnp.ones((*x_prev.shape[:-1], 1), x_prev.dtype)], -1)
    return jnp.broadcast_to(row[..., None, :], (*x_prev.shape[:-1], n_nodes, n_nodes + 1))
