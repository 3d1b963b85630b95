"""Input masking for the DFR (paper Sec. 2.1-2.2).

The digital DFR multiplies the (held) input sample by a per-virtual-node mask:
``j(k) = M @ u(k)`` where ``M`` is an (Nx, n_in) random matrix fixed at system
construction.  For multivariate inputs this follows the authors' prior
hardware-friendly DFR [10]: each virtual node sees a random +/-1 combination
of the input channels.  Input scaling gamma is folded into the trainable
reservoir gain ``p`` of the modular model (f is linear in the evaluation), so
the mask itself is unit-magnitude.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import DOT_PRECISION, Array


def make_mask(
    key: jax.Array, n_nodes: int, n_in: int, dtype=jnp.float32, mode: str = "select"
) -> Array:
    """Mask matrix M of shape (Nx, n_in).

    mode='select' (default): each virtual node reads ONE random input channel
    with a random +/-1 sign - the multivariate masking of the authors'
    hardware-friendly DFR [10]; keeps j(k) at the input's unit scale.
    mode='dense': every node reads a +/-1 combination of all channels.
    """
    k_sign, k_sel = jax.random.split(key)
    bits = jax.random.bernoulli(k_sign, 0.5, (n_nodes, n_in))
    signs = jnp.where(bits, 1.0, -1.0).astype(dtype)
    if mode == "dense":
        return signs
    if mode == "select":
        sel = jax.random.randint(k_sel, (n_nodes,), 0, n_in)
        onehot = jax.nn.one_hot(sel, n_in, dtype=dtype)
        return signs * onehot
    raise ValueError(f"unknown mask mode: {mode}")


def apply_mask(mask: Array, u: Array) -> Array:
    """j(k) = M u(k), batched over any leading dims of ``u``.

    u: (..., n_in)  ->  j: (..., Nx)
    """
    return jnp.einsum("ni,...i->...n", mask, u, precision=DOT_PRECISION)
