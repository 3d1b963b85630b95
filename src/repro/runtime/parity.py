"""The parity rule between two servings of one episode.

Two servers that must agree - ``devices=n`` against ``devices=1``, or an
adaptive detector that never fires against ``retirement='none'`` - compile
different programs around the same per-slot math.  XLA lays out the
slot-vmapped serve step for the device-local slot count (S/n), and it fuses
the serve step's forward differently once the detector consumes its
accuracy metric, so a few reductions (the readout-bias gradient over a
window, the loss EMA) round in a different order.  On a TPU the cohort
refresh's batched Cholesky solve also rounds differently for a different
batch (S/n rows per device against S on one), and the solve carries that
into the readout (W, b) even where the (A, B) statistics are bitwise
equal.  The rule is therefore: predictions, integer and boolean leaves
agree exactly, and a float leaf agrees to within ``PARITY_ULPS`` units in
the last place of the leaf's largest magnitude.  The CPU battery
(``tests/test_stream_sharded.py``, ``tests/test_adaptive.py``) measures at
most 1; ``chip_smoke.py --chips 4`` measured 16.25 and 20 on the readout
of v5e episodes at Nx = 30, with (A, B) bitwise equal.
"""
from __future__ import annotations

import jax
import numpy as np

PARITY_ULPS = 64


def ulps_apart(a, b) -> float:
    """Largest ``|a - b|`` in units of the last place of ``max |a|``.

    0 for identical arrays; ``inf`` where a non-float leaf differs at all
    or a float leaf differs from an all-zero one."""
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return float("inf")
    if not np.issubdtype(a.dtype, np.floating):
        return 0.0 if np.array_equal(a, b) else float("inf")
    diff = np.max(np.abs(a.astype(np.float64) - b), initial=0.0)
    if diff == 0.0:
        return 0.0
    scale = np.max(np.abs(a), initial=0.0)
    return float(diff / np.spacing(scale)) if scale else float("inf")


def tree_ulps_apart(tree_a, tree_b) -> float:
    """``ulps_apart`` maximized over the leaves of two equal-shaped trees."""
    leaves_a = jax.tree_util.tree_leaves(tree_a)
    leaves_b = jax.tree_util.tree_leaves(tree_b)
    assert len(leaves_a) == len(leaves_b), (len(leaves_a), len(leaves_b))
    return max((ulps_apart(a, b) for a, b in zip(leaves_a, leaves_b)),
               default=0.0)


def assert_parity(tree_a, tree_b, ulps: float = PARITY_ULPS) -> None:
    """Raise unless every leaf pair meets the parity rule."""
    d = tree_ulps_apart(tree_a, tree_b)
    assert d <= ulps, f"{d} ulps apart > {ulps}"
