"""Operation and byte counts against hand counts at small shapes, and the
peak table."""
import pytest

from harness import costs


def test_recurrence_step_by_hand():
    # Nx = 2: ring mix 2*2*2 = 8, a = p f(j + x) 2*2 = 4, the wrap 2*2 = 4,
    # DPRR x(k) [x(k-1), 1]^T 2*2*3 = 12
    assert costs.recurrence_step_ops(2) == 8 + 4 + 4 + 12


def test_streaming_kernel_by_hand():
    # two samples of lengths 3 and 5, Nx = 2, Ny = 3, one window
    ops, nbytes = costs.streaming_kernel([3, 5], nx=2, ny=3, n_windows=1)
    step = 28
    readout = 2 * 3 * 6 + 3
    assert ops == 8 * step + 2 * readout
    assert nbytes == 4 * (8 * 2 + 2 * (1 + 3) + 1 * 3 * 6)


def test_ridge_and_statistics_by_hand():
    assert costs.statistics_ops(s=7, ny=3) == 2 * 49 + 2 * 21
    assert costs.ridge_refresh_ops(s=6, ny=2) == 6 + 6 ** 3 / 3 + 2 * 36 * 2


def test_fleet_sample_phases():
    s = 2 * 2 + 2 + 1
    base = 3 * 28 + (2 * 3 * 6 + 3)
    assert costs.fleet_sample_ops(3, 2, 3, adapt=False) == \
        base + costs.statistics_ops(s, 3)
    assert costs.fleet_sample_ops(3, 2, 3, adapt=True) == \
        base + costs.truncated_bp_ops(2, 3)


def test_unknown_device_kind_is_an_error():
    assert costs.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        costs.peaks("cpu")
