"""End-to-end DFR system behaviour (the paper's pipeline on synthetic data)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DFRModel, OnlineDFR
from repro.core.readout import DistributedDFRReadout, ReadoutConfig
from repro.core.types import DFRConfig, TimeSeriesBatch
from repro.data import load


@pytest.fixture(scope="module")
def jpvow_small():
    return load("JPVOW", size_cap=72)


def test_fit_reaches_nontrivial_accuracy(jpvow_small):
    train, test = jpvow_small
    cfg = DFRConfig(n_in=12, n_classes=9, n_nodes=20, epochs=8)
    m = DFRModel.create(cfg)
    params = m.fit(train, minibatch=4)
    acc = float(m.accuracy(test, params))
    assert acc > 3.0 / 9.0, acc  # far above chance on 9 classes


def test_ridge_only_interpolates_train(jpvow_small):
    train, _ = jpvow_small
    cfg = DFRConfig(n_in=12, n_classes=9, n_nodes=20)
    m = DFRModel.create(cfg)
    from repro.core.types import DFRParams
    params = m.fit_ridge(train, DFRParams.init(cfg))
    assert float(m.accuracy(train, params)) > 0.95


def test_online_stepper_matches_features_and_learns(jpvow_small):
    train, _ = jpvow_small
    cfg = DFRConfig(n_in=12, n_classes=9, n_nodes=16)
    online = OnlineDFR(cfg)
    state = online.init()
    # stream the training set in windows of 8 (the edge loop)
    for lo in range(0, train.batch - 7, 8):
        state, metrics = online.step(
            state, train.u[lo:lo+8], train.length[lo:lo+8],
            train.label[lo:lo+8], jnp.float32(0.5), jnp.float32(0.5),
        )
    assert int(state.ridge.count) >= 64
    state = online.refresh_output(state, jnp.float32(1e-2))
    preds = online.infer(state, train.u[:32], train.length[:32])
    acc = float(jnp.mean((preds == train.label[:32]).astype(jnp.float32)))
    assert acc > 2.0 / 9.0


def test_distributed_readout_single_device_path(jpvow_small):
    """The psum-free (axis_names=()) path: accumulate -> solve -> predict."""
    train, _ = jpvow_small
    rc = ReadoutConfig(feature_dim=12, n_classes=9, n_nodes=16)
    ro = DistributedDFRReadout(rc, axis_names=())
    params, ridge_state = ro.init()
    h = train.u  # treat raw series as 'backbone features' (D = 12)
    ridge_state = ro.accumulate(ridge_state, params, h, train.label,
                                lengths=train.length)
    fitted = ro.solve(ridge_state, params, jnp.float32(1e-2))
    preds = ro.predict(fitted, h, lengths=train.length)
    acc = float(jnp.mean((preds == train.label).astype(jnp.float32)))
    assert acc > 0.6  # far above the 1/9 chance level (regularized fit)


def test_distributed_readout_psum_consistency(jpvow_small):
    """shard_map over 1-device mesh: psum path == local path exactly."""
    from jax.sharding import PartitionSpec as P

    train, _ = jpvow_small
    mesh = jax.make_mesh((1,), ("data",))
    rc = ReadoutConfig(feature_dim=12, n_classes=9, n_nodes=8)
    ro_local = DistributedDFRReadout(rc, axis_names=())
    ro_dist = DistributedDFRReadout(rc, axis_names=("data",))
    params, rs = ro_local.init()
    h, lab = train.u[:16], train.label[:16]

    local_state = ro_local.accumulate(rs, params, h, lab)
    local_W = ro_local.solve(local_state, params, jnp.float32(1e-2)).W

    def shard_fn(h, lab):
        st = ro_dist.accumulate(rs, params, h, lab)
        return ro_dist.solve(st, params, jnp.float32(1e-2)).W

    dist_W = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P()
    )(h, lab)
    np.testing.assert_allclose(np.asarray(local_W), np.asarray(dist_W),
                               rtol=1e-4, atol=1e-4)


def test_grid_search_runs_and_improves_with_divisions(jpvow_small):
    from repro.core.grid_search import grid_search
    train, test = jpvow_small
    cfg = DFRConfig(n_in=12, n_classes=9, n_nodes=16)
    g1 = grid_search(cfg, train, test, divs=1)
    g3 = grid_search(cfg, train, test, divs=3)
    assert g3["n_points"] > g1["n_points"]
    assert g3["acc"] >= g1["acc"] - 0.05
