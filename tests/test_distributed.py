"""Distribution: guarded specs, sharded train step == single-device step
(8 virtual host devices via subprocess), compression collectives."""
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd


def test_guarded_spec_divisibility(monkeypatch):
    mesh = jax.make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    spec = shd.guarded_spec((8, 128), ("kv", "kv_alt"), FakeMesh(),
                            dict(shd.DEFAULT_RULES))
    # 8 kv heads indivisible by 16 -> falls to head_dim via kv_alt
    assert spec == P(None, "model")
    spec2 = shd.guarded_spec((32, 128), ("kv", "kv_alt"), FakeMesh(),
                             dict(shd.DEFAULT_RULES))
    # both divisible, but 'model' already used by dim0 -> dim1 unsharded
    assert spec2 == P("model", None)


def test_guarded_spec_multi_axis_batch():
    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 16, "model": 16}

    spec = shd.guarded_spec((64, 128), ("batch", None), FakeMesh(),
                            dict(shd.DEFAULT_RULES))
    assert spec == P(("pod", "data"), None)
    # batch=1 (long_500k): indivisible -> replicated
    spec = shd.guarded_spec((1, 128), ("batch", None), FakeMesh(),
                            dict(shd.DEFAULT_RULES))
    assert spec == P(None, None)


_SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_reduced
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_host_mesh
    from repro.models.lm import make_train_step
    from repro.models.transformer import Transformer
    from repro.optim.optimizers import make_optimizer
    from repro.optim.schedule import constant_schedule

    cfg = get_reduced("smollm-135m")
    model = Transformer(cfg)
    opt = make_optimizer("adamw")
    step_fn = make_train_step(model, opt, constant_schedule(1e-3), accum=1)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
    batch = {"tokens": toks, "targets": toks}

    def run(mesh):
        with shd.use_mesh(mesh):
            params, axes = model.init(jax.random.PRNGKey(0))
            if mesh is not None:
                params = jax.device_put(
                    params, shd.guarded_shardings(params, axes, mesh))
            opt_state = opt.init(params)
            p2, _, m = jax.jit(step_fn)(params, opt_state, jnp.asarray(0), batch)
            return float(m["loss"]), jax.device_get(p2)

    loss_single, p_single = run(None)
    mesh = make_host_mesh(data=4, model=2)
    loss_shard, p_shard = run(mesh)
    diffs = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
             for a, b in zip(jax.tree_util.tree_leaves(p_single),
                             jax.tree_util.tree_leaves(p_shard))]
    print(json.dumps({"loss_single": loss_single, "loss_shard": loss_shard,
                      "max_param_diff": max(diffs)}))
""")


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    """2x4 mesh (8 virtual devices, subprocess) == single device numerics."""
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_PROG],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
             "JAX_PLATFORMS": "cpu", "HOME": "/root"},
        cwd="/root/repo",
    )
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(rec["loss_single"] - rec["loss_shard"]) < 2e-2
    assert rec["max_param_diff"] < 2e-2


def test_compressed_psum_single_shard_identity():
    """With axis size 1, compressed psum == plain quantized passthrough and
    the error feedback residual shrinks the bias across steps."""
    from repro.optim.compression import tree_compressed_psum

    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(256,)),
                          jnp.float32)}
    res = jax.tree_util.tree_map(jnp.zeros_like, g)

    def step(grads, res):
        return jax.jit(
            lambda gg, rr: tree_compressed_psum(gg, (), rr)
        )(grads, res)

    # () axis: degenerate psum - exercise quantize/dequantize + residual
    out, res = step(g, res)
    err1 = float(jnp.max(jnp.abs(out["w"] - g["w"])))
    out2, res = step(g, res)
    err2 = float(jnp.max(jnp.abs(out2["w"] + res["w"] - g["w"])))
    assert err1 < 0.02 * float(jnp.max(jnp.abs(g["w"])))
    assert err2 <= err1 + 1e-6


# ---------------------------------------------------------------------------
# Ensemble member axis (online serving): rule table + psum-exact online step
# ---------------------------------------------------------------------------


def test_member_axis_shards_ensemble_state():
    """The 'member' logical axis shards the ensemble K axis over the data
    axes, with the divisibility guard and per-array uniqueness intact."""

    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 4, "model": 2}

    rules = dict(shd.DEFAULT_RULES)
    # K=16 divisible by pod*data=8 -> sharded; trailing dims replicated
    spec = shd.guarded_spec((16, 10, 992), ("member", None, None),
                            FakeMesh(), rules)
    assert spec == P(("pod", "data"), None, None)
    # K=4 indivisible by 8 -> replicated (guard, not an error)
    spec = shd.guarded_spec((4,), ("member",), FakeMesh(), rules)
    assert spec == P(None)


def test_ensemble_logical_axes_cover_state():
    """ensemble_logical_axes() mirrors the OnlineState tree leaf-for-leaf
    and every leaf leads with 'member'."""
    from repro.core.online import OnlineEnsemble, ensemble_logical_axes
    from repro.core.types import DFRConfig

    cfg = DFRConfig(n_in=2, n_classes=3, n_nodes=6)
    state = OnlineEnsemble(cfg, 4).init()
    axes = ensemble_logical_axes()
    state_leaves, state_def = jax.tree_util.tree_flatten(state)
    axes_leaves, axes_def = jax.tree_util.tree_flatten(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    assert state_def == axes_def
    for leaf, ax in zip(state_leaves, axes_leaves):
        assert ax[0] == "member"
        assert len(ax) == leaf.ndim


def test_slot_axis_rule_resolution():
    """The 'slot' logical axis prefers a dedicated serving-mesh axis, falls
    back to the production data axes, and replicates when indivisible."""

    class SlotMesh:
        axis_names = ("slot",)
        shape = {"slot": 8}

    class ProdMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 4, "model": 2}

    rules = dict(shd.DEFAULT_RULES)
    # serving mesh: S=64 divisible by 8 -> sharded over "slot"
    spec = shd.guarded_spec((64, 57, 57), ("slot", None, None),
                            SlotMesh(), rules)
    assert spec == P("slot", None, None)
    # production mesh (no "slot" axis): falls back to the data axes
    spec = shd.guarded_spec((64, 57, 57), ("slot", None, None),
                            ProdMesh(), rules)
    assert spec == P(("pod", "data"), None, None)
    # indivisible slot count -> replicated (guard, not an error)
    spec = shd.guarded_spec((6,), ("slot",), SlotMesh(), rules)
    assert spec == P(None)


def test_combined_slot_member_spec():
    """An ensemble-of-slots state ((S, K, ...) leaves) on a 2-D serving
    mesh shards slot AND member at once; on the production mesh the
    uniqueness guard gives 'slot' the data axes and replicates 'member'."""

    class SlotMemberMesh:
        axis_names = ("slot", "member")
        shape = {"slot": 4, "member": 2}

    class ProdMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 4, "model": 2}

    rules = dict(shd.DEFAULT_RULES)
    spec = shd.guarded_spec((8, 4, 57, 57), ("slot", "member", None, None),
                            SlotMemberMesh(), rules)
    assert spec == P("slot", "member", None, None)
    spec = shd.guarded_spec((8, 8, 57, 57), ("slot", "member", None, None),
                            ProdMesh(), rules)
    assert spec == P(("pod", "data"), None, None, None)


def test_slot_logical_axes_cover_state():
    """slot_logical_axes() / ensemble_slot_logical_axes() mirror the
    OnlineState tree leaf-for-leaf with 'slot' leading (and 'member'
    second for the ensemble-of-slots variant)."""
    from repro.core.online import (
        ensemble_slot_logical_axes, init_state, slot_logical_axes,
    )
    from repro.core.types import DFRConfig

    cfg = DFRConfig(n_in=2, n_classes=3, n_nodes=6)
    state = init_state(cfg)
    state_leaves, state_def = jax.tree_util.tree_flatten(state)
    for axes_tree, lead in ((slot_logical_axes(), ("slot",)),
                            (ensemble_slot_logical_axes(),
                             ("slot", "member"))):
        axes_leaves, axes_def = jax.tree_util.tree_flatten(
            axes_tree, is_leaf=lambda x: isinstance(x, tuple))
        assert state_def == axes_def
        for leaf, ax in zip(state_leaves, axes_leaves):
            assert ax[:len(lead)] == lead
            # batching stacks len(lead) leading dims onto each leaf
            assert len(ax) == leaf.ndim + len(lead)


def test_online_step_psum_matches_unsharded():
    """online_step(axis_names=('data',)) inside shard_map over a 1-device
    data mesh reproduces the plain step exactly ((A, B)/grad sums are
    associative, so the psum is the identity at world size 1)."""
    from jax.sharding import PartitionSpec

    from repro.core import online
    from repro.core.types import DFRConfig

    cfg = DFRConfig(n_in=2, n_classes=2, n_nodes=6)
    system = online.OnlineDFR(cfg)
    state = system.init()
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(4, 10, 2)).astype(np.float32))
    ln = jnp.asarray(rng.integers(3, 11, 4), jnp.int32)
    lab = jnp.asarray(rng.integers(0, 2, 4), jnp.int32)
    lr = jnp.float32(0.2)

    ref_state, ref_metrics = system.step(state, u, ln, lab, lr, lr)

    mesh = jax.make_mesh((1,), ("data",))
    P_ = PartitionSpec
    sharded = jax.shard_map(
        lambda st, uu, ll, yy: online.online_step(
            cfg, system.mask, st, uu, ll, yy, lr, lr, axis_names=("data",)
        ),
        mesh=mesh,
        in_specs=(P_(), P_("data"), P_("data"), P_("data")),
        out_specs=P_(),
        check_vma=False,
    )
    got_state, got_metrics = jax.jit(sharded)(state, u, ln, lab)
    for a, b in zip(jax.tree_util.tree_leaves(ref_state),
                    jax.tree_util.tree_leaves(got_state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(float(ref_metrics["loss"]),
                               float(got_metrics["loss"]), rtol=1e-6)
