"""Compile-only tests of the main-path kernels for a described TPU v5e.

The chip's compiler is installed beside JAX, and it compiles for a v5e
that is described rather than attached: nothing runs, but everything the
chip's Mosaic lowering refuses (a block shape off the (8, 128) tiling, too
much VMEM) is refused here.  Every test compiles one wrapper of
``kernels.ops`` at the paper's Nx = 30 and checks that the program holds
the Pallas kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and the test workers must all collect the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

NX, NY, T_MAX, N_IN = 30, 10, 93, 13     # ARAB at the paper's width
NR = NX * NX + NX


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back without one
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("b", [1, 4, 8, 12])
def test_streaming_logits_compiles(spec, b):
    _assert_kernel(
        lambda j, ln, p, q, W, bias: ops.streaming_logits(
            j, ln, p, q, W, bias, NX, backend="tpu"),
        spec((b, T_MAX, NX)), spec((b,), jnp.int32), spec(()), spec(()),
        spec((NY, NR)), spec((NY,)),
    )


@pytest.mark.parametrize("b", [1, 4, 8, 12])
def test_streaming_logits_q8_compiles(spec, b):
    _assert_kernel(
        lambda j, ln, p, q, Wq, ws, xs, bias: ops.streaming_logits_q8(
            j, ln, p, q, Wq, ws, xs, bias, NX, backend="tpu"),
        spec((b, T_MAX, NX)), spec((b,), jnp.int32), spec(()), spec(()),
        spec((NY, NR), jnp.int8), spec(()), spec(()), spec((NY,)),
    )


def test_streaming_logits_slots_compiles(spec):
    """The stream server's fused-infer dispatch: 256 slots x window 4."""
    s, w = 256, 4
    _assert_kernel(
        lambda j, ln, p, q, W, bias: ops.streaming_logits_slots(
            j, ln, p, q, W, bias, NX, backend="tpu"),
        spec((s, w, T_MAX, NX)), spec((s, w), jnp.int32), spec((s,)),
        spec((s,)), spec((s, NY, NR)), spec((s, NY)),
    )


@pytest.mark.parametrize("members", [None, 8])
def test_train_forward_compiles(spec, members):
    """The fused training forward, alone (B=64, T=128) and vmapped over
    population candidates at the refinement shape (B=256, T=93)."""
    if members is None:
        _assert_kernel(
            lambda j, ln, p, q: ops.train_forward(j, ln, p, q, NX,
                                                  backend="tpu"),
            spec((64, 128, NX)), spec((64,), jnp.int32), spec(()), spec(()),
        )
        return
    _assert_kernel(
        jax.vmap(lambda j, ln, p, q: ops.train_forward(j, ln, p, q, NX,
                                                       backend="tpu"),
                 in_axes=(None, None, 0, 0)),
        spec((256, T_MAX, NX)), spec((256,), jnp.int32), spec((members,)),
        spec((members,)),
    )
