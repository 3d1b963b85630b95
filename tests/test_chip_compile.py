"""Compile-only tests of the main-path programs for a described TPU v5e.

The chip's compiler is installed beside JAX, and it compiles for a v5e
that is described rather than attached: nothing runs, but everything the
chip's Mosaic lowering refuses (a block shape off the (8, 128) tiling, too
much VMEM) is refused here.  The kernel tests compile one wrapper of
``kernels.ops`` at the paper's Nx = 30 and check that the program holds
the Pallas kernel (``tpu_custom_call``); the snapshot test holds the
stream server's batched retirement snapshot to the compiler's estimates.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and the test workers must all collect the same tests.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.online import init_state
from repro.core.types import DFRConfig
from repro.kernels import ops
from repro.runtime import stream_server as ss

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


@pytest.mark.parametrize("members, b, t", [
    (None, 64, 128), (8, 256, T_MAX), (64, 808, 994), (64, 8, 994),
], ids=["None", "8", "64", "64-refine"])
def test_train_forward_compiles(spec, members, b, t):
    """The fused training forward, alone (B=64, T=128), vmapped over
    population candidates at B=256, T=93, at the NET evaluation's shape
    (64 members, B=808, T=994: the length sort and the scalar-prefetched
    live chunk counts), whose temporaries stay within the evaluation's
    4.26 GB plus the sorted copy of the input (the chunk stacks of the
    accumulator fold are VMEM scratch), and at the NET refinement's (64
    members, one minibatch of 8, T=994)."""
    if members is None:
        _assert_kernel(
            lambda j, ln, p, q: ops.train_forward(j, ln, p, q, NX,
                                                  backend="tpu"),
            spec((b, t, NX)), spec((b,), jnp.int32), spec(()), spec(()),
        )
        return
    fn = jax.vmap(lambda j, ln, p, q: ops.train_forward(j, ln, p, q, NX,
                                                        backend="tpu"),
                  in_axes=(None, None, 0, 0))
    args = (spec((b, t, NX)), spec((b,), jnp.int32), spec((members,)),
            spec((members,)))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if members == 64:
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 4.26e9 + b * t * NX * 4, temp


def _cost(fn, *args):
    """(summed ``estimated_cycles`` of the compiled program, its
    temporary bytes) for the described chip."""
    compiled = fn.lower(*args).compile()
    cycles = sum(int(c) for c in re.findall(
        r'"estimated_cycles":"?(\d+)', compiled.as_text()))
    return cycles, compiled.memory_analysis().temp_size_in_bytes


def test_batched_snapshot_reads_each_leaf_once(spec):
    """The stream server's retirement snapshot of K = 32 rows (the most
    one program reads) of the 512-slot state at Nx = 30 (s = 931)
    compiles, and costs what a few single-row snapshots cost, not K of
    them: the TPU keeps the (512, 931, 931) leaves slot-minor, so each
    per-row slice reads a whole leaf through a 446 MB padded temporary,
    while the gather relayouts each leaf once for all K rows."""
    cfg = DFRConfig(n_in=N_IN, n_classes=NY, n_nodes=NX)
    slots, k = 512, ss.SNAPSHOT_MAX_ROWS
    states = jax.tree_util.tree_map(
        lambda leaf: spec((slots, *leaf.shape), leaf.dtype),
        jax.eval_shape(lambda: init_state(cfg)))
    one_cycles, one_temp = _cost(ss._snapshot_slot, states,
                                 spec((), jnp.int32))
    cycles, temp = _cost(ss._snapshot_slots, states, spec((k,), jnp.int32))
    assert one_cycles > 0 and cycles > 0
    # K single-row slices in one program: 32x the cycles, 18x the bytes
    assert cycles < 8 * one_cycles
    assert temp < 8 * one_temp


@pytest.mark.parametrize("program", ["evaluate", "refine"])
def test_search_programs_fit_one_chip(spec, program, monkeypatch):
    """The population search on NET at the paper's Nx = 30 (K = 64
    members, 803 train and 534 eval samples of T up to 994, minibatch 8)
    compiles with the training kernel in both programs and fits one v5e's
    HBM: evaluation features through the kernel hold no state sequence
    (run_reservoir's would need about 20 GB of temporaries)."""
    from repro.core import population
    from repro.core.types import DFRParams

    monkeypatch.setattr(ops, "_auto_backend",
                        lambda b: "tpu" if b is None else b)
    k, b_tr, b_ev, t, n_in, ny = 64, 803, 534, 994, 4, 13
    cfg = DFRConfig(n_in=n_in, n_classes=ny, n_nodes=NX)
    mask = spec((NX, n_in))
    if program == "evaluate":
        lowered = population.evaluate_population.lower(
            cfg, mask, spec((k,)), spec((k,)), spec((b_tr, t, n_in)),
            spec((b_tr,), jnp.int32), spec((b_tr, ny)),
            spec((b_ev, t, n_in)), spec((b_ev,), jnp.int32),
            spec((b_ev, ny)), select="acc")
    else:
        pop = DFRParams(p=spec((k,)), q=spec((k,)), W=spec((k, ny, NR)),
                        b=spec((k, ny)))
        lowered = population.refine_population.lower(
            cfg, mask, pop, spec((b_tr, t, n_in)), spec((b_tr,), jnp.int32),
            spec((b_tr, ny)), spec(()), spec(()), minibatch=8)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 12 * 2 ** 30, used
