"""Faults planted under the fleet's timed path, each of which the check
has to catch (``correct`` false): the check of the check.

* ``state_unchanged``: the step returns the slot states it was given;
* ``half_batch``: half of each slot's window is left out, its mean and its
  statistics taken over the rest;
* ``answer_altered``: the first prediction of every slot's window is moved
  to the next class where it is produced.

The fleet on one chip holds no collective, so there is no exchange between
chips to leave out.  ``bench/calibrate.py readings --fault <name>`` reads
each on the chip at the cell's size; ``bench/tests/test_faults.py`` on the
CPU at a tiny one.
"""
from __future__ import annotations

import contextlib

FAULTS = ("state_unchanged", "half_batch", "answer_altered")


@contextlib.contextmanager
def planted(name: str, n_classes: int):
    """Plant the named fault in ``repro.runtime.stream_server`` for the
    duration of the block.  Both step programs are routed through the
    undonated one, so that a fault may hand back its inputs; the traced
    programs are dropped on the way in and out."""
    import jax
    from repro.runtime import stream_server as ss

    jax.clear_caches()

    names = ("_stream_step_pool", "_stream_step_pool_donated", "_gather_window")
    saved = {k: getattr(ss, k) for k in names}
    plain = saved["_stream_step_pool"]

    def route(change):
        def step(*args, **kw):
            return change(args, plain(*args, **kw))
        ss._stream_step_pool = ss._stream_step_pool_donated = step

    if name == "state_unchanged":
        route(lambda args, out: (args[2], out[1], out[2]))
    elif name == "answer_altered":
        route(lambda args, out: (out[0], out[1], out[2].at[:, 0].set(
            (out[2][:, 0] + 1) % n_classes)))
    elif name == "half_batch":
        gather = saved["_gather_window"]

        def half(*args, **kw):
            u, length, label, weight = gather(*args, **kw)
            return u, length, label, weight.at[:, weight.shape[1] // 2:].set(0.0)

        ss._gather_window = half
    else:
        raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
    try:
        yield
    finally:
        for k, v in saved.items():
            setattr(ss, k, v)
        jax.clear_caches()
