"""Seeded inputs of the benchmark: the Table-4 stand-in and the streams
made of it.

The stand-in follows the distribution of ``repro.data.timeseries.
make_dataset`` (per-class sinusoidal prototypes per channel, a random time
warp, offset and scale per sample, AR(1) observation noise, per-channel
z-normalisation over the valid length, zero padding after it), but steps
the AR(1) noise over time for all samples at once instead of one sample at
a time.  It is a copy kept with the benchmark, so that a change to the
program's generator cannot change what the benchmark measures.

A stream is one speaker's session of the train split, as the Spoken
Arabic Digits recordings are organised: every utterance of a speaker, ten
of each digit.  Every size a seed could change is drawn from a fixed
multiset and only permuted by the seed: sample lengths are spread evenly
over [t_min, t_max] and sessions are whole speakers.  Two seeds then give
the same work in another order.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Iterator

import numpy as np


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    """A numpy generator for any whole-number seed (negative or past 64
    bits included) and a tuple of salts naming its use."""
    return np.random.default_rng([seed % 2**64, *salt])


@dataclasses.dataclass(frozen=True)
class Split:
    u: np.ndarray       # (n, t_max, n_in) float32, zero past length
    length: np.ndarray  # (n,) int32
    label: np.ndarray   # (n,) int32


def spread_lengths(n: int, t_min: int, t_max: int) -> np.ndarray:
    """n lengths spread evenly over the integers [t_min, t_max]."""
    span = t_max - t_min + 1
    return (t_min + (np.arange(n) * span) // n).astype(np.int32)


def make_split(spec: dict, n: int, seed: int, split_id: int) -> Split:
    """One split of the stand-in named by ``spec`` (a configuration's
    ``dataset`` entry): ``n`` samples, balanced classes."""
    n_in, n_cls = spec["n_in"], spec["n_classes"]
    t_min, t_max = spec["t_min"], spec["t_max"]
    noise, n_h, ar_coef = spec["noise"], spec["harmonics"], spec["ar"]
    name_salt = zlib.crc32(spec["name"].encode())
    crng = rng_for(seed, name_salt, 0)
    amp = crng.uniform(0.3, 1.0, (n_cls, n_in, n_h))
    cycles = crng.uniform(0.5, 4.0, (n_cls, n_in, n_h))
    phase = crng.uniform(0.0, 2 * np.pi, (n_cls, n_in, n_h))

    rng = rng_for(seed, name_salt, split_id)
    labels = np.arange(n) % n_cls
    rng.shuffle(labels)
    lengths = spread_lengths(n, t_min, t_max)
    rng.shuffle(lengths)
    warp = rng.uniform(0.85, 1.15, n)
    offs = rng.uniform(-0.05, 0.05, n)
    scale = rng.uniform(0.8, 1.25, n)

    t = np.arange(t_max)[None, :]
    frac = t / np.maximum(lengths[:, None] - 1, 1)               # (n, T)
    arg = warp[:, None] * frac + offs[:, None]                   # (n, T)
    x = np.zeros((n, t_max, n_in))
    for h in range(n_h):
        a = amp[labels, :, h][:, None, :]                         # (n, 1, V)
        c = cycles[labels, :, h][:, None, :]
        p = phase[labels, :, h][:, None, :]
        x += a * np.sin(2 * np.pi * c * arg[:, :, None] + p)
    x *= scale[:, None, None]
    e = rng.normal(0.0, noise, (n, t_max, n_in))
    ar = np.zeros((n, n_in))
    for k in range(t_max):
        ar = ar_coef * ar + e[:, k]
        x[:, k] += ar
    live = (t < lengths[:, None])[:, :, None]                     # (n, T, 1)
    cnt = lengths[:, None].astype(np.float64)
    mu = np.sum(x * live, axis=1) / cnt
    sd = np.sqrt(np.sum(((x - mu[:, None]) * live) ** 2, axis=1) / cnt) + 1e-6
    u = np.where(live, (x - mu[:, None]) / sd[:, None], 0.0)
    return Split(u=u.astype(np.float32), length=lengths,
                 label=labels.astype(np.int32))


def make_dataset(spec: dict, seed: int):
    """(train, test) of the stand-in at the spec's split sizes."""
    return (make_split(spec, spec["n_train"], seed, 1),
            make_split(spec, spec["n_test"], seed, 2))


def speaker_sessions(labels: np.ndarray, n_classes: int,
                     per_class: int) -> np.ndarray:
    """(speakers, n_classes * per_class) sample indices of the split's
    sessions: speaker k owns, of every class, the samples ranked
    ``per_class * k`` to ``per_class * (k + 1) - 1`` in index order (the
    Spoken Arabic Digits train split is 66 speakers x 10 digits x 10
    repetitions)."""
    by_class = [np.flatnonzero(labels == c) for c in range(n_classes)]
    n_spk = min(len(b) for b in by_class) // per_class
    return np.stack([
        np.concatenate([b[k * per_class:(k + 1) * per_class] for b in by_class])
        for k in range(n_spk)])


@dataclasses.dataclass
class Stream:
    rid: int
    idx: np.ndarray          # (n,) sample indices into the train split


def stream_source(seed: int, labels: np.ndarray, n_classes: int,
                  per_class: int, window: int,
                  in_progress: int = 0) -> Iterator[Stream]:
    """Streams in submission order, each one speaker's session: its
    utterances in a seeded order.  Speakers cycle through the split, each
    pass in a seeded order.

    The first ``in_progress`` streams continue sessions already under way,
    so that a fleet that starts full retires its streams evenly over the
    steps: stream i of them starts at window ``(i * n_windows) //
    in_progress`` of its session.  Every seed gives the same multiset of
    stream lengths."""
    sessions = speaker_sessions(labels, n_classes, per_class)
    n_spk, n = sessions.shape
    n_win = -(-n // window)
    rng = rng_for(seed, 13)
    rid = 0
    while True:
        for k in rng.permutation(n_spk):
            idx = sessions[k][rng.permutation(n)]
            if rid < in_progress:
                idx = idx[window * ((rid * n_win) // in_progress):]
            yield Stream(rid=rid, idx=idx)
            rid += 1


def session_samples(spec: dict, per_class: int) -> int:
    """Samples in one whole session."""
    return spec["n_classes"] * per_class
