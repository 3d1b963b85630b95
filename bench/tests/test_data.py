"""The seeded traffic: determinism, the spec's shapes, length ranges,
class balance, and the speaker sessions the streams are made of."""
import itertools

import numpy as np
import pytest

from harness import data
from harness.common import Cell

SPECS = {w: Cell(w).config["dataset"] for w in ("arab-fleet-saturated",)}


def small(spec, n_train=60, n_test=30):
    return dict(spec, n_train=n_train, n_test=n_test)


@pytest.mark.parametrize("workload", sorted(SPECS))
def test_same_seed_same_inputs(workload):
    spec = small(SPECS[workload])
    a = data.make_dataset(spec, 2**31 + 7)
    b = data.make_dataset(spec, 2**31 + 7)
    c = data.make_dataset(spec, 2**31 + 8)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.u, y.u)
        np.testing.assert_array_equal(x.label, y.label)
    assert not np.array_equal(a[0].u, c[0].u)


@pytest.mark.parametrize("workload", sorted(SPECS))
def test_shapes_lengths_and_balance(workload):
    spec = small(SPECS[workload])
    train, test = data.make_dataset(spec, 5)
    for split, n in ((train, spec["n_train"]), (test, spec["n_test"])):
        assert split.u.shape == (n, spec["t_max"], spec["n_in"])
        assert split.u.dtype == np.float32
        assert split.length.min() >= spec["t_min"]
        assert split.length.max() <= spec["t_max"]
        counts = np.bincount(split.label, minlength=spec["n_classes"])
        assert counts.max() - counts.min() <= 1
        for i in range(n):
            ln = split.length[i]
            assert not split.u[i, ln:].any()        # zero past the length
            seg = split.u[i, :ln]
            np.testing.assert_allclose(seg.mean(0), 0.0, atol=1e-4)


def test_seeds_permute_one_set_of_sizes():
    spec = small(SPECS["arab-fleet-saturated"])
    a, _ = data.make_dataset(spec, 1)
    b, _ = data.make_dataset(spec, 2)
    assert sorted(a.length) == sorted(b.length)
    assert not np.array_equal(a.length, b.length)


def test_speaker_sessions_cover_the_split():
    spec = SPECS["arab-fleet-saturated"]
    labels = data.make_split(dict(spec, t_max=8, t_min=4), spec["n_train"],
                             3, 1).label
    sess = data.speaker_sessions(labels, spec["n_classes"], 10)
    assert sess.shape == (66, 100)          # 66 speakers x 10 digits x 10
    assert sorted(sess.ravel()) == list(range(spec["n_train"]))
    for row in sess:
        assert np.all(np.bincount(labels[row], minlength=10) == 10)


def _streams(seed, n, in_progress):
    labels = np.arange(200) % 10
    return list(itertools.islice(
        data.stream_source(seed, labels, 10, 2, 4, in_progress), n))


def test_stream_source_sessions():
    streams = _streams(4, 40, 0)
    assert [s.rid for s in streams] == list(range(40))
    assert all(len(s.idx) == 20 for s in streams)
    labels = np.arange(200) % 10
    for s in streams:
        assert np.all(np.bincount(labels[s.idx], minlength=10) == 2)
    first = sorted(tuple(sorted(s.idx)) for s in streams[:10])
    again = sorted(tuple(sorted(s.idx)) for s in streams[10:20])
    assert first == again and len(set(first)) == 10   # every speaker once


def test_in_progress_sessions_retire_evenly():
    slots = 12
    streams = _streams(5, slots + 3, slots)
    windows = [-(-len(s.idx) // 4) for s in streams[:slots]]
    assert sorted(windows) == sorted([5 - (i * 5) // slots
                                      for i in range(slots)])
    assert all(len(s.idx) == 20 for s in streams[slots:])


def test_same_seed_same_streams_other_seed_other_order():
    a, b, c = _streams(2**33 + 1, 30, 8), _streams(2**33 + 1, 30, 8), \
        _streams(2**33 + 2, 30, 8)
    assert all(np.array_equal(x.idx, y.idx) for x, y in zip(a, b))
    assert sorted(len(x.idx) for x in a) == sorted(len(x.idx) for x in c)
    assert not all(np.array_equal(x.idx, y.idx) for x, y in zip(a, c))
