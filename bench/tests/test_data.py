"""The traffic generator's parameters, each on a small collection.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import data  # noqa: E402
import traffic  # noqa: E402

CFG = {"n": 4096, "dim": 8, "n_clusters": 4, "data_seed": 3,
       "attribute": "uniform"}
MIX = {"ranges": {"log2_fractions": list(range(10))},
       "queries": {"kind": "cluster_centres", "noise": 1.0}, "k": 10}


def stream(mix, seed=2**40 + 7, count=1000):
    _, attr = data.collection(CFG)
    return data.requests(CFG, mix, seed, count, np.sort(attr),
                         data.centres(CFG))


def test_collection_is_the_seeds():
    v, a = data.collection(CFG)
    v2, a2 = data.collection(CFG)
    assert np.array_equal(v, v2) and np.array_equal(a, a2)
    # the centres are the collection's: its vectors lie around them
    c0 = data.centres(CFG)
    near = ((v[:, None, :] - c0[None]) ** 2).sum(-1).min(1)
    assert np.median(near) < 2 * CFG["dim"]
    c = dict(CFG, attribute="clustered")
    assert data.collection(c)[1].max() < 1000 * CFG["n_clusters"]
    assert np.array_equal(data.collection(c)[0], v)


def test_widths_are_stratified_and_ranges_hold():
    r = stream(MIX)
    spans = r.R - r.L + 1
    for b in range(0, 1000, 10):       # every block of 10 has each width
        assert sorted(spans[b:b + 10]) == sorted(
            max(4096 >> i, 8) for i in range(10))
    assert (r.L >= 0).all() and (r.R < 4096).all()
    assert (r.lo <= r.hi).all() and r.vectors.dtype == np.float32
    # another seed, another order of the same work
    r2 = stream(MIX, seed=5)
    assert not np.array_equal(r.L, r2.L)
    assert sorted(r.R - r.L) == sorted(r2.R - r2.L)


def test_open_loop_count_is_fixed():
    due = traffic.due_times({"rate": 500.0}, 11, 4.0)
    assert len(due) == 2000 and (np.diff(due) >= 0).all()
    assert 0.0 <= due[0] and due[-1] < 4.0
    assert np.array_equal(due, traffic.due_times({"rate": 500.0}, 11, 4.0))
