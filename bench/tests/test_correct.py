"""The comparison that decides ``correct`` fails what it must.

Runs by hand, on the CPU, at a size a test run holds:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Each test drives a whole run of ``bench/run.py`` (set-up, the window
through ``AsyncServingEngine``, the reference, the comparison) on the
``tiny-32`` configuration, with the look for a chip skipped, and reads
``correct`` from the result line:

* a sound run is correct;
* the control, the program with its bfloat16 vector storage switched on,
  is not;
* with the timed path broken underneath, a run is not correct: half of
  every batch left out (those queries answered with no ids), and an answer
  altered where it is produced (an id replaced by another object of the
  same range, its distance kept).
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench  # noqa: E402

CELLS = ("tiny-32.mixed-closed", "tiny-32.mixed-open")


def spec():
    metric = {"better": "lower", "source": "host_clock", "bound": 0.25}
    return {
        "configs": [{"name": "tiny-32", "file": "bench/tests/tiny-32.json"}],
        "workloads": [
            {"name": CELLS[0], "config": "tiny-32",
             "traffic": "mixed-closed", "chips": 1},
            {"name": CELLS[1], "config": "tiny-32",
             "traffic": "mixed-open", "chips": 1},
        ],
        "end_to_end": [{"name": "setup_s", "unit": "s", **metric}],
        "per_layer": [],
    }


def run_cell(cell: str, seed: int = 1234567890123) -> dict:
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench.main(["--workload", cell, "--seed", str(seed),
                         "--seconds", "1.5"], spec=spec(), chip=False)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = run_cell(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_control_is_not_correct(monkeypatch):
    from repro.core import StorageConfig

    load_index = bench.load_index

    def low(cfg):
        index, attr = load_index(cfg)
        return index.astype_storage(StorageConfig(
            vector_dtype="bfloat16", neighbor_dtype="int32")), attr

    monkeypatch.setattr(bench, "load_index", low)
    res = run_cell(CELLS[0])
    assert not res["correct"]
    c = res["checks"]["dist_err"]
    assert c["value"] > c["limit"], res["checks"]


def _broken(monkeypatch, alter):
    from repro.core.search import SearchResult
    from repro.serve import SearchExecutor

    search = SearchExecutor.search_ranks

    def broken(self, queries, L, R, *, k, config=None):
        res = search(self, queries, L, R, k=k, config=config)
        ids, dists = alter(np.array(res.ids), np.array(res.dists),
                           np.asarray(L), np.asarray(R))
        return SearchResult(ids, dists, res.n_hops, res.n_dists)

    monkeypatch.setattr(SearchExecutor, "search_ranks", broken)


def test_half_batch_left_out_is_not_correct(monkeypatch):
    def half(ids, dists, L, R):
        h = (len(ids) + 1) // 2
        ids[h:] = -1
        dists[h:] = np.inf
        return ids, dists

    _broken(monkeypatch, half)
    res = run_cell(CELLS[0])
    assert not res["correct"]
    c = res["checks"]["recall_at_10"]
    assert c["value"] < c["limit"], res["checks"]


def test_altered_answer_is_not_correct(monkeypatch):
    def alter(ids, dists, L, R):
        # rank-space ids: the first answer's nearest becomes another object
        # of its range, keeping the distance it was served with
        r = ids[0, 0]
        ids[0, 0] = r + 1 if r < R[0] else r - 1
        return ids, dists

    _broken(monkeypatch, alter)
    res = run_cell(CELLS[0])
    assert not res["correct"]
    c = res["checks"]["dist_err"]
    assert c["value"] > c["limit"], res["checks"]
