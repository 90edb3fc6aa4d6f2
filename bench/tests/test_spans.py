"""The serving path's spans in a trace (``spans.py``) and the per-layer
readers that use them and the engine's counters.

Runs by hand with the benchmark's other tests:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Two traces: a hand-made one whose every number is worked out below, and
``trace_slice_spans.json``, 145 ms from the middle of a traced 30 s window
of ``youtube-audio-128.mixed-closed`` recorded on one TPU v5 lite (op names
already shortened): four search programs, the last cut by the slice's end,
and three whole flushes.
"""
from __future__ import annotations

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import devtrace as tr  # noqa: E402
import run as bench  # noqa: E402
import spans  # noqa: E402

DEV, OPS, MODS = "/device:TPU:0", tr.OPS_LINE, tr.MODULES_LINE
LOOP, WORKER = "python3", "worker"
SPAN_READERS = ("loop_ms_per_flush.closed", "prep_ms_per_flush.closed",
                "handoff_ms_per_flush.closed", "fetch_tail_ms.closed",
                "unspanned_gap_ms.closed")
COUNTER_READERS = ("queue_wait_ms.closed", "frontier_fill.closed",
                   "hop_yield.closed")


def flush_spans(form, prep, launch, wait, fetch, map_, post):
    """One flush's seven spans from (start, end) pairs in us."""
    names = spans.FLUSH
    threads = (LOOP, WORKER, WORKER, WORKER, WORKER, WORKER, LOOP)
    return [("/host:CPU", t, n, s * 1e3, (e - s) * 1e3) for n, t, (s, e)
            in zip(names, threads, (form, prep, launch, wait, fetch, map_,
                                    post))]


def hand_made():
    """Window [0, 1000] us. Search programs [100, 400] and [600, 800] us,
    three hop launches between them. Flush 0 and flush 1, in us:

    ======  ========  ========  ========  ========  ========  ========  ========
    flush   form      prep      launch    wait      fetch     map       post
    ======  ========  ========  ========  ========  ========  ========  ========
    0       50-60     65-75     75-95     95-410    410-430   430-440   446-460
    1       470-480   484-500   500-520   520-805   805-815   815-820   823-830
    ======  ========  ========  ========  ========  ========  ========  ========

    On the loop besides: ``serve.reap`` 20-23, ``serve.admit`` 462-464 and
    835-837, ``bench.record`` 465-468; and a runtime event over the whole
    device gap that counts as no span.

    * loop: form, post, admit, reap = 10 + 14 + 10 + 7 + 2 + 2 + 3 = 48 us
      over 2 programs: 0.024 ms;
    * prep: prep, launch, map = (10 + 20 + 10) + (16 + 20 + 5) = 81 us over
      2: 0.0405 ms;
    * hand-offs: flush 0 (65 - 60) + (446 - 440) = 11 us, flush 1 (484 -
      480) + (823 - 820) = 7 us: mean 0.009 ms;
    * fetch tail: 430 - 400 = 30 us and 815 - 800 = 15 us: 0.0225 ms;
    * the one gap, 400-600 us, is covered but for 460-462, 464-465 and
      468-470: 0.005 ms.

    Counters: 128 requests dispatched in 2 flushes, 0.128 s of queue wait
    (1 ms each); 384 nodes expanded, 192 a flush, against 1.5 launches of B
    * W = 64 * 4 slots: a fill of 0.5; 1,536 rows gathered against 384 * 16
    candidate slots: a yield of 0.25."""
    us = 1e3
    return [
        ("/host:CPU", LOOP, tr.WINDOW_SPAN, 0.0, 1000 * us),
        (DEV, MODS, "jit__search_improvised_jit(1)", 100 * us, 300 * us),
        (DEV, OPS, "while.1", 100 * us, 300 * us),
        (DEV, OPS, "hop_kernel_call.1", 110 * us, 90 * us),
        (DEV, OPS, "hop_kernel_call.2", 260 * us, 130 * us),
        (DEV, MODS, "jit__search_improvised_jit(1)", 600 * us, 200 * us),
        (DEV, OPS, "while.1", 600 * us, 200 * us),
        (DEV, OPS, "hop_kernel_call.3", 610 * us, 180 * us),
        ("/host:CPU", LOOP, spans.REAP, 20 * us, 3 * us),
        *flush_spans((50, 60), (65, 75), (75, 95), (95, 410), (410, 430),
                     (430, 440), (446, 460)),
        ("/host:CPU", LOOP, spans.ADMIT, 462 * us, 2 * us),
        ("/host:CPU", LOOP, "bench.record", 465 * us, 3 * us),
        *flush_spans((470, 480), (484, 500), (500, 520), (520, 805),
                     (805, 815), (815, 820), (823, 830)),
        ("/host:CPU", LOOP, spans.ADMIT, 835 * us, 2 * us),
        ("/host:CPU", "pjrt", "CommonPjRtBuffer::ToLiteral", 400 * us,
         200 * us),
    ]


STATS = {"dispatched": 128, "flushes": 2, "queue_wait_s": 0.128,
         "nodes_expanded": 384, "rows_gathered": 1536}


def reader_run(red, stats=STATS, B=64):
    """What the readers ask of a run."""
    return types.SimpleNamespace(
        trace=red, window_stats=stats,
        hop_shape=lambda: (B, 4, 16, 128, 4))


def test_hand_made_trace():
    red = tr.Reduced(hand_made())
    fl = spans.flushes(red)
    assert len(fl) == 2
    assert [(f[spans.FORM], f[spans.POST]) for f in fl] == [
        ((50e3, 60e3), (446e3, 460e3)), ((470e3, 480e3), (823e3, 830e3))]
    pairs = spans.fetch_of_runs(red)
    assert [f[3] for _, f in pairs] == [410e3, 805e3]
    assert spans.unspanned_gaps_ns(red) == pytest.approx([5e3])
    run = reader_run(red)
    want = {"loop_ms_per_flush.closed": 0.024,
            "prep_ms_per_flush.closed": 0.0405,
            "handoff_ms_per_flush.closed": 0.009,
            "fetch_tail_ms.closed": 0.0225,
            "unspanned_gap_ms.closed": 0.005,
            "queue_wait_ms.closed": 1.0,
            "frontier_fill.closed": 0.5,
            "hop_yield.closed": 0.25}
    for name, v in want.items():
        assert bench.reader(name)(run) == pytest.approx(v), name


def test_readers_find_nothing_without_their_sources():
    no_trace = reader_run(None)
    for name in SPAN_READERS + ("frontier_fill.closed",):
        assert bench.reader(name)(no_trace) is None, name
    # a trace of a program that writes no span: the benchmark's alone
    bare = [e for e in hand_made() if not e[2].startswith("serve.")]
    for name in SPAN_READERS:
        assert bench.reader(name)(reader_run(tr.Reduced(bare))) is None, name
    # stats of an engine without the counters
    old = {"dispatched": 128, "flushes": 2}
    for name in COUNTER_READERS:
        assert bench.reader(name)(
            reader_run(tr.Reduced(hand_made()), old)) is None, name


def test_recorded_trace():
    red = tr.Reduced(tr.load_events(os.path.join(HERE,
                                                 "trace_slice_spans.json")))
    runs = red.search_runs()
    fl = spans.flushes(red)
    assert len(runs) >= 3 and len(fl) >= 3
    # every program's results come back in a fetch of its own, after it;
    # the slice ends inside the last program, before its fetch
    pairs = spans.fetch_of_runs(red)
    assert [r for r, _ in pairs] == runs[:-1]
    assert len({f for _, f in pairs}) == len(pairs)
    for r, f in pairs:
        assert f[3] + f[4] > r[3] + r[4]
    # the program's spans and hand-offs leave little of each gap unnamed
    gaps = red.flush_gaps_s()
    free = spans.unspanned_gaps_ns(red)
    assert len(free) == len(gaps)
    assert sum(free) * 1e-9 <= 0.2 * sum(gaps)
    run = reader_run(red)
    for name in SPAN_READERS:
        v = bench.reader(name)(run)
        assert v is not None and v >= 0.0, name
