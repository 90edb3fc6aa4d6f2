"""The reduction from a device trace to numbers (``devtrace.py``) and the
metric readers that use it.

Runs by hand with the benchmark's other tests:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Two traces: a hand-made one whose every number is worked out below, and
``trace_slice.json``, 70 ms of the device and host events recorded on one
TPU v5 lite while the closed loop served a 2^14-object index (the middle of
a 2 s traced window, op names already shortened).
"""
from __future__ import annotations

import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import devtrace as tr  # noqa: E402
import roofline  # noqa: E402
import run as bench  # noqa: E402

DEV, OPS, MODS = "/device:TPU:0", tr.OPS_LINE, tr.MODULES_LINE
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def hand_made():
    """Window [0, 1 ms]. Two search programs, [0.1, 0.4] and [0.6, 0.8]
    ms, each a while loop holding hop launches; the host waits on a result
    across the gap between them."""
    us = 1e3
    return [
        ("/host:CPU", "python3", tr.WINDOW_SPAN, 0.0, 1000 * us),
        (DEV, MODS, "jit__search_improvised_jit(1)", 100 * us, 300 * us),
        (DEV, OPS, "while.1", 100 * us, 300 * us),
        (DEV, OPS, "hop_kernel_call.1", 110 * us, 90 * us),
        (DEV, OPS, "fusion.1", 210 * us, 40 * us),
        (DEV, OPS, "hop_kernel_call.2", 260 * us, 130 * us),
        (DEV, MODS, "jit__search_improvised_jit(1)", 600 * us, 200 * us),
        (DEV, OPS, "while.1", 600 * us, 200 * us),
        (DEV, OPS, "hop_kernel_call.3", 610 * us, 180 * us),
        ("/host:CPU", "worker", "np.asarray(jax.Array)", 380 * us,
         240 * us),
        ("/host:CPU", "python3", "bench.record", 450 * us, 10 * us),
    ]


def reader_run(red, B=64):
    """What the trace readers ask of a run."""
    return types.SimpleNamespace(
        trace=red, peaks=PEAKS,
        hop_shape=lambda: (B, 4, 16, 128, 4))


def test_hand_made_trace():
    red = tr.Reduced(hand_made())
    assert red.window_s == pytest.approx(1e-3)
    assert red.busy_s() == pytest.approx(5e-4)          # 0.3 + 0.2 ms
    assert red.idle_share() == pytest.approx(0.5)
    assert len(red.search_runs()) == 2
    assert [e[2] for e in red.hop_ops()] == [
        "hop_kernel_call.1", "hop_kernel_call.2", "hop_kernel_call.3"]
    assert red.flush_gaps_s() == pytest.approx([2e-4])
    # control flow is left out of the table of ops
    assert [k for k, _ in red.top_ops()] == [
        "hop_kernel_call.3", "hop_kernel_call.2", "hop_kernel_call.1",
        "fusion.1"]
    gaps = dict(red.idle_gaps())
    # [0, 0.1] and [0.8, 1.0] ms: no host event; [0.4, 0.6]: the wait
    assert gaps == pytest.approx({tr.NO_SPAN: 3e-4,
                                  "np.asarray(jax.Array)": 2e-4})
    run = reader_run(red)
    assert bench.reader("hops_per_flush.closed")(run) == 1.5
    assert bench.reader("hop_ms_per_flush.closed")(run) == \
        pytest.approx(0.2)                                # 0.4 ms / 2
    assert bench.reader("search_ms_per_flush.closed")(run) == \
        pytest.approx(0.25)
    assert bench.reader("flush_gap_ms.closed")(run) == pytest.approx(0.2)
    assert bench.reader("idle_share.closed")(run) == pytest.approx(0.5)
    # 64 * 4 * 16 candidates of 4 + 512 bytes over 819 GB/s, 3 launches,
    # over 0.4 ms of hop time
    least = 64 * 4 * 16 * 516 / 819e9
    assert bench.reader("hop_roofline.closed")(run) == \
        pytest.approx(100 * 3 * least / 4e-4)


def test_readers_find_nothing_without_a_trace():
    run = types.SimpleNamespace(trace=None, peaks=PEAKS)
    for name in ("idle_share.closed",
                 "hop_roofline.closed", "hop_ms_per_flush.closed",
                 "search_ms_per_flush.closed", "hops_per_flush.closed",
                 "flush_gap_ms.closed"):
        assert bench.reader(name)(run) is None


def test_roofline_counts():
    assert roofline.hop_bytes(64, 4, 16, 128, 4) == 64 * 4 * 16 * 516
    assert roofline.hop_flops(64, 4, 16, 128) == 64 * 4 * 16 * 256
    # bytes bound the hop: 0.5 flop per byte against a ridge near 240
    assert roofline.least_seconds(1e9, 1e9, PEAKS) == 1e9 / 819e9


def test_recorded_trace():
    red = tr.Reduced(tr.load_events(os.path.join(HERE, "trace_slice.json")))
    assert red.devices == [DEV]
    # the union of op intervals, by a 1 us raster
    w0 = red.w0
    raster = np.zeros(int(np.ceil(red.window_s * 1e6)) + 1, bool)
    for e in red.ops:
        a = max(int((e[3] - w0) / 1e3), 0)
        b = min(int(np.ceil((e[3] + e[4] - w0) / 1e3)), len(raster))
        raster[a:b] = True
    assert red.busy_s() == pytest.approx(raster.sum() * 1e-6, rel=0.01)
    assert 0.0 < red.idle_share() < 1.0
    hops = red.hop_ops()
    assert len(hops) == 57
    assert red.top_ops()[0][0] == hops[0][2]
    assert len(red.search_runs()) == 3
    assert red.flush_gaps_s() == pytest.approx([0.0057054, 0.0057038],
                                               rel=1e-3)
    share = bench.reader("hop_roofline.closed")(reader_run(red))
    assert 0.0 < share < 100.0
    assert sum(v for _, v in red.idle_gaps()) == pytest.approx(
        red.window_s - red.busy_s())
