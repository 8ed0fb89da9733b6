"""The trace reduction (bench/trace.py): a synthetic event list with
overlapping ops and known gaps, and a small trace recorded on a TPU v5e."""
from __future__ import annotations

from pathlib import Path

import pytest

import bench_testkit  # noqa: F401  (import paths)
from bench import trace

DATA = Path(__file__).resolve().parent / "data" / "v5e_probe.xplane.pb"
MS = 1_000_000.0


def _synthetic():
    ops = [(0 * MS, 4 * MS, "fusion.1"),      # overlaps the next op
           (3 * MS, 5 * MS, "fusion.2"),
           (5 * MS, 6 * MS, "copy-start"),    # touches: no gap at 5 ms
           (9 * MS, 10 * MS, "fusion.1"),     # gap 6..9 ms
           (16 * MS, 17 * MS, "fusion.2")]    # gap 10..16 ms
    spans = [(-1 * MS, 20 * MS, "bench.window"),
             (6 * MS, 8.5 * MS, "bench.sleep"),
             (8.5 * MS, 9 * MS, "bench.submit"),
             (10 * MS, 15 * MS, "bench.step"),
             (15 * MS, 16 * MS, "bench.next_rhs")]
    return trace.Trace(device={"/device:TPU:0": ops}, spans=spans)


def test_merge_unions_overlapping_and_touching_intervals():
    got = trace.merge([(3, 5), (0, 4), (5, 6), (9, 10)])
    assert got == [(0, 6), (9, 10)]


def test_reduce_synthetic_busy_ops_and_gaps():
    s = trace.reduce(_synthetic())
    assert s.chips == 1
    assert s.window_s == pytest.approx(0.021)
    assert s.busy_s == pytest.approx(0.008)          # 0-6, 9-10, 16-17 ms
    ops = dict(s.device_ops)
    assert ops["fusion.1"] == pytest.approx(0.005)
    assert ops["fusion.2"] == pytest.approx(0.003)
    assert s.device_ops[0][0] == "fusion.1"          # longest first
    # gaps: 10-16 (step), -1-0 (window start), 6-9 (sleep), 17-20 (none)
    names = [n for n, _ in s.idle_gaps]
    secs = [g for _, g in s.idle_gaps]
    assert secs == pytest.approx([0.006, 0.003, 0.003, 0.001])
    assert names[0] == "bench.step"
    assert set(names[1:3]) == {"bench.sleep", "no bench span"}


def test_nested_ops_count_their_self_time():
    ops = [(0 * MS, 10 * MS, "while.1"), (1 * MS, 3 * MS, "a"),
           (4 * MS, 9 * MS, "b"), (5 * MS, 6 * MS, "c")]
    s = trace.reduce(trace.Trace(device={"/device:TPU:0": ops}, spans=[]))
    assert s.busy_s == pytest.approx(0.010)
    assert dict(s.device_ops) == pytest.approx(
        {"while.1": 0.003, "a": 0.002, "b": 0.004, "c": 0.001})
    assert sum(dict(s.device_ops).values()) == pytest.approx(s.busy_s)


def test_reduce_clips_to_an_explicit_window_and_averages_chips():
    t = _synthetic()
    t.device["/device:TPU:1"] = [(0, 2 * MS, "fusion.9")]
    s = trace.reduce(t, window=(0.0, 10 * MS))
    assert s.chips == 2
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx((0.007 + 0.002) / 2)


def test_reduce_without_device_ops_reads_nothing():
    t = trace.Trace(device={"/device:TPU:0": []},
                    spans=[(0.0, 1e9, "bench.window")])
    assert trace.reduce(t) is None


def test_op_name_strips_the_hlo_text():
    assert trace.op_name("%fusion.12 = f32[8]{0} fusion(f32[8] %p)") == \
        "fusion.12"
    assert trace.op_name("copy-start") == "copy-start"


def test_recorded_v5e_trace():
    """Three calls of a jitted two-matmul program with host spans, traced
    on one TPU v5 lite chip (about 16 KB)."""
    t = trace.read_xplane(str(DATA))
    assert list(t.device) == ["/device:TPU:0"]
    assert len(t.device["/device:TPU:0"]) == 12
    assert [n for *_, n in t.spans] == ["bench.submit", "bench.wait"] * 3
    s = trace.reduce(t)
    assert s.chips == 1
    assert s.busy_s == pytest.approx(549.246e-6, rel=1e-6)
    assert s.window_s == pytest.approx(23.560726e-3, rel=1e-6)
    names = [n for n, _ in s.device_ops]
    assert names[:2] == ["convolution_tanh_fusion", "fusion"]
    assert s.idle_gaps[0][0] == "bench.wait"
    assert 0 < s.busy_s < s.window_s
