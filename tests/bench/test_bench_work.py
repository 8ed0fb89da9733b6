"""Operations and bytes of one APC iteration (bench/work.py) and the peaks
table (bench/peaks.json)."""
from __future__ import annotations

import json

import pytest

import bench_testkit  # noqa: F401  (import paths)
from bench import work


def test_dense_shape_tall16k_at_k16():
    w = work.apc_iteration(m=8, p=2048, n=8192, width=8192, k=16)
    assert w.flops == 4 * 16 * 8 * 2048 * 8192
    assert w.bytes == 2 * 8 * 2048 * 8192 * 4 + 2 * 16 * 9 * 8192 * 4
    b = work.least_time(w, work.load_peaks("TPU v5 lite"))
    assert b.by == "memory"
    assert b.seconds == pytest.approx(w.bytes / 819e9)
    assert 1.3e-3 < b.seconds < 1.4e-3


def test_sparse_shape_at_k1():
    w = work.apc_iteration(m=4, p=2048, n=8192, width=2560, k=1)
    assert w.flops == 4 * 1 * 4 * 2048 * 2560
    assert w.bytes == 2 * 4 * 2048 * 2560 * 4 + 2 * 1 * 5 * 8192 * 4
    b = work.least_time(w, work.load_peaks("TPU v5 lite"))
    assert b.by == "memory"
    assert b.seconds == pytest.approx(w.bytes / 819e9)


def test_compute_bound_when_the_batch_is_wide():
    w = work.apc_iteration(m=1, p=8192, n=128, width=128, k=1024)
    assert work.least_time(w, work.load_peaks("TPU v5 lite")).by == \
        "compute"


def test_peaks_table_has_its_source_and_refuses_unknown_devices(tmp_path):
    table = json.loads(work.PEAKS_FILE.read_text())
    assert "819" in table["source"] and "197" in table["source"]
    v5e = work.load_peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="TPU v9"):
        work.load_peaks("TPU v9")
    with pytest.raises(KeyError, match="cpu"):
        work.load_peaks("cpu")
