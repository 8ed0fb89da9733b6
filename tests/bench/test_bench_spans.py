"""The set-up metrics that read the program's span totals
(bench/program_spans.py): each reader on fixed totals; against a program
with no spans module every one of them reads None and every other metric
reads what it read before; and a traced run on the CPU."""
from __future__ import annotations

import sys

import numpy as np
import pytest

from bench_testkit import SECONDS, f32, tiny_files  # noqa: F401
from bench import harness, trace, work
import repro.runtime
from repro.runtime import spans

# metric -> (span, field) it reads
NEW = {"fingerprint_s": ("repro.store.fingerprint", "total_s"),
       "prepare_s": ("repro.store.prepare", "total_s"),
       "autotune_s": ("repro.ops.autotune", "self_s"),
       "compile_s": ("repro.linsys.compile", "self_s"),
       "eig_s": ("repro.spectral.eig", "total_s"),
       "x_matrix_s": ("repro.spectral.x_matrix", "total_s")}
OLD = ("analyze_s", "warm_s", "setup_s", "batch_fill.open",
       "gen_late_ms.open", "idle_share.open", "iter_us.open",
       "iter_roofline.open", "iters_to_tol.open", "lat_p95_ms")


def _read(name, run):
    return harness.Files().module("metrics", name).read(run)


@pytest.fixture
def no_spans(monkeypatch):
    """The benchmark's view of a program that has no spans module."""
    monkeypatch.delattr(repro.runtime, "spans")
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)


class _Problem:
    m, p, n, width = 8, 2048, 8192, 8192


def _fixed_run():
    out = {"attempted": 32, "window_s": 2.0,
           "stats": {"served": 30, "padded": 2, "batches": 2, "shed": 0},
           "latency_s": np.linspace(1.0, 1.5, 30),
           "late_s": np.linspace(0.0, 0.002, 30),
           "iters_to_tol": [5, -1, 7]}
    summary = trace.Summary(busy_s=0.5, window_s=1.0, chips=1,
                            device_ops=[], idle_gaps=[])
    return harness.Run(cell="tall16k.open", cfg={"iters": 10}, mix={},
                       problem=_Problem(), out=out, setup_s=60.0,
                       analyze_s=25.0, warm_s=20.0, trace=summary,
                       peaks=work.load_peaks("TPU v5 lite"))


def _old_expected():
    run = _fixed_run()
    least = work.least_time(work.apc_iteration(
        m=8, p=2048, n=8192, width=8192, k=15.0),
        run.peaks).seconds * 20
    return {"analyze_s": 25.0, "warm_s": 20.0, "setup_s": 60.0,
            "batch_fill.open": 100.0 * 30 / 32,
            "gen_late_ms.open": 1e3 * np.percentile(run.out["late_s"], 95),
            "idle_share.open": 50.0, "iter_us.open": 1e6 * 0.5 / 20,
            "iter_roofline.open": 100.0 * least / 0.5,
            "iters_to_tol.open": np.mean([5, 10, 7]),
            "lat_p95_ms": 1e3 * np.percentile(run.out["latency_s"], 95)}


def test_each_reader_reads_its_span_field(monkeypatch):
    fixed = {span: spans.Total(count=3, total_s=2.0 + i, self_s=1.0 + i)
             for i, (span, _) in enumerate(NEW.values())}
    monkeypatch.setattr(spans, "totals", lambda: dict(fixed))
    for name, (span, field) in NEW.items():
        assert _read(name, _fixed_run()) == getattr(fixed[span], field)
    # a span that never closed is no time
    monkeypatch.setattr(spans, "totals", dict)
    assert all(_read(name, _fixed_run()) == 0.0 for name in NEW)


@pytest.mark.parametrize("program", ["with_spans", "without_spans"])
def test_the_other_metrics_read_the_same_on_a_fixed_record(program,
                                                           request):
    if program == "without_spans":
        request.getfixturevalue("no_spans")
        assert all(_read(name, _fixed_run()) is None for name in NEW)
    expected = _old_expected()
    for name in OLD:
        assert _read(name, _fixed_run()) == pytest.approx(expected[name],
                                                          rel=1e-12)


def test_traced_run_reports_the_setup_split(tmp_path, f32):  # noqa: F811
    spans.reset()
    r = harness.run_cell("tall16k.open", 2**31 + 21, SECONDS, True,
                         files=tiny_files(tmp_path), require_tpu=False)
    assert r["correct"] is True
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(NEW) <= set(m)
    assert all(r["metrics"][k]["unit"] == "s" for k in NEW)
    assert min(m["fingerprint_s"], m["prepare_s"], m["compile_s"],
               m["eig_s"], m["x_matrix_s"]) > 0
    assert m["autotune_s"] >= 0         # interpret mode measures nothing
    assert (m["fingerprint_s"] + m["prepare_s"] + m["autotune_s"]
            + m["compile_s"]) <= m["warm_s"]
    assert m["eig_s"] + m["x_matrix_s"] < m["analyze_s"]


def test_without_program_spans_a_traced_run_leaves_them_out(
        tmp_path, f32, no_spans):  # noqa: F811
    r = harness.run_cell("tall16k.open", 2**31 + 23, SECONDS, True,
                         files=tiny_files(tmp_path), require_tpu=False)
    assert r["correct"] is True
    assert not set(NEW) & set(r["metrics"])
    assert {"analyze_s", "warm_s", "batch_fill.open", "gen_late_ms.open",
            "iters_to_tol.open"} <= set(r["metrics"])
