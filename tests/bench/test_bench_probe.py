"""bench/probe.py: the named-scope reader on the recorded chip trace, and
a probed run on the CPU (set-up span counts, stage means that make up the
latency)."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench_testkit import SECONDS, f32, tiny_files  # noqa: F401
from bench import probe
from repro.runtime import spans

RECORDED = str(Path(__file__).parent / "data" / "v5e_probe.xplane.pb")


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(_cold)/while/body/apc.step/jit(_cho_solve)/dot_general",
     "apc.step/jit(_cho_solve)"),
    ("jit(_cold)/apc.init/jit(_cho_solve)/triangular_solve",
     "apc.init/jit(_cho_solve)"),
    ("jit(_cold)/while/body/residual/dot_general:", "residual/dot_general"),
    ("jit(_cold)/while/body/residual", "residual"),
    ("jit(f)/dot_general", "(none)"),
    ("", "(none)")])
def test_scope_is_the_first_phase_frame_and_the_one_under_it(tf_op, scope):
    assert probe.scope_of(tf_op) == scope


def test_recorded_chip_trace_carries_tf_op():
    tf_ops = probe.op_tf_ops(RECORDED)
    assert set(tf_ops.values()) == {"jit(f)/dot_general:"}
    scopes = probe.device_scopes(RECORDED)
    assert [s for s, _ in scopes] == ["(none)"] and scopes[0][1] > 0


def test_probed_run_counts_setup_spans_and_stages(f32, tmp_path):  # noqa: F811
    spans.reset()           # the totals are the process's, as in a bench run
    rep = probe.probe("tall16k.open", 2**31 + 31, SECONDS, True,
                      files=tiny_files(tmp_path), require_tpu=False)
    assert rep["correct"] is True
    counts = {k: v[0] for k, v in rep["setup_spans"].items()}
    assert counts == dict.fromkeys(
        ("repro.spectral.x_matrix", "repro.spectral.eig",
         "repro.store.fingerprint", "repro.store.prepare",
         "repro.linsys.compile"), 1)
    st = rep["stages_ms"]
    assert st["served"] > 0
    assert min(st["queue_ms"], st["hold_ms"], st["run_ms"],
               st["late_ms"]) >= 0
    # the answer's callback comes after the stage record's t_done, and
    # little else: on a loaded host a few ms of a ~70 ms latency
    assert -20.0 < st["sum_gap_pct"] <= 0.0
    assert rep["device_scopes"] == []          # no TPU plane on the CPU
