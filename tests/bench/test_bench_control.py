"""The comparison that decides ``correct`` has to fail: the control (the
reference one precision below the configuration's, bench/control.py) and
each fault a cell can have, planted under the timed path of a whole run
(the look for a chip skipped).

Faults: a step that returns its state unchanged; half of a batch left out
(every other slot, the first included, keeps its initial state) where the
cell's mix batches more than one request; an answer altered where it is
produced.  The cells run on one chip, so there is no exchange between
chips to leave out; and half of the tall system's row blocks still
determine x, so an exchange cut to half of the workers is no fault there.
"""
from __future__ import annotations

import copy
import json

import jax.numpy as jnp
import pytest

from bench_testkit import (CELLS, SECONDS, SPEC, STEPS,  # noqa: F401
                           check_control, f32, tiny_files)
from bench import control, harness
from repro.solvers.projection import APCSolver


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, f32, tmp_path):  # noqa: F811
    check_control(cell, tiny_files(tmp_path))


def test_closed_loop_control_is_not_correct(f32, tmp_path):  # noqa: F811
    """The closed stream's control feeds its own answers back, step by
    step, and fails the same comparison."""
    cell = "tall16k.steps"
    (tmp_path / "cells").mkdir()
    (tmp_path / "cells" / f"{cell}.json").write_text(json.dumps(
        {"config": "tall_gauss_16k", "traffic": "closed_stream"}))
    spec = copy.deepcopy(SPEC)
    spec["workloads"].append({"name": cell, "config": "tall_gauss_16k",
                              "traffic": "closed_stream", "chips": 1,
                              "why": "test"})
    r = control.control_run(cell, 13, SECONDS, steps=STEPS, spec=spec,
                            files=tiny_files(tmp_path))
    assert r["answers"] == STEPS
    c = r["compared"]["max_rel_residual"]
    assert c["value"] > c["limit"]
    assert r["correct"] is False


def _unchanged(orig):
    def step(self, factors, Bb, states, params):
        return states, jnp.zeros(Bb.shape[0], Bb.dtype)
    return step


def _half_batch(orig):
    def step(self, factors, Bb, states, params):
        new, rsq = orig(self, factors, Bb, states, params)
        keep = (jnp.arange(Bb.shape[0]) % 2) == 1
        return (new._replace(
            x=jnp.where(keep[:, None, None], new.x, states.x),
            xbar=jnp.where(keep[:, None], new.xbar, states.xbar)), rsq)
    return step


def _altered(orig):
    def extract(self, state):
        return orig(self, state).at[..., 0].multiply(-1.0)
    return extract


FAULTS = {
    "unchanged": ("step_many_residual", _unchanged),
    "half_batch": ("step_many_residual", _half_batch),
    "altered": ("extract", _altered),
}


def _faults(cell: str):
    """The faults ``cell`` can have: half a batch only where its mix
    batches more than one request."""
    batch = int(harness.resolve(cell, SPEC).mix["batch"])
    return [f for f in FAULTS if f != "half_batch" or batch > 1]


CASES = [(cell, fault) for cell in CELLS for fault in _faults(cell)]


@pytest.mark.parametrize("cell,fault", CASES)
def test_planted_fault_is_not_correct(cell, fault, f32,  # noqa: F811
                                      monkeypatch, tmp_path):
    name, make = FAULTS[fault]
    monkeypatch.setattr(APCSolver, name, make(getattr(APCSolver, name)))
    r = harness.run_cell(cell, 2**31 + 5, SECONDS, False,
                         files=tiny_files(tmp_path), require_tpu=False)
    c = r["compared"]["max_rel_residual"]
    assert c["value"] > 10 * c["limit"]
    assert r["correct"] is False
