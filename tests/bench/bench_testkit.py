"""Shared set-up of the benchmark's CPU tests: the import paths, float32
for the duration of a test, each configuration cut to the ``tiny`` size
its own file gives, and the checks that every cell passes.  Every
expectation comes from the spec and the files, so a configuration or a
cell added by files alone is tested with no edit here."""
from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import Optional

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import control, harness  # noqa: E402

SPEC = harness.load_spec()
CELLS = sorted(w["name"] for w in SPEC["workloads"])
CONFIGS = sorted(c["name"] for c in SPEC["configs"])
SECONDS = 1.0
STEPS = 20          # closed-stream steps the control answers
TINY_OPERAND_BYTES = 16 * 2**20     # the most a tiny build may hold of A


def tiny_cfg(name: str, files: Optional[harness.Files] = None) -> dict:
    """Configuration ``name`` with its ``tiny`` keys put over it; a
    configuration whose file has no ``tiny`` is an error that names it."""
    cfg = (files or harness.Files()).json("configs", name)
    if not isinstance(cfg.get("tiny"), dict) or not cfg["tiny"]:
        raise KeyError(f"configuration {name!r} has no 'tiny' object of the "
                       f"keys its CPU tests override")
    return {**cfg, **cfg["tiny"]}


def tiny_files(directory: Path, spec: Optional[dict] = None,
               files: Optional[harness.Files] = None) -> harness.Files:
    """``files`` (the benchmark's by default) with every configuration of
    ``spec`` cut to its ``tiny`` size: the cut copies are written to
    ``directory``, searched first."""
    spec = SPEC if spec is None else spec
    files = harness.Files() if files is None else files
    (directory / "configs").mkdir(parents=True, exist_ok=True)
    for c in spec["configs"]:
        (directory / "configs" / f"{c['name']}.json").write_text(
            json.dumps(tiny_cfg(c["name"], files)))
    return harness.Files([directory, *files.dirs])


def operand_bytes(name: str, files: Optional[harness.Files] = None) -> int:
    """Bytes of the operand A in configuration ``name``'s tiny build."""
    files = harness.Files() if files is None else files
    cfg = tiny_cfg(name, files)
    problem = files.module("configs", cfg["generator"]).build(cfg, 1)
    return problem.m * problem.p * problem.width * 4


def check_end_to_end(cell: str, files: harness.Files,
                     spec: Optional[dict] = None, seed: int = 2**31 + 99):
    """An untraced run: correct, every request served, exactly the cell's
    end-to-end metrics, each above 0, nothing compiled in the window."""
    spec = SPEC if spec is None else spec
    r = harness.run_cell(cell, seed, SECONDS, False, spec=spec, files=files,
                         require_tpu=False)
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {
        m["name"] for m in harness.metric_entries(spec, cell, "end_to_end")}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["compiles_in_window"] == 0
    assert list(r)[-1] == "compared"
    res = r["compared"]["max_rel_residual"]
    assert res["value"] <= res["limit"]
    assert r["device"]["platform"] == "cpu"
    return r


def check_traced(cell: str, files: harness.Files,
                 spec: Optional[dict] = None, seed: int = 3):
    """A traced run: correct; every per-layer metric of the cell that does
    not come from the device trace reads a number, and none of those that
    do (the CPU trace holds no TPU plane); no end-to-end metric."""
    spec = SPEC if spec is None else spec
    r = harness.run_cell(cell, seed, SECONDS, True, spec=spec, files=files,
                         require_tpu=False)
    assert r["correct"] is True
    names = set(r["metrics"])
    per_layer = harness.metric_entries(spec, cell, "per_layer")
    assert names == {m["name"] for m in per_layer
                     if m["source"] != "device_trace"}
    assert not names & {m["name"] for m in spec["end_to_end"]}
    assert "busy_s" not in r["device"]
    return r


def check_control(cell: str, files: harness.Files,
                  spec: Optional[dict] = None, seed: int = 11):
    """The control answers the cell's requests and is not correct."""
    r = control.control_run(cell, seed, SECONDS, steps=STEPS, spec=spec,
                            files=files)
    assert r["answers"] > 0
    c = r["compared"]["max_rel_residual"]
    assert c["value"] > c["limit"]
    assert r["correct"] is False
    return r


@pytest.fixture
def f32():
    """The benchmark runs with x64 off; the suite's conftest turns it on."""
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", prev)
