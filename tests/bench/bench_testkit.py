"""Shared set-up of the benchmark's CPU tests: the import paths, float32
for the duration of a test, and tiny sizes of each configuration."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

# each configuration at a size a test run holds; the shape (N = 2n) is the
# configuration's own
TINY = {
    "tall_gauss_16k": {"N": 256, "n": 128, "m": 4, "iters": 30},
}
SPEC = harness.load_spec()
CELLS = sorted(w["name"] for w in SPEC["workloads"])
SECONDS = 1.0
STEPS = 20          # closed-stream steps the control answers


def tiny_files(directory: Path) -> harness.Files:
    """The benchmark's files with every configuration cut to its ``TINY``
    size: the cut copies are written to ``directory``, searched first."""
    (directory / "configs").mkdir(parents=True, exist_ok=True)
    for name, cut in TINY.items():
        cfg = harness.Files().json("configs", name)
        (directory / "configs" / f"{name}.json").write_text(
            json.dumps({**cfg, **cut}))
    return harness.Files([directory, harness.BENCH_DIR])


@pytest.fixture
def f32():
    """The benchmark runs with x64 off; the suite's conftest turns it on."""
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", prev)
