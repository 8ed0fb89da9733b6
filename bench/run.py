"""Run one benchmark cell once on the chip and print its result.

    python bench/run.py --workload tall16k.open --seed 7 --seconds 20 --trace 0

Set-up (data from the seed, the program's analyze, register, the first
batches) is timed as ``setup_s``; then the window runs for ``--seconds``
with the profiler off (``--trace 0``: the end-to-end metrics) or on
(``--trace 1``: the per-layer metrics); then every answer of the window is
checked against the plain float64 reference.  The last line of standard
output is one JSON object; the numbers compared, with their limits, are
the last lines of standard error.  Exits 2, printing no result, where JAX
finds no TPU, fewer chips than the cell asks for, or a device that is not
in ``bench/peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(jax) -> str:
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else the fixed ``<checkout>/.jax_cache``; every program is kept,
    so that only a checkout's first run compiles."""
    path = os.environ.get(CACHE_ENV) or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    enable_compile_cache(jax)
    jax.config.update("jax_enable_x64", False)
    from bench import harness
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
