"""The control of the comparison that decides ``correct``: the plain
reference, put in the program's place and computed one precision below
what the configuration states (float32 at ``highest`` -> ``high``, three
bfloat16 passes: ``numerics.dot3``), answering the same requests as a run
and judged by the same comparison.  It has to come out not correct.  The
benchmark's own runs never run it.

    python bench/control.py --workload tall16k.open --seeds 1,2,3 --seconds 20
    python bench/control.py --workload tall16k.stream --seeds 1,2,3 \\
        --seconds 20 --steps 100

The open loop's requests are the run's own (same schedule, same right-hand
sides from the seed); the closed stream feeds the control's answers back,
as the program's would be, for ``--steps`` steps.  One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Optional

ROOT = Path(__file__).resolve().parents[1]


def control_run(cell: str, seed: int, seconds: float, *,
                steps: Optional[int] = None, spec: Optional[dict] = None,
                files=None) -> dict:
    from bench import harness, load
    files = harness.Files() if files is None else files
    c = harness.resolve(cell, spec, files)
    problem = c.build(seed)
    traffic = load.make_traffic(files, problem, c.cfg, c.mix, seed, seconds)
    t = time.perf_counter()
    X, B = traffic.replay(problem.control_solve, steps)
    solve_s = time.perf_counter() - t
    out = {"status": ["served"] * len(X), "X": X, "B": B}
    compared = harness.check(problem, c.cfg, out)
    return {"cell": cell, "seed": seed, "answers": len(X),
            "correct": all(v["value"] <= v["limit"]
                           for v in compared.values()),
            "solve_s": solve_s, "compared": compared}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--steps", type=int)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import run
    run.enable_compile_cache(jax)
    jax.config.update("jax_enable_x64", False)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(control_run(args.workload, seed, args.seconds,
                                     steps=args.steps)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
