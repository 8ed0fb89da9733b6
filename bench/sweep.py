"""Find the knee of an open-loop cell: the highest offered rate that the
server sustains without a growing backlog, in one process on the chip.

    python bench/sweep.py --workload tall16k.open --seed 5 --seconds 10 \\
        --rates 24,32,40,44,48,52,56

Set-up runs once; then one window per rate, lowest first, each with its own
requests.  A rate is sustained when nothing is shed, every request is
answered, and the last third of the window's requests wait no longer than
1.2 times the first third (a backlog that grows shows there).  One JSON
line per rate, then the knee and 0.8 times it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
GROWTH = 1.2


def rate_row(rate: float, out: dict) -> dict:
    lat = out["latency_s"]
    third = max(len(lat) // 3, 1)
    first, last = float(np.mean(lat[:third])), float(np.mean(lat[-third:]))
    status = out["status"]
    row = {"rate_per_s": rate, "attempted": out["attempted"],
           "served": status.count("served"), "shed": status.count("shed"),
           "unanswered": len(status) - status.count("served")
           - status.count("shed"),
           "p50_ms": 1e3 * float(np.percentile(lat, 50)),
           "p95_ms": 1e3 * float(np.percentile(lat, 95)),
           "first_third_ms": 1e3 * first, "last_third_ms": 1e3 * last,
           "window_s": out["window_s"],
           "batch_fill": out["stats"]["served"]
           / max(out["stats"]["served"] + out["stats"]["padded"], 1)}
    row["sustained"] = (row["shed"] == 0 and row["unanswered"] == 0
                        and last <= GROWTH * first)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import harness, load, run
    run.enable_compile_cache(jax)
    jax.config.update("jax_enable_x64", False)
    rates = sorted(float(r) for r in args.rates.split(","))

    files = harness.Files()
    c = harness.resolve(args.workload, files=files)
    harness.device_info(int(c.entry["chips"]), require_tpu=True)
    problem = c.build(args.seed)
    traffic = load.make_traffic(files, problem, c.cfg,
                                {**c.mix, "rate_per_s": rates[0]},
                                args.seed, args.seconds)
    rows = []
    try:
        traffic.setup()
        for i, rate in enumerate(rates):
            traffic.schedule(rate, args.seed + i, 100 + i)
            traffic.server.reset_metrics()
            row = rate_row(rate, traffic.window())
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        traffic.close()
    knee = None
    for r in rows:                       # the last rate before a failure
        if not r["sustained"]:
            break
        knee = r["rate_per_s"]
    print(json.dumps({"knee_per_s": knee,
                      "cell_rate_per_s": None if knee is None
                      else 0.8 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
