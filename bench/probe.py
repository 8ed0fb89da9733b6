"""Where a cell's set-up and a served request's time go, beyond what the
result line reports.

    python bench/probe.py --workload tall16k.open --seed 7 --seconds 20 --trace 1

One run of the cell through ``harness.run_cell`` (``--trace 0`` there, so
the result's metrics are the end-to-end ones), with three readings of the
program that the result line does not carry:

- ``setup_spans``: the program's span totals (``repro.runtime.spans``) at
  the end of set-up, per span name ``[count, seconds, self seconds]``;
- ``stages_ms``: the async server's stage means over the window
  (``stage_means()``: queue, hold, run), beside the mean latency of the
  served requests and the generator's mean lateness, which with the three
  stages make it up (``sum_gap_pct``: their sum against it);
- ``device_scopes`` (``--trace 1``, the window under the profiler): the
  device's self seconds per ``jax.named_scope`` phase, read from each
  op's ``tf_op`` metadata in the trace, as ``[scope, seconds]``, longest
  first.  A scope is the first of ``SCOPES`` in the op's name stack and
  the frame under it (``apc.step/jit(_cho_solve)``); ``(none)`` where the
  stack holds none of them.

The last line of standard output is one JSON object of these and the
result's ``correct``, ``metrics`` and ``timing``.  It needs a program with
the span module and the stage record, and runs on a TPU only (as
``bench/run.py``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
SCOPES = ("apc.init", "apc.step", "residual")


def op_tf_ops(path: str) -> Dict[str, str]:
    """Device op name (as ``bench.trace`` names it) -> its ``tf_op`` stat,
    the name stack of the JAX code the op came from."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from bench import trace
    space = xplane_pb2.XSpace()
    space.ParseFromString(Path(path).read_bytes())
    out: Dict[str, str] = {}
    for plane in space.planes:
        if not plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        for md in plane.event_metadata.values():
            for st in md.stats:
                if stat_names.get(st.metadata_id) == "tf_op":
                    out[trace.op_name(md.name)] = (
                        st.str_value or stat_names.get(st.ref_value, ""))
    return out


def scope_of(tf_op: str) -> str:
    """``jit(_cold)/while/body/apc.step/jit(_cho_solve)/dot_general`` ->
    ``apc.step/jit(_cho_solve)``; ``(none)`` where no ``SCOPES`` frame is
    in the stack."""
    frames = tf_op.split(":")[0].split("/")
    for i, frame in enumerate(frames):
        if frame in SCOPES:
            return "/".join(frames[i:i + 2])
    return "(none)"


def device_scopes(path: str) -> Optional[List[Tuple[str, float]]]:
    """Self seconds of the device's ops per scope inside the window (the
    ``bench.window`` span), longest first; None where no op ran."""
    from bench import trace
    tr = trace.read_xplane(path)
    window = trace.window_of(tr)
    if window is None:
        return None
    tf_ops = op_tf_ops(path)
    out: Dict[str, float] = {}
    for ops in tr.device.values():
        for name, ns in trace._self_ns(list(trace._clip(ops, *window))
                                       ).items():
            scope = scope_of(tf_ops.get(name, ""))
            out[scope] = out.get(scope, 0.0) + ns / 1e9
    return sorted(out.items(), key=lambda kv: -kv[1])


def _probe_files(files, rec: dict, trace_dir: Optional[str]):
    """``files`` whose traffic loops also leave their set-up span totals,
    window record and stage record in ``rec``, and run the window under
    the profiler where ``trace_dir`` is given."""
    from bench import harness

    class ProbeFiles(harness.Files):
        def module(self, kind, name):
            mod = super().module(kind, name)
            if kind == "traffic" and hasattr(mod, "Loop"):
                mod.Loop = _probe_loop(mod.Loop, rec, trace_dir)
            return mod

    return ProbeFiles(files.dirs)


def _probe_loop(base, rec: dict, trace_dir: Optional[str]):
    from repro.runtime import spans

    class Loop(base):
        def setup(self):
            super().setup()
            rec["setup_spans"] = {k: list(v) for k, v
                                  in spans.totals().items()}

        def window(self):
            if trace_dir is None:
                out = super().window()
            else:
                import jax
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                try:
                    out = super().window()
                finally:
                    jax.profiler.stop_trace()
            rec["out"], rec["stages_ms"] = out, self.server.stage_means()
            return out

    return Loop


def stage_means(out: dict, stages_ms: Dict[str, float]) -> Dict[str, float]:
    """The server's stage means (``queue_ms``, ``hold_ms``, ``run_ms``)
    with the served requests' mean lateness and latency (the window
    record's), and how far the four fall short of the latency."""
    import numpy as np
    served = np.array([s == "served" for s in out["status"]])
    res = dict(stages_ms)
    res["late_ms"] = 1e3 * float(np.asarray(out["late_s"])[served].mean())
    res["latency_ms"] = 1e3 * float(np.asarray(out["latency_s"])[served]
                                    .mean())
    res["sum_gap_pct"] = 100.0 * (
        sum(res[k] for k in ("queue_ms", "hold_ms", "run_ms", "late_ms"))
        / res["latency_ms"] - 1.0)
    res["served"] = int(served.sum())
    return res


def probe(cell: str, seed: int, seconds: float, trace: bool, *,
          files=None, t_start: Optional[float] = None,
          require_tpu: bool = True) -> dict:
    from bench import harness, trace as trace_mod
    rec: dict = {}
    trace_dir = tempfile.mkdtemp(prefix="bench_probe_") if trace else None
    try:
        result = harness.run_cell(
            cell, seed, seconds, False, t_start=t_start,
            files=_probe_files(files or harness.Files(), rec, trace_dir),
            require_tpu=require_tpu)
        scopes = (device_scopes(trace_mod.find_xplane(trace_dir))
                  if trace else None)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return {"correct": result["correct"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "timing": result["timing"],
            "setup_spans": rec["setup_spans"],
            "stages_ms": stage_means(rec["out"], rec["stages_ms"]),
            "device_scopes": scopes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import harness, run
    run.enable_compile_cache(jax)
    jax.config.update("jax_enable_x64", False)
    try:
        rep = probe(args.workload, args.seed, args.seconds,
                    bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(rep))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
