"""Run one cell once: find its files by name, build the configuration from
the seed, set up, measure, check the answers, and assemble the result.

Everything that belongs to one configuration, traffic mix, cell or metric
sits in a file of its own that is found by the name in ``BENCHMARK.json``:

    bench/configs/<config>.json        sizes, tolerances, limit, generator,
                                       and ``tiny``: the keys the CPU tests
                                       override (never read by a run)
    bench/configs/<generator>.py       build(cfg, seed) and the reference
    bench/traffic/<mix>.json           the mix's parameters, its ``loop``
    bench/traffic/<loop>.py            Loop, the driver it names (bench/load.py)
    bench/cells/<cell>.json            config, mix, and the cell's params
    bench/metrics/<metric>.py          read(run) -> number or None

A later cell, mix, configuration or metric is a new file and a new entry
in ``BENCHMARK.json``; no file here changes, nor under ``tests/bench``,
whose tests read every expectation from ``BENCHMARK.json`` and each
configuration's file.  ``Files`` searches a list of directories in order,
so a test can put its own files first.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import numpy as np

from bench import load, numerics, trace as trace_mod

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "bench"
SPEC_FILE = ROOT / "BENCHMARK.json"

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a device not in the peaks table."""


def load_spec(path: Path = SPEC_FILE) -> dict:
    return json.loads(Path(path).read_text())


class Files:
    """Finds a benchmark file by kind and name in the first directory of
    ``dirs`` that holds it."""

    def __init__(self, dirs: Sequence[Path] = (BENCH_DIR,)):
        self.dirs = [Path(d) for d in dirs]

    def path(self, kind: str, name: str, ext: str) -> Path:
        for d in self.dirs:
            p = d / kind / f"{name}{ext}"
            if p.is_file():
                return p
        raise FileNotFoundError(f"no {kind}/{name}{ext} under "
                                f"{[str(d) for d in self.dirs]}")

    def json(self, kind: str, name: str) -> dict:
        return json.loads(self.path(kind, name, ".json").read_text())

    def module(self, kind: str, name: str):
        path = self.path(kind, name, ".py")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name}".replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


class Cell(NamedTuple):
    """A cell's entry in ``BENCHMARK.json`` with its files read: the
    configuration, the mix merged with the cell's ``params``, and the
    configuration's generator module."""
    entry: dict
    cfg: dict
    mix: dict
    generator: Any

    def build(self, seed: int):
        return self.generator.build(self.cfg, seed)


def resolve(cell: str, spec: Optional[dict] = None,
            files: Optional[Files] = None) -> Cell:
    """Find ``cell``'s entry and files by name."""
    spec = load_spec() if spec is None else spec
    files = Files() if files is None else files
    entry = next((w for w in spec["workloads"] if w["name"] == cell), None)
    if entry is None:
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json")
    cell_file = files.json("cells", cell)
    if (cell_file["config"], cell_file["traffic"]) != (entry["config"],
                                                       entry["traffic"]):
        raise ValueError(f"cells/{cell}.json names {cell_file['config']}/"
                         f"{cell_file['traffic']}, BENCHMARK.json "
                         f"{entry['config']}/{entry['traffic']}")
    cfg = files.json("configs", entry["config"])
    mix = {**files.json("traffic", entry["traffic"]),
           **cell_file.get("params", {})}
    return Cell(entry, cfg, mix, files.module("configs", cfg["generator"]))


def metric_entries(spec: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports.
    An entry with ``workloads`` lists its cells; a per-layer entry without
    it goes wherever its ``moves`` metric is reported."""
    e2e = {m["name"] for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])}
    out = []
    for m in spec[kind]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


class CompileCounter:
    """Counts traces and backend compiles while it is entered."""

    def __init__(self):
        self.count = 0

    def _on(self, event, _secs, **_):
        if event in COMPILE_EVENTS:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


class Run:
    """What the metric readers read: the run's host clocks, the program's
    counters over the window, the reduced trace, and the work model."""

    def __init__(self, *, cell, cfg, mix, problem, out, setup_s, analyze_s,
                 warm_s, trace, peaks):
        self.cell, self.cfg, self.mix = cell, cfg, mix
        self.problem, self.out, self.trace, self.peaks = (problem, out,
                                                          trace, peaks)
        self.setup_s, self.analyze_s, self.warm_s = setup_s, analyze_s, warm_s
        self.iters = int(cfg["iters"])
        self.stats = out["stats"]
        self.window_s = out["window_s"]

    def iterations(self) -> int:
        """Iterations the device ran in the window: batches × iters."""
        return self.stats["batches"] * self.iters

    def least_seconds(self) -> Optional[float]:
        """Least time of the window's iterations on the cell's chips, the
        real right-hand sides spread evenly over the batches (which, max
        being convex, never overstates the least time).  The work is shared
        by the chips that ran ops, so it is divided by their number, as
        ``busy_s`` is their mean."""
        from bench import work
        if self.peaks is None or not self.stats["batches"]:
            return None
        k = self.stats["served"] / self.stats["batches"]
        p = self.problem
        w = work.apc_iteration(m=p.m, p=p.p, n=p.n, width=p.width, k=k)
        chips = self.trace.chips if self.trace is not None else 1
        return (work.least_time(w, self.peaks).seconds * self.iterations()
                / chips)

    # ----- the reductions the metric readers share -------------------------
    def idle_share(self) -> Optional[float]:
        """% of the traced window in which no op ran on the device."""
        if self.trace is None:
            return None
        return 100.0 * (1.0 - self.trace.busy_s / self.trace.window_s)

    def iter_us(self) -> Optional[float]:
        """Device busy time per iteration run, in microseconds."""
        if self.trace is None or not self.iterations():
            return None
        return 1e6 * self.trace.busy_s / self.iterations()

    def iter_roofline(self) -> Optional[float]:
        """% of the least time per iteration in the busy time per one."""
        least = self.least_seconds()
        if self.trace is None or least is None or not self.trace.busy_s:
            return None
        return 100.0 * least / self.trace.busy_s

    def iters_to_tol(self) -> Optional[float]:
        """Mean iterations to ``tol`` over the window's answers; an answer
        that never reached it counts as ``iters``."""
        its = self.out["iters_to_tol"]
        if not its:
            return None
        return float(np.mean([self.iters if i < 0 else i for i in its]))


def device_info(chips: int, require_tpu: bool):
    import jax
    from bench import work
    devs = jax.devices()
    dev = devs[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    peaks = None
    try:
        peaks = work.load_peaks(dev.device_kind)
    except KeyError as e:
        if require_tpu:
            raise NoChip(str(e)) from None
    if require_tpu:
        if dev.platform != "tpu":
            raise NoChip(f"no TPU: JAX runs on {dev.platform!r}")
        if len(devs) < chips:
            raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                         f"{len(devs)}")
    return info, peaks


def _peak_bytes(chips: int):
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def check(problem, cfg, out) -> Dict[str, dict]:
    """The comparison that decides ``correct``: every answer of the window
    against the plain float64 reference, and every request served (one
    shed, errored or never answered counts against it)."""
    unserved = sum(s != "served" for s in out["status"])
    # no answer at all reads as the answer x = 0, whose residual is 1
    res = (numerics.rel_residuals(problem.reference(), np.stack(out["X"]),
                                  np.stack(out["B"]))
           if out["X"] else np.ones(1))
    return {"max_rel_residual": {
                "value": float(np.max(res)),
                "limit": float(cfg["check"]["max_rel_residual"])},
            "unserved": {"value": int(unserved), "limit": 0}}


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             spec: Optional[dict] = None, files: Optional[Files] = None,
             t_start: Optional[float] = None,
             require_tpu: bool = True) -> dict:
    """One run of ``cell``; returns the result object the CLI prints.
    ``require_tpu=False`` (tests only) skips the look for a chip."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_spec() if spec is None else spec
    files = Files() if files is None else files
    c = resolve(cell, spec, files)
    device, peaks = device_info(int(c.entry["chips"]), require_tpu)
    cfg, mix = c.cfg, c.mix
    problem = c.build(seed)
    traffic = load.make_traffic(files, problem, cfg, mix, seed, seconds)
    data_s = time.perf_counter() - t_start
    try:
        traffic.setup()
        # collect and freeze the set-up's heap (tracing, compiles, autotune,
        # data), so that no full collection of it lands in the window at a
        # point that differs from run to run; the window's own objects are
        # still collected as they come
        gc.collect()
        gc.freeze()
        setup_s = time.perf_counter() - t_start
        log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        with CompileCounter() as compiles:
            if trace:
                import jax
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(log_dir, profiler_options=opts)
            try:
                out = traffic.window()
            finally:
                if trace:
                    jax.profiler.stop_trace()
        device["memory_peak_bytes"] = _peak_bytes(int(c.entry["chips"]))
    finally:
        gc.unfreeze()
        traffic.close()
    summary = None
    if trace:
        try:
            summary = trace_mod.reduce(trace_mod.read_xplane(
                trace_mod.find_xplane(log_dir)))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
    analyze_s, warm_s = traffic.analyze_s, traffic.warm_s
    del traffic
    gc.collect()

    t_check = time.perf_counter()
    compared = check(problem, cfg, out)
    check_s = time.perf_counter() - t_check
    run = Run(cell=cell, cfg=cfg, mix=mix, problem=problem, out=out,
              setup_s=setup_s, analyze_s=analyze_s, warm_s=warm_s,
              trace=summary, peaks=peaks)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metric_entries(spec, cell, kind):
        value = files.module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if trace and summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    failed = sum(s != "served" for s in out["status"])
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in compared.values()),
              "attempted": int(out["attempted"]), "failed": int(failed),
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.device_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
    result["compiles_in_window"] = compiles.count
    result["timing"] = {"to_data_s": data_s, "analyze_s": analyze_s,
                        "warm_s": warm_s, "setup_s": setup_s,
                        "window_s": out["window_s"], "check_s": check_s}
    result["engines"] = _engines()
    result["compared"] = compared
    return result


def _engines() -> Dict[str, str]:
    """The projection engine the program's autotune chose, per shape."""
    from repro.kernels import ops as kops
    return {" ".join(map(str, k)): "fused" if v else "unfused"
            for k, v in kops.engine_cache().items()}


def print_result(result: dict, stream=sys.stdout, err=sys.stderr) -> None:
    """Numbers compared beside their limits as the last lines of standard
    error, then the result as the last line of standard output."""
    err.write(f"timing: {json.dumps(result['timing'])}\n")
    err.write(f"engines: {json.dumps(result['engines'])}\n")
    err.write(f"compiles inside the window: {result['compiles_in_window']}\n")
    for name, c in result["compared"].items():
        err.write(f"compared {name}: {c['value']!r} limit {c['limit']!r}\n")
    err.flush()
    stream.write(json.dumps(result) + "\n")
    stream.flush()
