"""Each cell end to end, in process, at a tiny size on the CPU
(Pallas in interpret mode), and the benchmark's files against
BENCHMARK.json."""
from __future__ import annotations

import copy
import json

import pytest

from bench_testkit import (CELLS, CONFIGS, SECONDS, SPEC,  # noqa: F401
                           TINY_OPERAND_BYTES, check_control,
                           check_end_to_end, check_traced, f32,
                           operand_bytes, tiny_cfg, tiny_files)
from bench import harness, trace, work
from repro.solvers.pipeline import AsyncLinsysServer


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_and_is_correct(cell, f32, tmp_path):  # noqa: F811
    check_end_to_end(cell, tiny_files(tmp_path))


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics_only(cell, f32,  # noqa: F811
                                                  tmp_path):
    check_traced(cell, tiny_files(tmp_path))


@pytest.mark.parametrize("config", CONFIGS)
def test_tiny_build_is_small(config, f32):  # noqa: F811
    """Whatever a configuration's chip size, its CPU tests run at a size
    that keeps the suite's time bounded."""
    assert operand_bytes(config) <= TINY_OPERAND_BYTES


def test_a_configuration_without_tiny_is_refused_by_name(tmp_path):
    cfg = harness.Files().json("configs", "tall_gauss_16k")
    del cfg["tiny"]
    (tmp_path / "src" / "configs").mkdir(parents=True)
    (tmp_path / "src" / "configs" / "no_tiny.json").write_text(
        json.dumps({**cfg, "name": "no_tiny"}))
    spec = {"configs": [{"name": "no_tiny"}]}
    files = harness.Files([tmp_path / "src", harness.BENCH_DIR])
    with pytest.raises(KeyError, match="no_tiny"):
        tiny_files(tmp_path / "tiny", spec, files)


def test_a_configuration_and_its_cell_are_added_by_files_alone(
        tmp_path, f32):  # noqa: F811
    """A new configuration with its own ``tiny``, and a cell on it, written
    as files and spec entries only, pass the checks every cell passes."""
    base = harness.Files().json("configs", "tall_gauss_16k")
    config, cell = "tall_gauss_copy", "copy.stream"
    src = tmp_path / "src"
    (src / "configs").mkdir(parents=True)
    (src / "cells").mkdir()
    (src / "configs" / f"{config}.json").write_text(json.dumps(
        {**base, "name": config,
         "tiny": {"N": 192, "n": 96, "m": 3, "iters": 30}}))
    (src / "cells" / f"{cell}.json").write_text(json.dumps(
        {"config": config, "traffic": "closed_stream"}))
    spec = copy.deepcopy(SPEC)
    spec["configs"].append({**spec["configs"][0], "name": config,
                            "file": f"bench/configs/{config}.json"})
    spec["workloads"].append({"name": cell, "config": config,
                              "traffic": "closed_stream", "chips": 1,
                              "why": "test"})
    next(m for m in spec["end_to_end"]
         if m["name"] == "lat_p95_ms")["workloads"].append(cell)
    next(m for m in spec["per_layer"]
         if m["name"] == "iters_to_tol.stream")["workloads"].append(cell)
    files = harness.Files([src, harness.BENCH_DIR])
    assert operand_bytes(config, files) <= TINY_OPERAND_BYTES
    tiny = tiny_files(tmp_path / "tiny", spec, files)
    r = check_end_to_end(cell, tiny, spec)
    assert set(r["metrics"]) == {"setup_s", "lat_p95_ms"}
    r = check_traced(cell, tiny, spec)
    assert set(r["metrics"]) == {"iters_to_tol.stream"}
    check_control(cell, tiny, spec)


def test_a_closed_loop_records_one_latency_per_served_step(
        tmp_path, f32):  # noqa: F811
    """The closed loop's record holds a latency for every step it served,
    each within the window, so ``lat_p95_ms`` reads in its cells."""
    from bench import load
    files = tiny_files(tmp_path)
    c = harness.resolve("tall16k.stream", SPEC, files)
    traffic = load.make_traffic(files, c.build(2**31 + 17), c.cfg, c.mix,
                                2**31 + 17, SECONDS)
    traffic.setup()
    try:
        out = traffic.window()
    finally:
        traffic.close()
    served = sum(s == "served" for s in out["status"])
    assert served == out["steps"] > 1
    lat = out["latency_s"]
    assert len(lat) == served
    assert (lat > 0).all() and lat.sum() <= out["window_s"]


CLOSED_CELLS = [c for c in CELLS
                if harness.resolve(c, SPEC).mix["loop"] == "closed"]


@pytest.fixture
def fresh_compile_cache(tmp_path):
    """JAX's persistent compile cache in an empty directory, every program
    kept, for the duration of a test."""
    import jax
    from jax._src import compilation_cache, config
    keep = {"jax_compilation_cache_dir": config.compilation_cache_dir.value,
            "jax_persistent_cache_min_compile_time_secs":
                config.persistent_cache_min_compile_time_secs.value,
            "jax_persistent_cache_min_entry_size_bytes":
                config.persistent_cache_min_entry_size_bytes.value}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("cell", CLOSED_CELLS)
def test_a_closed_loop_compiles_its_window_programs_in_every_run(
        cell, tmp_path, f32, fresh_compile_cache):  # noqa: F811
    """The closed loop's warm-up compiles the executor in this process even
    where the persistent cache holds it from an earlier run of the seed,
    so a seed's second run in a checkout serves as its first does."""
    import jax
    from bench import load
    events = {"compiles": 0, "hits": 0}

    def on_duration(name, _secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            events["compiles"] += 1

    def on_event(name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            events["hits"] += 1

    files = tiny_files(tmp_path)
    c = harness.resolve(cell, SPEC, files)
    seed = 2**31 + 19
    fresh = []
    for _ in range(2):
        traffic = load.make_traffic(files, c.build(seed), c.cfg, c.mix, seed,
                                    SECONDS)
        events.update(compiles=0, hits=0)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        try:
            traffic.setup()
        finally:
            jax.monitoring.unregister_event_duration_listener(on_duration)
            jax.monitoring.unregister_event_listener(on_event)
            traffic.close()
        fresh.append(events["compiles"] - events["hits"])
    assert fresh[1] >= 1        # not every program came from the cache
    assert fresh[1] < fresh[0]  # but what does not close over the seed did


class _Problem:
    m, p, n, width = 8, 2048, 8192, 8192


@pytest.mark.parametrize("chips", [1, 2, 4])
def test_least_time_is_shared_by_the_chips_that_ran_ops(chips):
    """``busy_s`` is the mean over the chips that ran ops, so the least time
    it is compared with is the work's over as many chips."""
    out = {"window_s": 1.0, "iters_to_tol": [],
           "stats": {"served": 32, "padded": 0, "batches": 2, "shed": 0}}
    summary = trace.Summary(busy_s=0.5, window_s=1.0, chips=chips,
                            device_ops=[], idle_gaps=[])
    peaks = work.load_peaks("TPU v5 lite")
    run = harness.Run(cell="c", cfg={"iters": 10}, mix={},
                      problem=_Problem(), out=out, setup_s=1.0,
                      analyze_s=0.5, warm_s=0.5, trace=summary, peaks=peaks)
    one = work.least_time(work.apc_iteration(
        m=8, p=2048, n=8192, width=8192, k=16), peaks).seconds * 2 * 10
    assert run.least_seconds() == pytest.approx(one / chips)
    assert run.iter_roofline() == pytest.approx(100.0 * one / chips / 0.5)


def test_a_metric_is_added_by_adding_a_file(tmp_path, f32):  # noqa: F811
    cell = "tall16k.open"
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "dummy_requests.py").write_text(
        "def read(run):\n    return run.out['attempted'] * 2\n")
    spec = copy.deepcopy(SPEC)
    spec["per_layer"].append({
        "name": "dummy_requests", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "test", "moves": "lat_p95_ms",
        "workloads": [cell]})
    r = harness.run_cell(cell, 5, SECONDS, True, spec=spec,
                         files=tiny_files(tmp_path), require_tpu=False)
    assert r["metrics"]["dummy_requests"]["value"] == 2 * r["attempted"]
    assert r["metrics"]["dummy_requests"]["unit"] == "requests"


def test_a_closed_loop_cell_is_added_by_adding_files(tmp_path, f32):  # noqa: F811
    """A cell of the closed-stream mix, its end-to-end metric and its reader
    come from files alone; the run steps, checks and reports them."""
    cell = "tall16k.steps"
    (tmp_path / "cells").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "cells" / f"{cell}.json").write_text(json.dumps(
        {"config": "tall_gauss_16k", "traffic": "closed_stream"}))
    (tmp_path / "metrics" / "steps_per_s.py").write_text(
        "def read(run):\n"
        "    return run.out['steps'] / run.window_s\n")
    spec = copy.deepcopy(SPEC)
    spec["workloads"].append({"name": cell, "config": "tall_gauss_16k",
                              "traffic": "closed_stream", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "steps_per_s", "unit": "1/s",
                               "better": "higher", "bound": 0.1,
                               "source": "host_clock", "workloads": [cell]})
    r = harness.run_cell(cell, 2**31 + 7, SECONDS, False, spec=spec,
                         files=tiny_files(tmp_path), require_tpu=False)
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] > 1                   # each step fed the next
    assert set(r["metrics"]) == {"setup_s", "steps_per_s"}
    assert r["metrics"]["steps_per_s"]["value"] > 0
    assert r["compiles_in_window"] == 0


def test_a_shed_request_is_not_correct(tmp_path, f32,  # noqa: F811
                                       monkeypatch):
    """A request the server sheds is no answer: the run is not correct,
    however right the answers it did give."""
    orig = AsyncLinsysServer.submit

    def submit(self, fp, rhs):
        self.admit_capacity = 1
        return orig(self, fp, rhs)

    monkeypatch.setattr(AsyncLinsysServer, "submit", submit)
    r = harness.run_cell("tall16k.open", 2**31 + 11, SECONDS, False,
                         files=tiny_files(tmp_path), require_tpu=False)
    assert r["compared"]["unserved"]["value"] > 0
    assert r["compared"]["max_rel_residual"]["value"] <= \
        r["compared"]["max_rel_residual"]["limit"]
    assert r["failed"] == r["compared"]["unserved"]["value"]
    assert r["correct"] is False


def test_a_loop_is_added_by_adding_a_file(tmp_path, f32):  # noqa: F811
    """A mix may name a loop of its own: ``bench/traffic/<loop>.py`` is
    found by name, with no edit to the harness."""
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "every_other.py").write_text(
        "from bench import harness\n"
        "_open = harness.Files().module('traffic', 'open')\n"
        "class Loop(_open.Loop):\n"
        "    def schedule(self, rate, seed, stream):\n"
        "        super().schedule(rate, seed, stream)\n"
        "        self.due, self.B = self.due[::2], self.B[::2]\n")
    (tmp_path / "traffic" / "halved.json").write_text(json.dumps(
        {"loop": "every_other", "batch": 4, "rate_per_s": 20.0}))
    cell = "tall16k.halved"
    (tmp_path / "cells").mkdir()
    (tmp_path / "cells" / f"{cell}.json").write_text(json.dumps(
        {"config": "tall_gauss_16k", "traffic": "halved"}))
    spec = copy.deepcopy(SPEC)
    spec["workloads"].append({"name": cell, "config": "tall_gauss_16k",
                              "traffic": "halved", "chips": 1,
                              "why": "test"})
    next(m for m in spec["end_to_end"]
         if m["name"] == "lat_p95_ms")["workloads"].append(cell)
    r = harness.run_cell(cell, 2**31 + 13, SECONDS, False, spec=spec,
                         files=tiny_files(tmp_path), require_tpu=False)
    assert r["correct"] is True
    assert r["attempted"] == 10
    assert set(r["metrics"]) == {"setup_s", "lat_p95_ms"}


def test_metric_entries_follow_workloads_and_moves():
    spec = {"end_to_end": [{"name": "a"}, {"name": "b", "workloads": ["y"]}],
            "per_layer": [{"name": "p", "moves": "b"},
                          {"name": "q", "moves": "a", "workloads": ["x"]}]}
    names = lambda c, k: [m["name"] for m in harness.metric_entries(spec, c, k)]  # noqa: E731
    assert names("x", "end_to_end") == ["a"]
    assert names("y", "end_to_end") == ["a", "b"]
    assert names("x", "per_layer") == ["q"]
    assert names("y", "per_layer") == ["p"]


def test_every_name_in_the_spec_has_its_file():
    files = harness.Files()
    configs = {c["name"]: c for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        cell = files.json("cells", w["name"])
        assert (cell["config"], cell["traffic"]) == (w["config"],
                                                     w["traffic"])
        files.json("traffic", w["traffic"])
        cfg = files.json("configs", w["config"])
        assert cfg["name"] == w["config"]
        assert sorted(cfg["reduced"]) == sorted(
            configs[w["config"]]["reduced"])
        assert harness.ROOT / configs[w["config"]]["file"] == \
            files.path("configs", w["config"], ".json")
        files.module("configs", cfg["generator"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        mod = files.module("metrics", m["name"])
        assert callable(mod.read)
        if m in SPEC["per_layer"]:
            assert (mod.LAYER, mod.MOVES) == (m["layer"], m["moves"])
    for c in SPEC["configs"]:
        assert tiny_cfg(c["name"])["tiny"]
    json.dumps(SPEC)
