"""Each cell end to end, in process, at a tiny size on the CPU
(Pallas in interpret mode), and the benchmark's files against
BENCHMARK.json."""
from __future__ import annotations

import copy
import json

import pytest

from bench_testkit import CELLS, SECONDS, SPEC, f32, tiny_files  # noqa: F401
from bench import harness
from repro.solvers.pipeline import AsyncLinsysServer

E2E = {"tall16k.open": "lat_p95_ms"}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_and_is_correct(cell, f32, tmp_path):  # noqa: F811
    r = harness.run_cell(cell, 2**31 + 99, SECONDS, False,
                         files=tiny_files(tmp_path), require_tpu=False)
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"setup_s", E2E[cell]}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["compiles_in_window"] == 0
    assert list(r)[-1] == "compared"
    res = r["compared"]["max_rel_residual"]
    assert res["value"] <= res["limit"]
    assert r["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics_only(cell, f32,  # noqa: F811
                                                  tmp_path):
    r = harness.run_cell(cell, 3, SECONDS, True, files=tiny_files(tmp_path),
                         require_tpu=False)
    assert r["correct"] is True
    names = set(r["metrics"])
    assert {"analyze_s", "warm_s", f"iters_to_tol.{cell.split('.')[1]}"} \
        <= names
    assert "setup_s" not in names and E2E[cell] not in names
    # the CPU trace holds no TPU plane: the device metrics read nothing
    assert not any(n.startswith(("idle_share", "iter_us", "iter_roofline"))
                   for n in names)
    assert "busy_s" not in r["device"]


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
    json.dumps(SPEC)
