"""`workers4.join` rehearsed on the CPU: a coordinator and four workers
on four virtual CPU devices, q3 at sf0.2 (lineitem in five 250,000-row
splits, so every worker gets a task), through `run.py` unedited. The
traced run reads the five metrics the cell brought and names four
devices; the float32 control is rejected. The five readers on spans a
four-worker statement writes, on the parent's spans (no `device`, no
`hedges`) and on the single-node route; the configuration's file; what
`BENCHMARK.json` gained. Numbers here are the CPU's, never a device's."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

CELL = "rehearsal.workers4.join"
CONFIG = "tpch_sf10_workers4"
NEW_METRICS = ("task_devices", "task_parallelism", "task_skew_ms",
               "task_create_ms", "task_hedges")


def benchmark_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def rehearsal_workers4(tmp_path_factory):
    """BENCHMARK.json with its configurations and cells swapped for the
    one rehearsal cell, so the metrics and their readers are the real
    ones."""
    bench = benchmark_json()
    assert any(w["name"] == "workers4.join" and w["traffic"] == "join"
               and w["chips"] == 4 for w in bench["workloads"])
    bench["configs"] = [{
        "name": "rehearsal_tiny_workers4",
        "file": "benchmark/configs/rehearsal_tiny_workers4.json"}]
    bench["workloads"] = [{"name": CELL, "config": "rehearsal_tiny_workers4",
                           "traffic": "join", "chips": 4}]
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [f"rehearsal.{w}" for w in m["workloads"]]
    path = str(tmp_path_factory.mktemp("workers4") / "rehearsal.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return path


def run_tool(tool, args):
    """Four virtual CPU devices, as the cell's `chips` asks for."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run([sys.executable, os.path.join(BENCH, tool)] + args,
                          cwd=ROOT, capture_output=True, text=True,
                          timeout=1500, env=env)


@pytest.mark.parametrize("trace", [0, 1])
def test_four_workers_on_four_devices_answer_the_window(rehearsal_workers4,
                                                        trace):
    p = run_tool("run.py", ["--workload", CELL, "--seed", "4300000043",
                            "--seconds", "8", "--trace", str(trace),
                            "--benchmark-file", rehearsal_workers4])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0, p.stdout[-3000:]
    assert out["attempted"] >= 1 and out["device"]["count"] == 4
    statements = [ln for ln in p.stdout.splitlines() if "] statement " in ln]
    assert all("distributed=True" in ln and "fallback=None" in ln
               for ln in statements)
    assert all(v == [0, 0] for v in out["checks"].values())
    bench = benchmark_json()
    if trace == 0:
        assert set(out["metrics"]) == {m["name"] for m in
                                       bench["end_to_end"]}
        return
    listed = {m["name"] for m in bench["per_layer"]
              if "workloads" not in m or "workers4.join" in m["workloads"]}
    assert set(NEW_METRICS) <= listed
    # the CPU backend reports no peak memory; everything else reads
    assert set(out["metrics"]) == listed - {"peak_hbm_gb"}
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["task_devices"] == 4 and m["task_hedges"] == 0
    assert 1.0 <= m["task_parallelism"] <= 4.0
    assert m["task_skew_ms"] >= 0 and m["task_create_ms"] > 0
    assert m["compiles_in_window"] == 0


def test_control_is_rejected(rehearsal_workers4):
    p = run_tool("prove.py", ["--workload", CELL, "--seeds", "4300000044",
                              "--seconds", "5", "--control", "1",
                              "--benchmark-file", rehearsal_workers4])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    (line,) = [json.loads(ln) for ln in p.stdout.splitlines()
               if ln.startswith("{")]
    assert line["correct"] and line["checks"]["off_device_statements"] == \
        [0, 0]
    assert line["control_mismatched_cells"] > 0


# ---------------------------------------------------------------------------
# the five readers
# ---------------------------------------------------------------------------

MS = 1_000_000


def span(name, span_id, parent, start_ms, ms, **attributes):
    return {"name": name, "spanId": span_id, "parentSpanId": parent,
            "startTimeUnixNano": start_ms * MS, "durationMs": ms,
            "attributes": attributes}


def statement(devices=True, hedges=0, skew=0.0):
    """A q3 on four workers: a two-task build stage (1.0 s and 0.6 s, at
    once), then a four-task probe stage whose tasks start together and
    run 2.0 s but the last, `skew` ms longer."""
    def dev(i):
        return {"device": f"tpu:{i}"} if devices else {}
    counted = {"hedges": hedges} if devices else {}
    spans = [span("build-stage", "b", "q", 0, 1100.0, fragment=1,
                  **({"hedges": 0} if devices else {})),
             span("source-stage", "s1", "b", 50, 1000.0, splits=6,
                  **({"hedges": 0} if devices else {})),
             span("task-create", "c1", "s1", 50, 30.0),
             span("task-create", "c2", "s1", 60, 30.0),
             span("worker-task", "t1", "s1", 100, 1000.0, node="w0",
                  **dev(0)),
             span("worker-task", "t2", "s1", 100, 600.0, node="w1",
                  **dev(1)),
             span("source-stage", "s2", "q", 2000, 3000.0 + skew,
                  splits=241, **counted),
             span("task-create", "c3", "s2", 2000, 400.0),
             span("task-create", "c4", "s2", 2100, 500.0)]
    for i in range(4):
        spans.append(span("worker-task", f"p{i}", "s2", 2600,
                          2000.0 + (skew if i == 3 else 0.0),
                          node=f"w{i}", **dev(i)))
    return {"spans": spans}


@pytest.fixture(scope="module")
def readers():
    return {n: importlib.import_module(f"layers.{n}") for n in NEW_METRICS}


def test_readers_on_a_four_worker_statement(readers):
    run = {"statements": [statement(), statement(skew=500.0, hedges=1),
                          statement(skew=100.0)]}
    assert readers["task_devices"].read(run) == 4
    assert readers["task_hedges"].read(run) == 0
    # the build stage's 400 ms, plus the probe stage's slowest task
    assert readers["task_skew_ms"].read(run) == 500.0
    # creates of a stage overlap: 50-90 ms and 2000-2600 ms
    assert readers["task_create_ms"].read(run) == 640.0
    # 1600 + 8000 task-ms over 1000 + 2000 ms with a task running
    assert readers["task_parallelism"].read(
        {"statements": [statement()]}) == pytest.approx(9600.0 / 3000.0)
    assert readers["task_hedges"].read(
        {"statements": [statement(hedges=2)]}) == 2


def test_readers_on_the_parents_spans_and_on_one_worker(readers):
    """No `device`, no `hedges`: those two have nothing to read and do
    not raise; the three that read intervals read."""
    old = {"statements": [statement(devices=False)]}
    assert readers["task_devices"].read(old) is None
    assert readers["task_hedges"].read(old) is None
    assert readers["task_skew_ms"].read(old) == 400.0
    assert readers["task_create_ms"].read(old) == 640.0
    assert readers["task_parallelism"].read(old) == pytest.approx(3.2)
    # one worker: a task a stage, one after another
    one = {"statements": [{"spans": [
        span("source-stage", "s1", "q", 0, 900.0, hedges=0),
        span("task-create", "c1", "s1", 0, 100.0),
        span("worker-task", "t1", "s1", 100, 700.0, device="tpu:0"),
        span("source-stage", "s2", "q", 1000, 5000.0, hedges=0),
        span("task-create", "c2", "s2", 1000, 1600.0),
        span("worker-task", "t2", "s2", 2600, 3300.0, device="tpu:0")]}]}
    assert readers["task_devices"].read(one) == 1
    assert readers["task_parallelism"].read(one) == 1.0
    assert readers["task_skew_ms"].read(one) == 0.0
    assert readers["task_create_ms"].read(one) == 1700.0
    assert readers["task_hedges"].read(one) == 0
    # the single-node route has no stage and no task
    single = {"statements": [{"spans": [
        span("execute", "e", "q", 0, 1180.0, scanPutBytes=0)]}]}
    for r in readers.values():
        assert r.read(single) is None
        assert r.read({"statements": []}) is None
        assert r.read({"statements": [{"spans": []}]}) is None


def test_the_configurations_file_says_what_a_deployment_is():
    with open(os.path.join(BENCH, "configs", f"{CONFIG}.json")) as f:
        conf = json.load(f)
    assert conf["name"] == CONFIG and conf["architecture"] is None
    for key in ("source", "deployment", "data", "guarantees", "reduced",
                "assumed"):
        assert conf[key], key
    assert sorted(conf["reduced"]) == ["scale_factor", "templates",
                                       "workers"]
    with open(os.path.join(BENCH, "configs", "tpch_sf10_worker.json")) as f:
        sibling = json.load(f)
    for key in ("platform", "catalog", "schema", "session_properties"):
        assert conf["deployment"][key] == sibling["deployment"][key], key
    assert conf["deployment"]["chips"] == conf["deployment"]["workers"] == 4
    assert conf["data"]["rows"] == sibling["data"]["rows"]
    assert conf["data"]["population"] == sibling["data"]["population"]
    assert conf["guarantees"]["results"] == sibling["guarantees"]["results"]
    assert conf["guarantees"]["caches"].startswith(
        sibling["guarantees"]["caches"].split(";")[0])
    assert set(conf["guarantees"]) >= {"execution", "spill", "membership"}
    assert conf["reduced"]["scale_factor"] == \
        sibling["reduced"]["scale_factor"]
    assert conf["assumed"]["seed"] == sibling["assumed"]["seed"]
    assert "4.1.3" in conf["source"] and "tpch.yaml" in conf["source"]
    layout = conf["deployment"]["layout"]
    assert "worker i computes on chip i" in layout
    assert "61/60/60/60" in layout


def test_benchmark_json_gained_entries_and_lost_nothing():
    """The older entries are what the parent commit has, byte for byte,
    and the new ones follow them."""
    p = subprocess.run(["git", "show", "HEAD:BENCHMARK.json"], cwd=ROOT,
                       capture_output=True, text=True)
    if p.returncode:
        pytest.skip("not a git checkout")
    old = json.loads(p.stdout)
    if any(w["name"] == "workers4.join" for w in old["workloads"]):
        pytest.skip("HEAD has the cell: nothing to compare it with")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        text = f.read()
    new = json.loads(text)
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert new[key] == old[key], key
    for key, added in (("configs", 1), ("workloads", 1), ("per_layer", 5)):
        assert new[key][:len(old[key])] == old[key], key
        assert len(new[key]) == len(old[key]) + added, key
    lines = iter(ln.rstrip(",") for ln in text.splitlines())
    assert all(any(ln == want.rstrip(",") for ln in lines)
               for want in p.stdout.splitlines())
    (config,) = new["configs"][-1:]
    (cell,) = new["workloads"][-1:]
    assert config["name"] == CONFIG and config["file"] == \
        f"benchmark/configs/{CONFIG}.json"
    assert config["reduced"] == ["scale_factor", "templates", "workers"]
    assert cell == dict(cell, name="workers4.join", config=CONFIG,
                        traffic="join", chips=4)
    assert sum(w["chips"] == 4 for w in new["workloads"]) == 1
    added = new["per_layer"][-5:]
    assert [m["name"] for m in added] == list(NEW_METRICS)
    for m in added:
        assert m["moves"] == "query_geomean_s"
        assert m["layer"] == "scheduling and exchange"
        assert m["source"] == "program_span"
        assert os.path.exists(os.path.join(BENCH, "layers",
                                           f"{m['name']}.py"))
        assert m["workloads"] == (["workers4.join", "worker.join"]
                                  if m["name"] == "task_create_ms"
                                  else ["workers4.join"])
    assert [m["better"] for m in added] == ["higher", "higher", "lower",
                                            "lower", "lower"]
    for entry in (config, cell):
        assert 0 < len(entry["why"]) <= 200
    assert len(config["source"]) <= 200
