"""Shared by the benchmark's own tests: they run on the CPU, against the
rehearsal configurations, and are not part of the repository's tier-1
suite (`pytest tests/`). Run them with `python -m pytest benchmark/tests`.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def rehearsal_file(directory) -> str:
    """BENCHMARK.json with its configurations and cells swapped for the
    rehearsal's (`rehearsal.<cell>` on `rehearsal_tiny_<deployment>`), so
    the metrics and their readers are the real ones."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    bench["configs"] = [
        {"name": f"rehearsal_tiny_{d}",
         "file": f"benchmark/configs/rehearsal_tiny_{d}.json"}
        for d in ("worker", "single")]
    bench["workloads"] = [
        {"name": f"rehearsal.{d}.{t}", "config": f"rehearsal_tiny_{d}",
         "traffic": t, "chips": 1}
        for d in ("worker", "single") for t in ("scan", "join")]
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [f"rehearsal.{w}" for w in m["workloads"]
                              if w in cells]
    path = os.path.join(str(directory), "rehearsal.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return path


@pytest.fixture(scope="session")
def rehearsal(tmp_path_factory):
    return rehearsal_file(tmp_path_factory.mktemp("rehearsal"))
