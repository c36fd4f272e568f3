"""The readers of the program's phase spans (layers/_spans.py and the
nine metrics that use it): on hand-built span lists, and in the traced
CPU rehearsal of `rehearsal.worker.scan`, where the same run also shows
that the program's spans land in the profiler's trace (`tt:<name>`) on
the clock the benchmark's anchors tie to the host's. Numbers read here
are the CPU's and never a device's.
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

from layers import _spans

NEW = ("split_read_ms", "split_put_ms", "split_run_ms", "split_fetch_ms",
       "split_emit_ms", "stage_overhead_ms", "final_stage_ms",
       "literal_keyed_compile_ms", "shape_keyed_compile_ms")
MS = 1_000_000          # ns


def span(name, sid, parent, start_ms, dur_ms, **attrs):
    return {"name": name, "spanId": sid, "parentSpanId": parent,
            "startTimeUnixNano": start_ms * MS, "durationMs": float(dur_ms),
            "attributes": attrs}


def statement():
    """One split-streamed statement: a 100 ms source stage whose worker
    task runs 10..90, two splits, the second compiling twice inside its
    run with the two compiles overlapping; then a 30 ms final stage."""
    return [
        span("query", "q", None, 0, 140),
        span("exec-lock-wait", "x", "q", 0, 1),
        span("source-stage", "st", "q", 5, 100, splits=2),
        span("task-create", "tc", "st", 5, 4),
        span("worker-task", "wt", "st", 10, 80),
        span("split-read", "r0", "wt", 10, 1), span("split-put", "p0", "wt", 11, 4),
        span("split", "s0", "wt", 15, 10),
        span("split-fetch", "f0", "wt", 25, 6), span("split-emit", "e0", "wt", 31, 1),
        span("split-read", "r1", "wt", 32, 3), span("split-put", "p1", "wt", 35, 6),
        span("split", "s1", "wt", 41, 40),
        span("compile", "c0", "s1", 45, 20, key="literal", site="a"),
        span("compile", "c1", "s1", 55, 20, key="shape", site="b"),
        span("split-fetch", "f1", "wt", 81, 8), span("split-emit", "e1", "wt", 89, 1),
        span("final-stage", "fs", "q", 106, 30),
        span("compile", "c2", "fs", 110, 5, key="literal", site="a"),
    ]


def read(metric, *statements):
    reader = importlib.import_module(f"layers.{metric}")
    return reader.read({"statements": [{"spans": s} for s in statements]})


def test_self_time_counts_overlapping_children_once():
    spans = statement()
    s1 = next(s for s in spans if s["spanId"] == "s1")
    # 40 ms less the union of [45, 65] and [55, 75]: 30 ms, not 40
    assert _spans.self_ms(_spans.by_parent(spans), s1) == \
        pytest.approx(10.0)
    assert _spans.union_ms([(0, 10 * MS), (5 * MS, 12 * MS),
                            (20 * MS, 99 * MS)], 0, 30 * MS) == \
        pytest.approx(22.0)


def test_readers_on_a_hand_built_statement():
    s = statement()
    assert read("split_read_ms", s) == pytest.approx((1 + 3) / 2)
    assert read("split_put_ms", s) == pytest.approx((4 + 6) / 2)
    assert read("split_fetch_ms", s) == pytest.approx((6 + 8) / 2)
    assert read("split_emit_ms", s) == pytest.approx(1.0)
    # the compiles are taken out of the run: (10 + 40 - 30) / 2
    assert read("split_run_ms", s) == pytest.approx(10.0)
    # 100 ms of stage, 80 of them under its worker task
    assert read("stage_overhead_ms", s) == pytest.approx(20.0)
    assert read("final_stage_ms", s) == pytest.approx(30.0)
    assert read("literal_keyed_compile_ms", s) == pytest.approx(25.0)
    assert read("shape_keyed_compile_ms", s) == pytest.approx(20.0)


def test_median_is_taken_over_statements():
    a, b, c = statement(), statement(), statement()
    for sp in b:
        if sp["name"] == "split-put":
            sp["durationMs"] *= 3
    for sp in c:
        if sp["name"] == "split-put":
            sp["durationMs"] *= 5
    assert read("split_put_ms", a, b, c) == pytest.approx(15.0)


@pytest.mark.parametrize("metric", NEW)
def test_nothing_to_read_gives_none(metric):
    # a statement that ran no split (the single-node route)
    local = [span("query", "q", None, 0, 50),
             span("exec-lock-wait", "x", "q", 0, 1),
             span("execute", "e", "q", 1, 40)]
    assert read(metric, local) is None
    assert read(metric, []) is None
    assert read(metric) is None


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_phase_spans(metric):
    """The parent of the PR that brought these metrics writes statement,
    stage, task and `split` spans only: the stage metrics read, and the
    phase and compile metrics find nothing and do not raise (a missing
    compile span must not read as 0 ms of compile)."""
    old = [span("query", "q", None, 0, 140),
           span("source-stage", "st", "q", 5, 100, splits=1),
           span("worker-task", "wt", "st", 10, 80),
           span("split", "s0", "wt", 15, 10),
           span("final-stage", "fs", "q", 106, 30)]
    got = read(metric, old)
    if metric == "stage_overhead_ms":
        assert got == pytest.approx(20.0)
    elif metric == "final_stage_ms":
        assert got == pytest.approx(30.0)
    else:
        assert got is None


def test_no_compile_reads_zero_not_none():
    s = [sp for sp in statement() if sp["name"] != "compile"]
    assert read("shape_keyed_compile_ms", s) == 0.0
    assert read("literal_keyed_compile_ms", s) == 0.0


def test_new_metrics_are_listed_beside_their_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = listed[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == \
            ("ms", "lower", "program_span", "query_geomean_s")
        assert m["workloads"] == ["worker.scan", "worker.join"]
        assert os.path.exists(os.path.join(BENCH, "layers", f"{name}.py"))


# one traced window of the rehearsal, driven through run.Cell so that the
# statements' spans and the trace file can be looked at afterwards
DRIVE = """
import glob, json, os, sys
sys.path.insert(0, {bench!r})
import run, trace_reduce
cell = run.Cell({rehearsal!r}, "rehearsal.worker.scan", True)
try:
    cell.setup()
    w = cell.window(2147483777, 1.0)
    out = cell.report(w, 0.0)
finally:
    cell.close()
from jax.profiler import ProfileData
path = glob.glob(os.path.join({bench!r}, ".cache", "trace",
                 "rehearsal.worker.scan", "plugins", "profile", "*",
                 "*.xplane.pb"))[0]
marks = w["trace"]["anchors"]
lo = min(s for s, _ in marks.values())
hi = max(e for _, e in marks.values())
tt = []
for plane in ProfileData.from_file(path).planes:
    if plane.name != "/host:CPU":
        continue
    for line in plane.lines:
        for e in line.events:
            if e.name.startswith("tt:"):
                tt.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
sliced = [s for s in w["statements"] if s["anchor"] in marks]
bridge = []
for s in sliced:
    a0, a1 = marks[s["anchor"]]
    q = next(sp for sp in s["spans"] if sp["name"] == "query")
    mine = [t for t in tt if t[0] == "tt:query" and a0 <= t[1] <= a1]
    assert len(mine) == 1, mine
    mapped = s["t_post_ns"] + (mine[0][1] - a0)
    bridge.append((mapped - q["startTimeUnixNano"]) / 1e6)
print(json.dumps({{
    "metrics": {{k: v["value"] for k, v in out["metrics"].items()}},
    "tt_names": sorted({{t[0] for t in tt}}),
    "tt_split": sum(t[0] == "tt:split" for t in tt),
    "tt_split_inside": sum(t[0] == "tt:split" and lo <= t[1] and t[2] <= hi
                           for t in tt),
    "span_split": sum(sp["name"] == "split" for s in sliced
                      for sp in s["spans"]),
    "bridge_ms": bridge,
    "gap_labels": [g[0] for g in out["breakdown"]["idle_gaps"]]}}))
"""


@pytest.fixture(scope="module")
def traced_rehearsal(rehearsal):
    p = subprocess.run(
        [sys.executable, "-c", DRIVE.format(bench=BENCH,
                                            rehearsal=rehearsal)],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_rehearsal_reads_all_nine(traced_rehearsal):
    m = traced_rehearsal["metrics"]
    for name in NEW:
        assert isinstance(m[name], (int, float)), (name, m)
        assert m[name] >= 0
    phases = sum(m[f"split_{p}_ms"]
                 for p in ("read", "put", "run", "fetch", "emit"))
    assert 0 < phases <= m["split_wall_ms"]
    # new literals in every statement, the shapes warmed up: since PR 30
    # neither compiles
    assert m["literal_keyed_compile_ms"] == 0
    assert m["shape_keyed_compile_ms"] == 0


def test_program_spans_are_in_the_profilers_trace(traced_rehearsal):
    t = traced_rehearsal
    assert {"tt:query", "tt:source-stage", "tt:worker-task", "tt:split",
            "tt:split-put", "tt:final-stage"} <= set(t["tt_names"])
    assert not any(n.startswith("bench:") for n in t["tt_names"])
    # every split of the sliced statements, inside the anchors
    assert t["tt_split_inside"] == t["span_split"] > 0
    # the bridge label_gaps rests on: a span's place on the profiler's
    # clock, taken through the anchor, is its place on the host's
    assert t["bridge_ms"] and all(abs(d) < 5.0 for d in t["bridge_ms"])


def test_gaps_take_the_phase_spans_names(traced_rehearsal):
    labels = {g.split(":", 1)[1] for g in traced_rehearsal["gap_labels"]
              if ":" in g}
    assert labels & {"compile", "split-put", "split-fetch", "task-drain",
                     "merge-decode", "merge-run", "split"}
