"""The reduction from a profiler trace to busy seconds, top operations
and labelled idle gaps: on hand-made planes, and on a trace recorded on
the chip in one of this PR's calls (trimmed; see make_fixture below)."""

import gzip
import json
import os
from types import SimpleNamespace as NS

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "data", "worker_scan_slice.json.gz")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes_from(doc):
    return [NS(name=p["name"], lines=[
        NS(name=ln["name"], events=[ev(*e) for e in ln["events"]])
        for ln in p["lines"]]) for p in doc]


HAND = [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_filter_project(123)", 1000, 400],
            ["jit_global_aggregate(9)", 2000, 600]]},
        {"name": "XLA Ops", "events": [
            ["fusion.1", 1000, 300], ["fusion.2", 1200, 200],
            ["reduce.7", 2000, 600], ["fusion.1", 9000, 50]]},
        {"name": "Steps", "events": [["0", 0, 10000]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["bench:q6:0", 500, 2500],
                                      ["something else", 0, 99999]]}]},
]


def test_busy_is_the_union_inside_the_anchored_slice():
    red = tr.reduce_planes(planes_from(HAND), "tpu")
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(2500e-9)
    # [1000,1400) and [2000,2600): overlap counted once, the op at 9000
    # lies outside the slice
    assert red["busy_s"] == pytest.approx(1000e-9)
    assert red["gaps"] == [(1400, 2000), (500, 1000), (2600, 3000)]
    names = dict(map(tuple, red["device_ops"]))
    assert names["jit_global_aggregate/reduce.7"] == pytest.approx(600e-9)
    assert names["jit_filter_project/fusion.1"] == pytest.approx(300e-9)
    assert "fusion.1" not in names


def test_gap_takes_the_innermost_covering_span():
    red = tr.reduce_planes(planes_from(HAND), "tpu")
    t_post = 1_000_000_000_000
    spans = [{"name": "query", "startTimeUnixNano": t_post,
              "durationMs": 2500e-6},
             {"name": "source-stage", "startTimeUnixNano": t_post + 800,
              "durationMs": 700e-6}]
    stmts = [{"anchor": "bench:q6:0", "template": "q6",
              "t_post_ns": t_post, "spans": spans}]
    labelled = tr.label_gaps(red, stmts)
    assert labelled[0] == ["q6:source-stage", pytest.approx(600e-9)]
    assert labelled[1][0] == "q6:query"
    assert tr.label_gaps(red, [])[0][0] == "between-statements"


def test_no_device_plane_reads_zero_busy():
    red = tr.reduce_planes(planes_from(HAND[1:]), "tpu")
    assert red["busy_s"] == 0.0 and red["devices"] == 0


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="the recorded trace is not in this checkout")
def test_recorded_chip_trace():
    with gzip.open(FIXTURE, "rt") as f:
        doc = json.load(f)
    red = tr.reduce_planes(planes_from(doc["planes"]), "tpu")
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(doc["expect"]["busy_s"])
    assert red["window_s"] == pytest.approx(doc["expect"]["window_s"])
    assert 0 < red["busy_s"] < red["window_s"]
    assert [n for n, _ in red["device_ops"]][:3] == doc["expect"]["top3"]
    assert all("/" in n for n, _ in red["device_ops"])


def make_fixture(xplane_path, out_path=FIXTURE, skip_s=1.6, keep_s=1.0):
    """How the recorded trace was trimmed (run by hand, once, on the
    39 MB trace of worker.scan's first traced run, PR 24): the device
    plane's `XLA Ops` and `XLA Modules` lines, the events of `keep_s`
    seconds from `skip_s` seconds into the first anchored statement
    (q6: its first split reaches the device at 1.58 s), each name cut to
    80 characters, and one anchor around them, as JSON."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(xplane_path).planes)
    marks = tr.anchors(planes)
    name, (lo, _) = min(marks.items(), key=lambda kv: kv[1][0])
    lo += int(skip_s * 1e9)
    hi = lo + int(keep_s * 1e9)
    doc = []
    for p in planes:
        if p.name.startswith("/device:TPU:"):
            doc.append({"name": p.name, "lines": [
                {"name": ln.name, "events": [
                    [e.name[:80], int(e.start_ns), int(e.duration_ns)]
                    for e in ln.events
                    if lo <= e.start_ns and e.start_ns + e.duration_ns <= hi]}
                for ln in p.lines if ln.name in ("XLA Ops", "XLA Modules")]})
    doc.append({"name": "/host:CPU", "lines": [
        {"name": "python", "events": [[name, int(lo), int(hi - lo)]]}]})
    red = tr.reduce_planes(planes_from(doc), "tpu")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with gzip.open(out_path, "wt") as f:
        json.dump({"planes": doc, "expect": {
            "busy_s": red["busy_s"], "window_s": red["window_s"],
            "top3": [n for n, _ in red["device_ops"]][:3]}}, f)
    return red
