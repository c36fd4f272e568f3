"""Phase spans: the tracer carried per query (utils/tracing.py `use` /
`current`), the spans of the split loop, the stage drain and the final
merge, `compile` spans that tell literal-keyed from shape-keyed
(exec/profiler.py), and tracing that no longer implies fences.
"""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.client.client import Client
from trino_tpu.exec.profiler import RECORDER, CompileRecorder, instrument
from trino_tpu.exec.session import Session
from trino_tpu.planner import logical as L
from trino_tpu.server.coordinator import CoordinatorServer
from trino_tpu.server.worker import WorkerServer
from trino_tpu.utils import tracing
from trino_tpu.utils.tracing import NOOP, Tracer

# every span of docs/operations.md's catalogue that a split-streamed
# aggregation opens (build-stage, pin-builds' children and the write and
# exchange spans belong to other plan shapes)
Q6_SPANS = {"query", "exec-lock-wait", "exec-lock-held", "plan-distributed",
            "stage-prepare", "source-stage", "spool-lookup", "stage-wait",
            "task-create", "task-drain", "task-record", "task-decode",
            "task-lock-wait", "worker-task", "pin-builds", "split-read",
            "split-put", "split", "split-fetch", "split-emit", "compile",
            "task-merge", "task-emit", "final-stage", "merge-decode", "merge-partials", "merge-run",
            "result-fetch", "decode-rows"}
SPLIT_PHASES = ("split-read", "split-put", "split", "split-fetch",
                "split-emit")


def q6(quantity: str) -> str:
    return ("SELECT sum(l_extendedprice * l_discount) AS revenue "
            "FROM lineitem WHERE l_shipdate >= DATE '1994-01-01' "
            "AND l_shipdate < DATE '1995-01-01' "
            "AND l_discount BETWEEN 0.05 AND 0.07 "
            f"AND l_quantity < {quantity}")


# ---------------------------------------------------------------------------
# the carrier
# ---------------------------------------------------------------------------

def test_current_is_noop_outside_use():
    assert tracing.current() is NOOP
    assert tracing.carried() is None
    t = Tracer()
    with tracing.use(t):
        assert tracing.current() is t
        with tracing.use(NOOP):
            # an untraced query inside: carried, but off
            assert tracing.carried() is NOOP
        assert tracing.current() is t
    assert tracing.current() is NOOP


def test_use_carries_onto_a_spawned_thread_under_the_named_parent():
    t = Tracer()
    seen = {}

    def helper(parent_id):
        seen["before"] = tracing.current()
        with tracing.use(t, parent=parent_id):
            seen["inside"] = tracing.current()
            seen["traceparent"] = t.traceparent()
            with t.span("helper-work"):
                with t.span("helper-inner"):
                    pass
        seen["after"] = tracing.current()

    with tracing.use(t), t.span("stage") as stage:
        th = threading.Thread(target=helper, args=(stage.span_id,))
        th.start()
        th.join()
    assert seen["before"] is NOOP and seen["after"] is NOOP
    assert seen["inside"] is t
    assert seen["traceparent"].split("-")[2] == stage.span_id
    by = {s["name"]: s for s in t.export()}
    assert by["helper-work"]["parentSpanId"] == stage.span_id
    assert by["helper-inner"]["parentSpanId"] == by["helper-work"]["spanId"]
    assert by["stage"]["parentSpanId"] is None


def test_span_parent_argument_and_record():
    t = Tracer()
    with t.span("a") as a:
        with t.span("b") as b:
            t0 = time.monotonic()
            time.sleep(0.01)
            # known only afterwards; lands under the innermost open span
            t.record("late", t0, time.monotonic(), site="x")
        with t.span("c", parent=b.span_id):
            pass
        t.record("late-explicit", t0, t0 + 0.001, parent=a.span_id)
    by = {s["name"]: s for s in t.export()}
    assert by["late"]["parentSpanId"] == b.span_id
    assert by["late"]["attributes"] == {"site": "x"}
    assert 9.0 <= by["late"]["durationMs"] < 200.0
    # on the exported clock it lies inside its parent, as it did on the
    # monotonic one
    assert _inside(by["late"], by["b"])
    assert by["c"]["parentSpanId"] == b.span_id
    assert by["late-explicit"]["parentSpanId"] == a.span_id


def test_laps_leave_no_moment_unnamed():
    t = Tracer()
    with t.span("task") as task:
        with t.laps() as lap:
            for i in range(3):
                lap("read", index=i)
                sp = lap("run", index=i)
                # the open phase is the thread's innermost span
                assert t.current_span() is sp
                t.record("compile", time.monotonic() - 0.001,
                         time.monotonic())
                lap("emit", index=i)
        assert t.current_span() is task
    spans = t.export()
    phases = sorted((s for s in spans if s["name"] in
                     ("read", "run", "emit")),
                    key=lambda s: s["startTimeUnixNano"])
    assert [s["name"] for s in phases] == ["read", "run", "emit"] * 3
    assert all(s["parentSpanId"] == task.span_id for s in phases)
    runs = {s["spanId"] for s in phases if s["name"] == "run"}
    assert all(s["parentSpanId"] in runs for s in spans
               if s["name"] == "compile")
    # each phase starts exactly where the one before ended
    for a, b in zip(phases, phases[1:]):
        end = a["startTimeUnixNano"] + a["durationMs"] * 1e6
        assert abs(end - b["startTimeUnixNano"]) <= ROUNDING_NS
    # off: the same code runs and builds nothing
    with NOOP.laps() as lap:
        assert lap("read", index=0) is None
    assert NOOP.export() == []


def test_laps_close_the_open_phase_on_an_error():
    t = Tracer()
    with pytest.raises(ValueError):
        with t.laps() as lap:
            lap("read")
            raise ValueError("boom")
    assert t.current_span() is None
    assert [s["name"] for s in t.export()] == ["read"]


def test_spans_of_one_process_share_one_clock_pair(monkeypatch):
    """Two tracers, `record()` and `laps()` while another thread takes
    the GIL wherever it can: on the exported clock a child lies inside
    its parent and laps touch, exactly (a wall-clock read per span put a
    thread switch, 5 ms here, between a span and its parent). And the
    wall clock is read once a process: while these spans are made the
    tracer cannot read it at all."""
    import types
    monkeypatch.setattr(tracing, "time",
                        types.SimpleNamespace(monotonic=time.monotonic))
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            sum(range(2000))

    spinner = threading.Thread(target=spin)
    spinner.start()
    a, b = Tracer(service="coordinator"), Tracer(service="worker")
    try:
        for i in range(300):
            with a.span("outer", n=i) as outer:
                with b.span("inner", parent=outer.span_id) as inner:
                    t0 = time.monotonic()
                    sum(range(200))
                    b.record("late", t0, time.monotonic(),
                             parent=inner.span_id)
                    with b.laps() as lap:
                        lap("read")
                        lap("run")
                a.record("held", outer.start, time.monotonic(),
                         parent=outer.span_id)
    finally:
        stop.set()
        spinner.join()
    spans = a.export() + b.export()
    ids = {s["spanId"]: s for s in spans}
    assert len(spans) == 300 * 6
    for s in spans:
        if s["parentSpanId"] is not None:
            assert _inside(s, ids[s["parentSpanId"]]), \
                (s, ids[s["parentSpanId"]])
    # a start is its monotonic reading through the process's one pair
    for t in (a, b):
        for sp, d in zip(t.spans, t.export()):
            assert d["startTimeUnixNano"] == tracing.unix_ns(sp.start)
    laps = sorted((s for s in spans if s["name"] in ("read", "run")),
                  key=lambda s: s["startTimeUnixNano"])
    for read, run in zip(laps[::2], laps[1::2]):
        assert (read["name"], run["name"]) == ("read", "run")
        assert abs(_interval(read)[1] - run["startTimeUnixNano"]) <= \
            ROUNDING_NS
    # `held` began when `outer` did: the same reading, the same stamp
    for s in spans:
        if s["name"] == "held":
            assert s["startTimeUnixNano"] == \
                ids[s["parentSpanId"]]["startTimeUnixNano"]


def test_split_spans_are_compact_until_a_trace_is_read():
    """A split loop's operator spans: nobody's context, five integers
    each where a task ships them, ordinary span dicts where the trace
    is read; rebased as one where they are adopted with an offset."""
    t = Tracer(service="worker")
    with t.span("task") as task:
        with t.laps() as lap:
            for i in range(2):
                split = lap("split", index=i)
                with t.split_span("join", task.span_id, i) as join:
                    assert t.current_span() is split
                    t.record("compile", time.monotonic() - 0.001,
                             time.monotonic())
                    with t.split_span("dynamic-filter", task.span_id, i,
                                      depth=1):
                        with t.span("inner"):
                            pass
                with t.split_span("aggregate", task.span_id, i):
                    pass
                assert t.current_span() is split and join[4] is not None
    shipped = t.export(compact=True)
    block, = [d for d in shipped if "splitSpans" in d]
    assert len(shipped) == 1 + 2 * 3 + 1
    assert block["parentSpanId"] == task.span_id
    assert len(block["splitSpans"]) == 6 * 5
    assert all(type(v) is int for v in block["splitSpans"])
    spans = t.export()
    assert len(spans) == 1 + 2 * 3 + 6 and \
        len({s["spanId"] for s in spans}) == len(spans)
    ids = {s["spanId"]: s for s in spans}
    laps = {s["attributes"]["index"]: s for s in spans
            if s["name"] == "split"}
    for s in spans:
        assert set(s) == set(spans[0]) and s["traceId"] == t.trace_id
        if s["name"] in ("join", "aggregate", "dynamic-filter"):
            assert list(s["attributes"]) == ["split"]
            assert s["service"] == "worker"
            assert _inside(s, laps[s["attributes"]["split"]])
            up = ids[s["parentSpanId"]]
            assert up["name"] == ("join" if s["name"] == "dynamic-filter"
                                  else "task") and _inside(s, up)
        elif s["name"] in ("compile", "inner"):
            assert ids[s["parentSpanId"]]["name"] == "split"
    # adopted (the coordinator's side, off the wire): still compact,
    # and every span 5 s earlier, to the nanosecond
    coord = Tracer()
    coord.adopt(json.loads(json.dumps(shipped)), offset_s=5.0)
    assert sum("splitSpans" in d for d in coord.export(compact=True)) == 1

    def facts(exported, shift):
        return sorted((s["name"], s["startTimeUnixNano"] + shift,
                       s["durationMs"], s["attributes"].get("split"))
                      for s in exported)

    assert facts(coord.export(), 5 * 10**9) == facts(spans, 0)
    # a block that skips a level (another program's) is still read
    odd = dict(block, splitSpans=[0, 7, 2, 0, 1], names=["join"])
    late, = tracing._expand(odd)
    assert late["parentSpanId"] == task.span_id
    # off: nothing is built
    with NOOP.split_span("join", "p", 0) as none:
        assert none is None
    assert NOOP.export(compact=True) == []


class CountingAnnotation:
    names = []

    def __init__(self, name, **_kw):
        CountingAnnotation.names.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_tracing_off_builds_nothing(monkeypatch):
    import jax.profiler
    CountingAnnotation.names = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountingAnnotation)
    with tracing.use(NOOP):
        with tracing.current().span("split", index=0) as sp:
            assert sp is None
        tracing.current().record("compile", 0.0, 1.0, key="shape")
    assert CountingAnnotation.names == []
    assert NOOP.export() == []
    # and on: one annotation a live span, under the program's prefix,
    # never the benchmark's
    t = Tracer()
    with t.span("split"):
        with t.span("compile-ish"):
            pass
    t.record("compile", 0.0, 1.0)       # after the fact: no annotation
    assert CountingAnnotation.names == ["tt:split", "tt:compile-ish"]
    assert not any(n.startswith("bench:") for n in CountingAnnotation.names)


def test_bare_session_keeps_its_own_tracer_and_current_comes_first():
    s = Session(default_schema="tiny")
    assert s.tracer is NOOP
    s.execute("SET SESSION enable_tracing = true")
    own = s.tracer
    assert own.enabled
    per_query = Tracer()
    with tracing.use(per_query):
        assert s.tracer is per_query
        s.execute("SELECT count(*) FROM nation")
    assert s.tracer is own
    assert {"plan", "execute"} <= {x["name"] for x in per_query.export()}
    assert own.export() == []
    s.execute("SET SESSION enable_tracing = false")
    assert s.tracer is NOOP


# ---------------------------------------------------------------------------
# literal-keyed or shape-keyed
# ---------------------------------------------------------------------------

def test_compile_kind_literal_or_shape():
    from functools import partial
    rec = CompileRecorder()

    @partial(jax.jit, static_argnums=(1,))
    def scaled(x, k):
        return x * k

    f = instrument(scaled, "test.scaled", recorder=rec)
    t = Tracer()
    with tracing.use(t):
        f(jnp.arange(8), 3)
        shapes_1 = rec.site_shape_counts()["test.scaled"]
        f(jnp.arange(8), 3)            # a hit: no event kind, no span
        f(jnp.arange(8), 4)            # new static value, shapes seen
        shapes_2 = rec.site_shape_counts()["test.scaled"]
        f(jnp.arange(16), 4)           # new array shape
        shapes_3 = rec.site_shape_counts()["test.scaled"]
    kinds = [e.key for e in rec.events if not e.hit]
    assert kinds == ["shape", "literal", "shape"]
    assert [e.key for e in rec.events if e.hit] == [""]
    # a counter named for shapes counts shapes, not literals
    assert (shapes_1, shapes_2, shapes_3) == (1, 1, 2)
    tot = rec.totals()
    assert tot["shapeKeyedCompiles"] == 2
    assert tot["literalKeyedCompiles"] == 1
    assert tot["compiles"] == 3
    assert tot["shapeKeyedCompileSeconds"] + \
        tot["literalKeyedCompileSeconds"] == \
        pytest.approx(tot["compileSeconds"], abs=1e-5)
    spans = [s for s in t.export() if s["name"] == "compile"]
    assert [s["attributes"]["key"] for s in spans] == kinds
    assert all(s["attributes"]["site"] == "test.scaled" for s in spans)
    assert {e["key"] for e in rec.snapshot()} == {"shape", "literal"}
    assert sum(s["durationMs"] for s in spans) / 1e3 == \
        pytest.approx(tot["compileSeconds"], rel=0.02, abs=1e-3)


def test_fixed_fingerprint_miss_still_learns_its_kind():
    rec = CompileRecorder()
    f = instrument(jax.jit(lambda x: x + 1), "test.fixed",
                   fingerprint="plan-a", recorder=rec)
    g = instrument(jax.jit(lambda x: x + 2), "test.fixed",
                   fingerprint="plan-b", recorder=rec)
    f(jnp.arange(4))
    f(jnp.arange(4))
    g(jnp.arange(4))                    # another plan, the same shapes
    assert [e.key for e in rec.events] == ["shape", "", "literal"]
    assert rec.site_shape_counts()["test.fixed"] == 1


# ---------------------------------------------------------------------------
# the spans of a split-streamed statement
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster():
    session = Session(default_schema="tiny")
    coord = CoordinatorServer(session).start()
    coord.state.scheduler.split_rows = 8192
    # one process, one clock pair: the worker's announce says so
    # (`spanClock`), its spans are adopted with no offset, and a child
    # lies inside its parent to the rounding of `durationMs`
    worker = WorkerServer("phase-w0", coord.uri, announce_interval_s=0.1,
                          catalog=session.catalog).start()
    deadline = time.time() + 5
    while not coord.state.active_nodes() and time.time() < deadline:
        time.sleep(0.05)
    yield coord, worker, session
    coord.stop()
    worker.stop()


# what is left between two spans of one process: `durationMs` is rounded
# to a microsecond, at either end of a comparison
ROUNDING_NS = 2e3


def _interval(sp):
    s0 = sp["startTimeUnixNano"]
    return s0, s0 + sp["durationMs"] * 1e6


def _inside(inner, outer):
    (i0, i1), (o0, o1) = _interval(inner), _interval(outer)
    return o0 <= i0 and i1 <= o1 + ROUNDING_NS


def _covered_ms(intervals, lo, hi):
    total, at = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, at), min(e, hi)
        if e > s:
            total += e - s
            at = e
    return total / 1e6


def _traced(coord, sql, profiling=False):
    coord.state.scheduler.spool.clear()
    client = Client(coord.uri, user="phases")
    client.execute("SET SESSION enable_tracing = true")
    if profiling:
        client.execute("SET SESSION enable_profiling = true")
    try:
        before = RECORDER.totals()
        res = client.execute(sql)
        after = RECORDER.totals()
    finally:
        client.execute("SET SESSION enable_profiling = false")
        client.execute("SET SESSION enable_tracing = false")
    info = client.query_info(res.query_id)
    assert info["distributed"], info["fallbackReason"]
    spans = client._request(
        "GET", f"{coord.uri}/v1/query/{res.query_id}/trace")["spans"]
    return spans, before, after


def test_traced_q6_yields_every_phase_span(cluster):
    coord, worker, session = cluster
    # a literal no other test sends: its filter compiles here
    spans, before, after = _traced(coord, q6("23.37"))
    names = {s["name"] for s in spans}
    assert Q6_SPANS <= names, Q6_SPANS - names
    ids = {s["spanId"]: s for s in spans}
    roots = [s for s in spans if s["parentSpanId"] not in ids]
    assert [s["name"] for s in roots] == ["query"]

    def parent(sp):
        return ids[sp["parentSpanId"]]

    # who hangs under whom
    want = {"exec-lock-wait": {"query"}, "exec-lock-held": {"query"},
            "task-lock-wait": {"source-stage"}, "stage-prepare": {"query"},
            "source-stage": {"query"}, "final-stage": {"query"},
            "task-create": {"source-stage"}, "task-drain": {"source-stage"},
            "task-record": {"source-stage"}, "task-decode": {"source-stage"},
            "spool-lookup": {"source-stage"}, "stage-wait": {"source-stage"},
            "worker-task": {"source-stage"}, "pin-builds": {"worker-task"},
            "task-merge": {"worker-task"}, "task-emit": {"worker-task"},
            "merge-decode": {"final-stage"},
            "merge-partials": {"final-stage"},
            "merge-run": {"final-stage"}, "result-fetch": {"final-stage"},
            "decode-rows": {"final-stage"}}
    want.update({n: {"worker-task"} for n in SPLIT_PHASES})
    for s in spans:
        if s["name"] in want:
            assert parent(s)["name"] in want[s["name"]], s
    # children inside their parents. One process, one clock pair: the
    # worker's spans, adopted with no offset here, too
    for s in spans:
        if s["parentSpanId"] in ids:
            assert _inside(s, parent(s)), (s, parent(s))
    stage = next(s for s in spans if s["name"] == "source-stage")
    for s in spans:
        if s["name"] in SPLIT_PHASES:
            assert _inside(s, stage), s
    # five spans a split
    n_splits = stage["attributes"]["splits"]
    for n in SPLIT_PHASES:
        assert sum(s["name"] == n for s in spans) == n_splits
    # the task folded its splits' partials and staged one page
    drain = next(s for s in spans if s["name"] == "task-drain")
    assert drain["attributes"]["pages"] == 1
    assert drain["attributes"]["bytes"] > 0
    task = next(s for s in spans if s["name"] == "worker-task")
    assert (task["attributes"]["foldedSplits"], task["attributes"]["pagesOut"],
            task["attributes"]["flushes"]) == (n_splits, 1, 0)
    merge = next(s for s in spans if s["name"] == "task-merge")
    assert merge["attributes"]["partials"] == n_splits
    emit = next(s for s in spans if s["name"] == "task-emit")
    assert emit["attributes"]["rows"] == 1 and \
        emit["attributes"]["bytes"] == drain["attributes"]["bytes"]
    assert all(s["attributes"]["bytes"] == 0 for s in spans
               if s["name"] == "split-emit")
    final = next(s for s in spans if s["name"] == "final-stage")
    assert final["attributes"]["pages"] == 1
    wait = next(s for s in spans if s["name"] == "stage-wait")
    assert drain["attributes"]["polls"] >= 0 and \
        wait["attributes"]["polls"] >= 1
    # the children tile the parent: 95% of worker-task outside
    # pin-builds, of final-stage, and of source-stage
    for name in ("worker-task", "final-stage", "source-stage"):
        p = next(s for s in spans if s["name"] == name)
        lo, hi = _interval(p)
        # `stage-wait` (the stage loop, looking every 20 ms) covers the
        # stage by itself; leave it out, and the work under the stage
        # still tiles it but for one look between the last page and
        # `task-record`: 5% of a stage of seconds, more of this one
        kids = [_interval(s) for s in spans
                if s["parentSpanId"] == p["spanId"]
                and s["name"] != "stage-wait"]
        wall = (hi - lo) / 1e6
        slack = 45.0 if name == "source-stage" else 0.0
        assert _covered_ms(kids, lo, hi) >= 0.95 * wall - slack, \
            (name, wall, _covered_ms(kids, lo, hi))
    # the statement's compile spans are the recorder's compile seconds
    compiles = [s for s in spans if s["name"] == "compile"]
    assert compiles and all(
        s["attributes"]["key"] in ("shape", "literal") and
        s["attributes"]["site"] for s in compiles)
    assert any(parent(s)["name"] == "split" for s in compiles)
    span_s = sum(s["durationMs"] for s in compiles) / 1e3
    rec_s = after["compileSeconds"] - before["compileSeconds"]
    assert rec_s > 0 and span_s == pytest.approx(rec_s, rel=0.02, abs=2e-3)
    assert len(compiles) == after["compiles"] - before["compiles"]


def test_tracing_alone_does_not_fence(cluster):
    from trino_tpu.metrics import OPERATOR_DEVICE_MS

    def fenced_ms():
        return sum(OPERATOR_DEVICE_MS.value(operator=op) for op in
                   ("FilterNode", "AggregateNode", "ProjectNode",
                    "ScanNode"))

    coord, worker, session = cluster
    f0 = fenced_ms()
    spans, _, _ = _traced(coord, q6("22.91"))
    # no operator was fenced: the fenced branch (block_until_ready and
    # its jit__reduce_sum row count, exec/executor.py) never ran
    assert fenced_ms() == f0
    task = next(s for s in spans if s["name"] == "worker-task")
    assert "deviceMs" not in task["attributes"]
    assert {s["name"] for s in spans} >= set(SPLIT_PHASES)
    # profiling on as well: the fences are back, on the worker too
    spans, _, _ = _traced(coord, q6("22.92"), profiling=True)
    assert fenced_ms() > f0
    task = next(s for s in spans if s["name"] == "worker-task")
    assert task["attributes"]["deviceMs"] >= 0
    assert "hostMs" in task["attributes"]


# ---------------------------------------------------------------------------
# inside a split: operator spans beside the `split` lap, and its dispatches
# ---------------------------------------------------------------------------

OPERATORS = ("filter-project", "join", "aggregate", "sort", "dynamic-filter")


def _split_operators(spans):
    """{(worker-task id, split index): [top-level operator spans]} and
    the `split` laps under the same keys."""
    ops, laps = {}, {}
    for s in spans:
        if s["name"] == "split":
            laps[(s["parentSpanId"], s["attributes"]["index"])] = s
        elif s["name"] in OPERATORS and "split" in s["attributes"]:
            ops.setdefault((s["parentSpanId"], s["attributes"]["split"]),
                           []).append(s)
    return ops, laps


# what a split's `join` span says with the query that makes it say so:
# the task's LUT answers an inner join over a unique build and no dynamic
# filter runs in front of it (PR 50); a semi join keeps the range test
# (the queries are defined further down: looked up when a test runs)
JOIN_LAPS = {
    "lut": (lambda day: orders_join(day),
            {"lutForm": "packed", "wordBits": 16, "dynamicFilter": "lut"}),
    "range": (lambda day: orders_exist(day), {"dynamicFilter": "range"}),
}


@pytest.mark.parametrize("filtered", sorted(JOIN_LAPS))
@pytest.mark.parametrize("profiling", [False, True],
                         ids=["tracing-alone", "fenced"])
def test_a_traced_tasks_splits_have_operator_spans(cluster, profiling,
                                                   filtered):
    coord, worker, session = cluster
    query, join_says = JOIN_LAPS[filtered]
    sql = query("1996-02-1" + str(int(profiling)))
    spans, _, _ = _traced(coord, sql, profiling=profiling)
    ids = {s["spanId"]: s for s in spans}
    ops, laps = _split_operators(spans)
    task, pin = _join_task(spans)
    mine = {k: v for k, v in laps.items() if k[0] == task["spanId"]}
    assert sorted(i for _, i in mine) == list(range(8))
    for key, lap in laps.items():
        # every split of every task, the probing task's with its join
        names = [s["name"] for s in sorted(ops[key], key=_interval)]
        if key in mine:
            assert names == ["join", "filter-project", "aggregate"], key
        else:
            assert set(names) == {"filter-project"}, (key, names)
        for s in ops[key]:
            # under the task, beside the lap, inside it on the clock,
            # and saying which split and, a join, what stood in front
            # of it and the form of the LUT it probed: the one-column
            # payload (five priorities) rides in the LUT's word, 1 + 8
            # + 1 bits in an int16
            assert ids[s["parentSpanId"]]["name"] == "worker-task"
            assert s["attributes"] == dict(
                {"split": key[1]},
                **(join_says if s["name"] == "join" else {}))
            assert _inside(s, lap), (s, lap)
        own = sorted(_interval(s) for s in ops[key])
        for (_, end), (start, _) in zip(own, own[1:]):
            assert start >= end - ROUNDING_NS      # each its own wall
    # the eager ops on the build's key range, inside their join: in no
    # split whose join the task's LUT answers
    filters = [s for s in spans if s["name"] == "dynamic-filter"]
    assert len(filters) == (8 if filtered == "range" else 0)
    for s in filters:
        join = ids[s["parentSpanId"]]
        assert join["name"] == "join" and _inside(s, join)
        assert s["attributes"] == {"split": join["attributes"]["split"]}
    # the lap keeps what it had: `compile` under `split`, never an
    # operator's child; no operator span is a lap's child
    taken = {s["spanId"] for v in ops.values() for s in v} | \
        {s["spanId"] for s in filters}
    assert not [s for s in spans if s["parentSpanId"] in taken
                and s["name"] != "dynamic-filter"]
    lap_ids = {s["spanId"] for s in laps.values()}
    assert {s["name"] for s in spans
            if s["parentSpanId"] in lap_ids} <= {"compile"}
    ex = worker.task_manager._executor
    assert (ex._operator_spans, ex._operator_split,
            ex._open_operators) == (False, None, [])


def test_a_compile_in_an_operator_still_hangs_under_the_split(cluster):
    coord, worker, session = cluster
    # an IN list's length is shape proper: this filter compiles
    spans, before, after = _traced(
        coord, q6("24") + " AND l_linenumber IN (1, 2, 3, 5, 6)")
    assert after["compiles"] > before["compiles"]
    ids = {s["spanId"]: s for s in spans}
    ops, laps = _split_operators(spans)
    inside_an_operator = 0
    for c in (s for s in spans if s["name"] == "compile"):
        lap = ids[c["parentSpanId"]]
        if lap["name"] != "split":
            continue
        key = (lap["parentSpanId"], lap["attributes"]["index"])
        inside_an_operator += any(_inside(c, op) for op in ops[key])
    assert inside_an_operator >= 1


def test_dispatches_is_the_recorders_call_delta_of_the_split(
        cluster, monkeypatch):
    coord, worker, session = cluster
    calls = []
    record = RECORDER.record

    def spy(*args, **kwargs):
        calls.append(tracing.unix_ns(time.monotonic()))
        return record(*args, **kwargs)

    monkeypatch.setattr(RECORDER, "record", spy)
    spans, before, after = _traced(coord, orders_join("1996-03-05"))
    counted = [s for s in spans if s["name"] == "split"]
    assert len(counted) == 10
    for s in counted:
        lo, hi = _interval(s)
        assert s["attributes"]["dispatches"] == \
            sum(lo <= at <= hi + ROUNDING_NS for at in calls), s
    # a probing split dispatches its join, its aggregate and the
    # expression under them; the task's first builds the LUT besides
    task, _ = _join_task(spans)
    probing = sorted((s for s in counted
                      if s["parentSpanId"] == task["spanId"]),
                     key=lambda s: s["attributes"]["index"])
    per_split = [s["attributes"]["dispatches"] for s in probing]
    assert per_split[0] > per_split[1] >= 2
    assert len(set(per_split[1:])) == 1
    # hits and misses: all of the statement's, the coordinator's too
    total = (after["hits"] + after["compiles"]) - \
        (before["hits"] + before["compiles"])
    assert len(calls) == total
    assert sum(s["attributes"]["dispatches"] for s in counted) <= total
    # a count, nothing else: no event, no lock taken for it
    rec = CompileRecorder()
    assert rec.thread_calls() == 0
    rec.record("site", "fp", 0.0, True)
    rec.record("site", "fp", 0.1, False)
    assert rec.thread_calls() == 2
    seen = []
    th = threading.Thread(target=lambda: seen.append(rec.thread_calls()))
    th.start()
    th.join()
    assert seen == [0]                  # per thread


def test_tracing_off_a_task_builds_no_operator_span(cluster, monkeypatch):
    import jax.profiler
    coord, worker, session = cluster
    CountingAnnotation.names = []
    built = []

    class CountingSpan(tracing.Span):
        def __init__(self, *args, **kwargs):
            built.append(args[0])
            super().__init__(*args, **kwargs)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountingAnnotation)
    monkeypatch.setattr(tracing, "Span", CountingSpan)
    coord.state.scheduler.spool.clear()
    client = Client(coord.uri, user="phases")
    res = client.execute(orders_join("1996-04-07"))
    info = client.query_info(res.query_id)
    assert info["distributed"] and info["stageStats"]["tasks"] >= 2
    assert built == [] and CountingAnnotation.names == []
    ex = worker.task_manager._executor
    assert ex._operator_spans is False and ex._operator_split is None
    ex.operator_span("join")
    assert ex._open_operators == [] and built == []
    # and on: each of a task's operator spans has its `tt:` twin
    spans, _, _ = _traced(coord, orders_exist("1996-04-08"))
    for name in ("join", "aggregate", "filter-project", "dynamic-filter"):
        n = sum(s["name"] == name for s in spans)
        assert n >= 8 and CountingAnnotation.names.count("tt:" + name) == n
    # in the split loop without a `Span` each: a row of five numbers
    assert not set(built) & {"join", "aggregate", "filter-project",
                             "dynamic-filter"}


# ---------------------------------------------------------------------------
# the broadcast build's hand-over: once a task, at a lattice capacity
# ---------------------------------------------------------------------------

JOIN_SITES = ("join.payload_ranges", "join.dense_build_packed_lut",
              "join.dense_join_packed")


def orders_join(before: str) -> str:
    # `orders` (15,000 rows at tiny) is over the fixture's split_rows, so
    # its filtered output is a build stage of its own and reaches the
    # lineitem stage's tasks as a ValuesNode inside the fragment
    return ("SELECT o_orderpriority, count(*) AS n, "
            "sum(l_extendedprice) AS revenue "
            "FROM lineitem JOIN orders ON l_orderkey = o_orderkey "
            f"WHERE o_orderdate < DATE '{before}' "
            "GROUP BY o_orderpriority ORDER BY o_orderpriority")


def orders_exist(before: str) -> str:
    # the same build as a semi join's: the split's join is no LUT join,
    # and the dynamic filter's range test runs in front of it
    return ("SELECT l_returnflag, count(*) AS n, "
            "sum(l_extendedprice) AS revenue FROM lineitem l "
            "WHERE EXISTS (SELECT 1 FROM orders o "
            "WHERE o.o_orderkey = l.l_orderkey "
            f"AND o.o_orderdate < DATE '{before}') "
            "GROUP BY l_returnflag ORDER BY l_returnflag")


def _join_task(spans):
    """(worker-task, pin-builds) of the stage that probes the build."""
    tasks = {s["spanId"]: s for s in spans if s["name"] == "worker-task"}
    pin = max((s for s in spans if s["name"] == "pin-builds"),
              key=lambda s: s["attributes"]["builds"])
    return tasks[pin["parentSpanId"]], pin


def test_builds_of_one_lattice_point_share_the_join_programs(cluster):
    coord, worker, session = cluster
    # 4,653 and 6,000 build rows: 5,120 and 6,144 in multiples of 1,024,
    # both 6,144 on the lattice
    spans, _, _ = _traced(coord, orders_join("1994-01-07"))
    _, pin = _join_task(spans)
    first_rows = pin["attributes"]["rows"]
    assert pin["attributes"]["capacity"] == 6144
    shapes = RECORDER.site_shape_counts()
    assert all(shapes.get(site, 0) >= 1 for site in JOIN_SITES), shapes
    spans, before, after = _traced(coord, orders_join("1994-08-19"))
    _, pin = _join_task(spans)
    assert pin["attributes"]["rows"] - first_rows > 1024
    assert pin["attributes"] == {"builds": 1, "capacity": 6144,
                                 "rows": pin["attributes"]["rows"]}
    again = RECORDER.site_shape_counts()
    assert {s: again[s] for s in JOIN_SITES} == \
        {s: shapes[s] for s in JOIN_SITES}
    assert after["shapeKeyedCompiles"] == before["shapeKeyedCompiles"]
    assert not [s for s in spans if s["name"] == "compile"
                and s["attributes"]["key"] == "shape"]


def test_a_task_puts_its_build_once_and_every_split_joins(cluster):
    coord, worker, session = cluster
    sql = orders_join("1995-06-17")
    spans, _, _ = _traced(coord, sql)
    task, pin = _join_task(spans)
    assert task["attributes"]["splits"] == 8
    assert task["attributes"]["valuePuts"] == 1
    assert pin["attributes"]["builds"] == 1
    # the stage that made the build has none of its own to put
    other = [s for s in spans if s["name"] == "worker-task"
             and s is not task]
    assert other and all(s["attributes"]["valuePuts"] == 0 for s in other)
    # the pinned build's reservation went with the task
    assert worker.task_manager.memory_info()["reserved"] == 0
    # every split probed the pinned build: the single-node answer
    coord.state.scheduler.spool.clear()
    got = Client(coord.uri, user="phases").execute(sql).rows
    want = Session(default_schema="tiny").execute(sql).rows
    assert [[str(c) for c in r] for r in got] == \
        [[str(c) for c in r] for r in want]
    assert sum(r[1] for r in want) > 0


@pytest.mark.parametrize("rows", [0, 1, 1024, 1025, 5000, 6000, 1_483_000])
def test_run_values_puts_at_a_lattice_capacity(rows):
    from trino_tpu.batch import bucket_capacity
    ex = Session(default_schema="tiny").executor
    zero_column = rows > 100_000        # a live mask only: cheap at any size
    if zero_column:
        node = L.ValuesNode((), (), rows, (), ())
    else:
        node = L.ValuesNode((np.arange(rows, dtype=np.int64),),
                            (np.ones(rows, dtype=np.bool_),), rows, (), ())
    puts = ex.stats.value_puts
    batch = ex.run_values(node)
    assert ex.stats.value_puts == puts + 1
    cap = batch.capacity
    assert cap >= rows and cap == bucket_capacity(rows)
    # at most a third of the capacity is padding above 1,024 rows
    assert cap == 1024 if rows <= 1024 else 2 * cap < 3 * rows
    # on the lattice {2^k, 1.5 * 2^k}
    assert cap & (cap - 1) == 0 or (cap // 3) & (cap // 3 - 1) == 0
    live = np.asarray(batch.live)
    assert live[:rows].all() and not live[rows:].any()
    if not zero_column:
        col = batch.columns[0]
        assert col.data.shape == (cap,)
        assert np.array_equal(np.asarray(col.data)[:rows], node.arrays[0])
        assert not np.asarray(col.valid)[rows:].any()


def test_static_subtrees_pin_a_values_build_and_not_a_scan():
    from trino_tpu.server.tasks import _static_subtrees

    def scan(table):
        return L.ScanNode("tpch", "tiny", table, None, (0,), ())

    def join(probe, build):
        return L.JoinNode("inner", probe, build, (0,), (0,), None, True, ())

    driver = scan("lineitem")
    values = L.ValuesNode((np.arange(3),), (np.ones(3, dtype=bool),), 3,
                          (), ())
    filtered = L.FilterNode(scan("customer"), None, ())
    bare = scan("orders")
    root = join(join(join(L.FilterNode(driver, None, ()), values),
                     filtered), bare)
    pinned = _static_subtrees(root, driver)
    assert sorted(map(id, pinned)) == sorted(map(id, (values, filtered)))
    # no driver in the fragment: nothing is constant "across splits"
    assert _static_subtrees(join(values, bare), driver) == []


def test_single_node_route_writes_plan_spans():
    """The serving layer plans ahead of Session.execute_planned: its
    `plan` / `optimize` spans fill the timeline's plan phase."""
    session = Session(default_schema="tiny")
    coord = CoordinatorServer(session).start()
    try:
        client = Client(coord.uri, user="phases")
        client.execute("SET SESSION enable_tracing = true")
        res = client.execute(
            "SELECT n_regionkey, count(*) FROM nation GROUP BY n_regionkey")
        base = f"{coord.uri}/v1/query/{res.query_id}"
        spans = client._request("GET", f"{base}/trace")["spans"]
        by = {}
        for s in spans:
            by.setdefault(s["name"], []).append(s)
        assert by["plan"][0]["attributes"]["planCache"] == "miss"
        assert "optimize" in by
        query = by["query"][0]["spanId"]
        assert by["plan"][0]["parentSpanId"] == query
        assert by["optimize"][0]["parentSpanId"] == query
        timeline = client._request("GET", f"{base}/timeline")
        assert timeline["phases"]["plan"] > 0
    finally:
        coord.stop()


def test_dispatcher_has_one_path():
    """Traced and untraced statements take the exec lock at the same
    place: concurrent traced statements do not serialise end to end on
    an outer lock, and each gets its own spans."""
    import inspect
    from trino_tpu.server import coordinator
    src = inspect.getsource(coordinator.Dispatcher)
    assert "saved_tracer" not in src
    assert src.count("exec_lock.acquire()") == 1
    assert "with self.exec_lock" not in src
