"""What a task-create puts on the wire (server/serde.py version 2,
server/tasks.py `task_body`): a stage's fragment as one body of bytes
with its arrays as raw buffers, string pools the nodes' catalog holds as
handles, version 1's text still taken, the work key made from the
fragment's bytes once a stage, and `POST /v1/task` in both body forms.
"""

import dataclasses
import json
import time
from urllib.request import Request, urlopen

import numpy as np
import pytest

from trino_tpu import ir
from trino_tpu.batch import Field, Schema
from trino_tpu.catalog import Catalog, PoolMismatchError, default_catalog
from trino_tpu.connectors.tpch.datagen import TableData
from trino_tpu.exec.session import Session
from trino_tpu.planner import logical as L
from trino_tpu.server import serde
from trino_tpu.server.exchange_spool import ExchangeSpool
from trino_tpu.server.tasks import (TASK_MEDIA_TYPE, Split, TaskManager,
                                    decode_fragment, encode_fragment,
                                    split_task_body, task_body)
from trino_tpu.types import BIGINT, VARCHAR

# every dtype a materialised build or a partition page carries
BUILD_DTYPES = ("int8", "int16", "int32", "int64", "uint8", "uint32",
                "float32", "float64", "bool", "<M8[D]")


class GeneratorConnector:
    """A connector of the generator kind (every node makes the same
    table from the schema's scale): what the catalog names pools of."""

    def __init__(self, tables):
        self.tables = tables

    @staticmethod
    def scale_for_schema(schema):
        return 1.0

    def get_table(self, schema, table):
        return self.tables[table]


def big_table(n=100_000, prefix="Customer#"):
    pool = tuple(f"{prefix}{i:09d}" for i in range(n))
    schema = Schema.of(Field("k", BIGINT),
                       Field("name", VARCHAR, dictionary=pool))
    return TableData("big", schema, [np.arange(n), np.arange(
        n, dtype=np.int32)])


def catalog_with(table) -> Catalog:
    cat = default_catalog()
    cat.register("gen", GeneratorConnector({"big": table}))
    return cat


@pytest.fixture(scope="module")
def tiny():
    cat = default_catalog()
    return cat, cat.get_table("tpch", "tiny", "customer")


def fragment(customer, dtypes=BUILD_DTYPES):
    """A fragment with a ScanNode (the driver: shared, so `$ref`), a
    ValuesNode whose `fields` hold a catalog pool, an `ir` node with a
    `sub_field`, and arrays of every dtype."""
    schema = customer.schema
    name = schema.field("c_name")
    scan = L.ScanNode("tpch", "tiny", "customer", schema, (0, 1),
                      (("c_custkey", BIGINT), ("c_name", VARCHAR)))
    arrays = tuple(np.arange(7).astype(dt) for dt in dtypes) + (
        np.zeros((0,), dtype=np.int64), np.arange(6.0).reshape(2, 3))
    values = L.ValuesNode(arrays=arrays,
                          valids=tuple(np.ones(7, dtype=np.bool_)
                                       for _ in dtypes),
                          num_rows=7, fields=(name, Field(
                              "plan_time", VARCHAR,
                              dictionary=("a", "b", "é"))),
                          output=(("c_name", VARCHAR),))
    member = ir.InSubqueryRef(ir.ColumnRef(1, VARCHAR), values, name,
                              schema.field("c_mktsegment"))
    root = L.FilterNode(scan, member, scan.output)
    return {"root": root, "driver": scan, "merge_agg": True}


def v2_body(head: dict, arrays: bytes = b"") -> bytes:
    """A version-2 body written by hand, as a foreign sender might."""
    raw = json.dumps(head).encode()
    return serde.MAGIC + len(raw).to_bytes(8, "little") + raw + \
        bytes(serde.pad(12 + len(raw))) + arrays


def same(a, b):
    """Equal field by field, arrays by dtype, shape and content."""
    if isinstance(a, np.ndarray):
        return isinstance(b, np.ndarray) and a.dtype == b.dtype and \
            a.shape == b.shape and np.array_equal(a, b)
    if dataclasses.is_dataclass(a):
        return type(a) is type(b) and all(
            same(getattr(a, f.name), getattr(b, f.name))
            for f in dataclasses.fields(a))
    if isinstance(a, (tuple, list)):
        return type(a) is type(b) and len(a) == len(b) and \
            all(same(x, y) for x, y in zip(a, b))
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(same(a[k], b[k]) for k in a)
    return type(a) is type(b) and a == b


# ---------------------------------------------------------------------------
# round trips
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("version", (1, 2))
def test_round_trip_is_equal_field_by_field_and_keeps_identity(tiny, version):
    cat, customer = tiny
    frag = fragment(customer)
    if version == 1:
        blob = serde.dumps(frag)
        assert isinstance(blob, str) and json.loads(blob)["v"] == 1
    else:
        blob = encode_fragment(frag, cat)
        assert isinstance(blob, bytes) and serde.is_bytes_form(blob)
    got = decode_fragment(blob, cat)
    assert same(got, frag)
    # the driver scan is the root's child: one object, as it was sent
    assert got["root"].child is got["driver"]
    member = got["root"].predicate
    assert member.arg_field.dictionary == customer.schema.field(
        "c_name").dictionary
    assert member.sub_field.name == "c_mktsegment"
    if version == 2:
        # and the pools are this catalog's own tuples again
        assert member.sub_field.dictionary is customer.schema.field(
            "c_mktsegment").dictionary
        assert member.plan.fields[0].dictionary is customer.schema.field(
            "c_name").dictionary


@pytest.mark.parametrize("dtype", BUILD_DTYPES)
def test_an_array_rides_as_its_raw_buffer(dtype):
    a = (np.arange(1000) % 2).astype(dtype)
    body = serde.dumps_bytes({"a": a, "b": a[::2]})
    head = json.loads(body[12:12 + int.from_bytes(body[4:12], "little")])
    assert head["v"] == 2 and b"base64" not in body
    got = serde.loads_bytes(body)
    assert got["a"].dtype == a.dtype and np.array_equal(got["a"], a)
    assert np.array_equal(got["b"], a[::2])
    # a view of the body at a 64-byte boundary, not a copy
    assert not got["a"].flags.owndata and not got["a"].flags.writeable
    assert len(body) < 2 * a.nbytes + 1024


def test_version_one_bytes_are_taken_as_text(tiny):
    cat, customer = tiny
    frag = fragment(customer)
    assert same(decode_fragment(serde.dumps(frag).encode(), cat), frag)


# ---------------------------------------------------------------------------
# string pools
# ---------------------------------------------------------------------------

def test_a_catalog_pool_goes_as_a_handle_and_comes_back_the_catalogs_own():
    table = big_table()
    cat = catalog_with(table)
    schema = cat.get_table("gen", "s", "big").schema
    pool = schema.field("name").dictionary
    scan = L.ScanNode("gen", "s", "big", schema, (0,), (("k", BIGINT),))
    values = L.ValuesNode((), (), 0, (schema.field("name"),), ())
    stats = {}
    body = encode_fragment({"root": scan, "build": values}, cat, stats)
    assert len(body) < 64 * 1024 < len(serde.dumps(scan))
    assert stats == {"poolHandles": 1, "inlinePools": 0,
                     "inlinePoolBytes": 0}
    # a worker with a catalog of its own: the same table, another tuple
    theirs = catalog_with(big_table())
    seen = {}
    got = decode_fragment(body, theirs, seen)
    their_pool = theirs.get_table("gen", "s", "big").schema.field(
        "name").dictionary
    assert their_pool is not pool and seen == {"resolvedPools": 1}
    assert got["root"].table_schema.field("name").dictionary is their_pool
    assert got["build"].fields[0].dictionary is their_pool
    # and in one process, the sender's own
    assert decode_fragment(body, cat)["build"].fields[0].dictionary is pool


def test_a_pool_no_catalog_names_goes_inline(tiny):
    cat, _ = tiny
    merged = tuple(f"merged-{i}" for i in range(50))
    memory = cat.connector("memory")
    fld = Field("s", VARCHAR, dictionary=merged)
    memory.create_table("default", "t", TableData(
        "t", Schema.of(fld), [np.arange(50, dtype=np.int32)]))
    held = cat.get_table("memory", "default", "t").schema.field("s")
    # a pool built at plan time (a set operation's merged dictionary)
    # and a memory table's own, which an INSERT may change under a task
    node = L.ValuesNode((), (), 0, (Field("m", VARCHAR, dictionary=tuple(
        merged)), held), ())
    stats = {}
    body = encode_fragment(node, cat, stats)
    assert stats["poolHandles"] == 0 and stats["inlinePools"] == 2
    assert stats["inlinePoolBytes"] == 2 * sum(map(len, merged))
    got = decode_fragment(body)           # no catalog needed
    assert got.fields[0].dictionary == merged == got.fields[1].dictionary


def test_a_wrong_digest_fails_the_task_naming_table_and_column():
    cat = catalog_with(big_table(1000))
    schema = cat.get_table("gen", "s", "big").schema
    scan = L.ScanNode("gen", "s", "big", schema, (0,), (("k", BIGINT),))
    body = encode_fragment({"root": scan, "driver": scan}, cat)
    theirs = catalog_with(big_table(1000, prefix="Kunde#"))
    with pytest.raises(PoolMismatchError, match=r"gen\.s\.big\.name"):
        decode_fragment(body, theirs)
    tm = TaskManager(theirs, node_id="wire-w")
    task = tm.create_or_update("t-digest", body,
                               [Split("gen", "s", "big", 0, 1000)])
    deadline = time.monotonic() + 60
    while task.state in ("PENDING", "RUNNING"):
        assert time.monotonic() < deadline
        time.sleep(0.01)
    assert task.state == "FAILED"
    assert "gen.s.big.name" in task.error and "differs" in task.error
    # a table this node does not have at all is named too
    with pytest.raises(PoolMismatchError, match=r"gen\.s\.big\.name"):
        decode_fragment(body, default_catalog())
    with pytest.raises(ValueError, match="no catalog"):
        decode_fragment(body)


# ---------------------------------------------------------------------------
# data only
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class NotOfThePlan:
    x: int = 0


@pytest.mark.parametrize("version", (1, 2))
def test_an_unregistered_class_is_refused(version):
    dumps = serde.dumps if version == 1 else serde.dumps_bytes
    with pytest.raises(TypeError, match="unregistered fragment class"):
        dumps({"root": NotOfThePlan()})
    head = {"v": version, "root": {"$ref": 0},
            "slots": [{"$dc": "NotOfThePlan", "f": {"x": 1}}]}
    blob = json.dumps(head) if version == 1 else v2_body(head)
    with pytest.raises(TypeError, match="unregistered fragment class"):
        decode_fragment(blob)


@pytest.mark.parametrize("leaf", (
    {"$nd": "<i8", "shape": [4], "at": 64, "n": 32},      # past the end
    {"$nd": "<i8", "shape": [4], "at": -8, "n": 32},
    {"$nd": "<i8", "shape": [4], "at": 0, "n": 8},        # length lies
    {"$nd": "|O", "shape": [1], "at": 0, "n": 8},         # object array
))
def test_a_foreign_body_cannot_read_outside_itself(leaf):
    blob = v2_body({"v": 2, "slots": [], "root": leaf}, bytes(64))
    with pytest.raises((ValueError, TypeError)):
        serde.loads_bytes(blob)


# ---------------------------------------------------------------------------
# the work key
# ---------------------------------------------------------------------------

def test_work_key_follows_the_fragment_its_pools_and_the_splits(tiny):
    cat, customer = tiny
    splits = [Split("tpch", "tiny", "customer", 0, 750),
              Split("tpch", "tiny", "customer", 750, 750)]

    def key(catalog, frag, sp):
        return ExchangeSpool.work_key(ExchangeSpool.fragment_key(
            encode_fragment(frag, catalog)), sp)
    base = key(cat, fragment(customer), splits)
    # equal fragments, two encodings (new node objects, another
    # statement of the process): one key
    assert key(cat, fragment(customer), splits) == base
    assert key(cat, fragment(customer), splits[:1]) != base
    assert key(cat, fragment(customer), [
        splits[0], Split("tpch", "tiny", "customer", 750, 749)]) != base
    assert key(cat, dict(fragment(customer), profile=True), splits) != base
    # the same plan over a table whose pool differs: the handle holds
    # the digest, so the key differs
    a, b = catalog_with(big_table(100)), \
        catalog_with(big_table(100, prefix="Kunde#"))

    def scan(catalog):
        schema = catalog.get_table("gen", "s", "big").schema
        return {"root": L.ScanNode("gen", "s", "big", schema, (1,),
                                   (("name", VARCHAR),))}
    sp = [Split("gen", "s", "big", 0, 100)]
    assert key(a, scan(a), sp) == key(a, scan(a), sp) != key(b, scan(b), sp)


# ---------------------------------------------------------------------------
# POST /v1/task
# ---------------------------------------------------------------------------

def test_task_body_is_an_envelope_and_the_fragment_untouched():
    frag = encode_fragment({"root": None, "a": np.arange(5)})
    body = task_body({"splits": [], "deadline": 12.5}, frag)
    envelope, got = split_task_body(body)
    assert envelope == {"splits": [], "deadline": 12.5}
    assert bytes(got) == frag and (len(body) - len(frag)) % 64 == 0
    with pytest.raises(ValueError):
        split_task_body(body[:3])
    with pytest.raises(ValueError):
        split_task_body((1 << 20).to_bytes(4, "little") + b"{}")


@pytest.fixture(scope="module")
def worker():
    from trino_tpu.server.coordinator import CoordinatorServer
    from trino_tpu.server.worker import WorkerServer
    session = Session(default_schema="tiny")
    coord = CoordinatorServer(session).start()
    w = WorkerServer("wire-http", coord.uri, announce_interval_s=0.1,
                     catalog=session.catalog).start()
    yield session, w
    w.stop()
    coord.stop()


@pytest.mark.parametrize("form", ("json", "bytes"))
def test_post_task_runs_a_task_in_either_body_form(worker, form):
    """Today's `application/json` body (a version-1 text as a string of
    the document) and the envelope-and-bytes form run the same task."""
    from trino_tpu.server.security import internal_headers
    from trino_tpu.sql.parser import parse
    session, w = worker
    rel = session.planner().plan_query(
        parse("SELECT n_name FROM nation WHERE n_regionkey = 1"))
    scan = next(n for n in _nodes(rel.node) if isinstance(n, L.ScanNode))
    frag = {"root": rel.node, "driver": scan}
    splits = [vars(Split("tpch", "tiny", "nation", 0, 25))]
    if form == "json":
        data = json.dumps({"fragment": serde.dumps(frag),
                           "splits": splits}).encode()
        ctype = "application/json"
    else:
        data = task_body({"splits": splits},
                         encode_fragment(frag, session.catalog))
        ctype = TASK_MEDIA_TYPE
    tid = f"wire-{form}"
    req = Request(f"{w.uri}/v1/task/{tid}", data=data, method="POST",
                  headers={"Content-Type": ctype, **internal_headers()})
    with urlopen(req, timeout=30) as resp:
        assert resp.status == 200
    task = w.task_manager.get(tid)
    deadline = time.monotonic() + 120
    while task.state in ("PENDING", "RUNNING"):
        assert time.monotonic() < deadline
        time.sleep(0.01)
    assert task.state == "FINISHED", task.error
    assert task.rows_out == 5


def _nodes(root):
    from trino_tpu.planner.fragmenter import _subtree_nodes
    return _subtree_nodes(root)
