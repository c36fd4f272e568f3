"""Data-only wire serde for plan fragments.

Reference: Trino ships plan fragments between coordinator and workers as
Jackson-serialized JSON (sql/planner/PlanFragment.java + the codec in
server/InternalCommunicationModule) — data-only: deserializing attacker
bytes can at worst build a malformed plan, never execute code.  Round-2's
pickle serde did not have that property (a crafted POST /v1/task body could
run arbitrary code in the worker); this module replaces it.

Design: every node in a plan tree is a frozen dataclass from a closed set
of modules (planner.logical, ir, batch, types, server.tasks).  The encoder
reflects over dataclass fields; the decoder instantiates ONLY classes in
the registry, via their constructors.  Leaves: JSON primitives, tuples,
numpy arrays, enums from the registry.  Shared references are
encoded once and re-linked on decode ("$ref"), preserving the object
identity the executor's driver-scan substitution relies on (id(scan)).

Two versions of one tree. Version 1 (`dumps` / `loads`) is one JSON text,
arrays as base64 inside it. Version 2 (`dumps_bytes` / `loads_bytes`) is
what a task-create carries: one body of bytes,

    MAGIC | u64 head length | head (JSON: "v": 2, "slots", "root") |
    padding | the arrays' raw buffers, each at a 64-byte boundary

an array leaf in the head naming its buffer by offset into that last
section and length, so the sender copies each buffer once and the
receiver builds its arrays as views of the body (`np.frombuffer`).  And a
string pool that the nodes' catalog holds (the TableScanNode of the
reference carries table and column HANDLES, the worker's connector
resolves them) is written as `(catalog, schema, table, column, digest)`:
the receiver links its own catalog's tuple in after checking the digest.
A pool the sender's catalog cannot name goes inline, as in version 1.
"""

from __future__ import annotations

import base64
import dataclasses
import enum
import json
import struct
import threading
from typing import Any, Dict, Optional

import numpy as np


def _build_registry():
    from .. import ir
    from ..batch import Field, Schema
    from ..planner import logical
    from ..sql import ast_nodes
    from ..types import DataType, TypeKind

    classes: Dict[str, type] = {}
    for mod in (ir, logical, ast_nodes):
        for name in dir(mod):
            obj = getattr(mod, name)
            if isinstance(obj, type) and dataclasses.is_dataclass(obj):
                classes[obj.__name__] = obj
    for cls in (Field, Schema, DataType):
        classes[cls.__name__] = cls
    enums = {"TypeKind": TypeKind}
    return classes, enums


_registry_lock = threading.Lock()
_classes: Dict[str, type] = {}
_enums: Dict[str, type] = {}


def _registry():
    global _classes, _enums
    if not _classes:
        with _registry_lock:
            if not _classes:
                _classes, _enums = _build_registry()
    return _classes, _enums


def register(cls: type) -> type:
    """Add an out-of-module dataclass (e.g. Split) to the closed set."""
    _registry()
    _classes[cls.__name__] = cls
    return cls


MAGIC = b"TTF2"
_HEAD = struct.Struct("<4sQ")              # magic, head length


def pad(n: int) -> int:
    """Zero bytes after `n` to the next 64-byte boundary: where arrays
    start, so the views a receiver builds are aligned."""
    return -n % 64


class _Encoder:
    def __init__(self, digest_schemas: bool = False, pools=None,
                 buffers: Optional[list] = None):
        self.memo: Dict[int, int] = {}     # id(obj) -> slot
        self.slots = []                    # slot -> encoded node
        self.digest_schemas = digest_schemas
        # version 2: `pools` names the pools it holds (Catalog.pool_handle),
        # `buffers` collects the arrays' bytes beside the head
        self.pools = pools
        self.buffers = buffers
        self.buffer_bytes = 0
        self.pool_handles = 0
        self.inline_pools = 0
        self.inline_pool_bytes = 0

    def _array(self, obj: np.ndarray) -> dict:
        a = np.ascontiguousarray(obj)
        if a.dtype.hasobject:
            raise TypeError("cannot encode an object array on the wire")
        node = {"$nd": a.dtype.str, "shape": list(a.shape)}
        if self.buffers is None:
            node["data"] = base64.b64encode(a.tobytes()).decode()
            return node
        node["at"], node["n"] = self.buffer_bytes, a.nbytes
        if a.nbytes:
            self.buffers.append(a.reshape(-1).view(np.uint8))
            gap = pad(a.nbytes)
            if gap:
                self.buffers.append(bytes(gap))
            self.buffer_bytes += a.nbytes + gap
        return node

    def _pool(self, pool: tuple) -> Any:
        """A Field's dictionary: by handle where the catalog names it."""
        handle = self.pools.pool_handle(pool) \
            if self.pools is not None else None
        if handle is not None:
            self.pool_handles += 1
            return {"$pool": list(handle)}
        self.inline_pools += 1
        self.inline_pool_bytes += sum(
            len(x) for x in pool if isinstance(x, str))
        return self.enc(pool)

    def enc(self, obj: Any) -> Any:
        if obj is None or isinstance(obj, (bool, int, float, str)):
            return obj
        if isinstance(obj, (np.integer, np.floating, np.bool_)):
            return {"$np": obj.dtype.name, "v": obj.item()}
        if isinstance(obj, tuple):
            return {"$tup": [self.enc(x) for x in obj]}
        if isinstance(obj, list):
            return {"$list": [self.enc(x) for x in obj]}
        if isinstance(obj, frozenset):
            return {"$fset": [self.enc(x) for x in sorted(obj, key=repr)]}
        if isinstance(obj, dict):
            return {"$dict": [[self.enc(k), self.enc(v)]
                              for k, v in obj.items()]}
        if isinstance(obj, np.ndarray):
            return self._array(obj)
        if isinstance(obj, enum.Enum):
            return {"$enum": type(obj).__name__, "v": obj.value}
        if dataclasses.is_dataclass(obj):
            if self.digest_schemas and type(obj).__name__ == "Schema":
                return {"$schema": _schema_digest(obj)}
            slot = self.memo.get(id(obj))
            if slot is not None:
                return {"$ref": slot}
            classes, _ = _registry()
            name = type(obj).__name__
            if classes.get(name) is not type(obj):
                raise TypeError(f"unregistered fragment class: {name}")
            slot = len(self.slots)
            self.memo[id(obj)] = slot
            self.slots.append(None)        # reserve (cycles impossible in
            fields = {}                    # frozen trees, but keep order)
            for f in dataclasses.fields(obj):
                if f.name == "lock":
                    continue
                v = getattr(obj, f.name)
                fields[f.name] = self._pool(v) \
                    if name == "Field" and f.name == "dictionary" \
                    and isinstance(v, tuple) else self.enc(v)
            self.slots[slot] = {"$dc": name, "f": fields}
            return {"$ref": slot}
        raise TypeError(f"cannot encode {type(obj).__name__} on the wire")


class _Decoder:
    def __init__(self, slots, pools=None, buffers=None):
        self.raw = slots
        self.built = [None] * len(slots)
        self.done = [False] * len(slots)
        self.pools = pools                 # Catalog.resolve_pool
        self.buffers = buffers             # version 2's array section
        self.resolved_pools = 0

    def _array(self, obj: dict) -> np.ndarray:
        dtype = np.dtype(obj["$nd"])
        if dtype.hasobject:
            raise TypeError("object array on the wire")
        shape = obj["shape"]
        if "data" in obj:
            a = np.frombuffer(base64.b64decode(obj["data"]), dtype=dtype)
            return a.reshape(shape)
        if self.buffers is None:
            raise ValueError("array without data in a version-1 fragment")
        at, n = obj["at"], obj["n"]
        count = int(np.prod(shape, dtype=np.int64))
        if at < 0 or n != count * dtype.itemsize or \
                at + n > len(self.buffers):
            raise ValueError("array outside the fragment's body")
        return np.frombuffer(self.buffers, dtype=dtype, count=count,
                             offset=at).reshape(shape)

    def dec(self, obj: Any) -> Any:
        if obj is None or isinstance(obj, (bool, int, float, str)):
            return obj
        if isinstance(obj, list):          # only produced inside markers
            return [self.dec(x) for x in obj]
        if "$np" in obj:
            return np.dtype(obj["$np"]).type(obj["v"])
        if "$tup" in obj:
            return tuple(self.dec(x) for x in obj["$tup"])
        if "$list" in obj:
            return [self.dec(x) for x in obj["$list"]]
        if "$fset" in obj:
            return frozenset(self.dec(x) for x in obj["$fset"])
        if "$dict" in obj:
            return {self.dec(k): self.dec(v) for k, v in obj["$dict"]}
        if "$nd" in obj:
            return self._array(obj)
        if "$pool" in obj:
            if self.pools is None:
                raise ValueError("fragment names a catalog's string pool "
                                 "and no catalog was given to resolve it")
            self.resolved_pools += 1
            return self.pools.resolve_pool(*obj["$pool"])
        if "$enum" in obj:
            _, enums = _registry()
            return enums[obj["$enum"]](obj["v"])
        if "$ref" in obj:
            slot = obj["$ref"]
            if not self.done[slot]:
                node = self.raw[slot]
                classes, _ = _registry()
                cls = classes.get(node["$dc"])
                if cls is None:
                    raise TypeError(
                        f"unregistered fragment class: {node['$dc']}")
                kwargs = {k: self.dec(v) for k, v in node["f"].items()}
                self.built[slot] = cls(**kwargs)
                self.done[slot] = True
            return self.built[slot]
        raise TypeError(f"bad wire object: {list(obj)[:3]}")


def dumps(obj: Any) -> str:
    e = _Encoder()
    root = e.enc(obj)
    return json.dumps({"v": 1, "slots": e.slots, "root": root})


# id(Schema) -> (the Schema, the sha256 of its wire form). A connector's
# Schema is one long-lived frozen object a table; the entry holds it, so
# a recycled id never aliases
_SCHEMA_DIGESTS_MAX = 256
_schema_digests: Dict[int, tuple] = {}


def _schema_digest(schema) -> str:
    hit = _schema_digests.get(id(schema))
    if hit is not None and hit[0] is schema:
        return hit[1]
    import hashlib
    digest = hashlib.sha256(dumps(schema).encode()).hexdigest()
    with _registry_lock:
        if len(_schema_digests) >= _SCHEMA_DIGESTS_MAX:
            _schema_digests.clear()
        _schema_digests[id(schema)] = (schema, digest)
    return digest


def structure_text(obj: Any) -> str:
    """Canonical text of a plan subtree for a structural hash: the wire
    form with every Schema written as the digest of its own wire form,
    computed once a Schema object. A scanned table's schema carries its
    dictionary pools (customer's at SF10: 35 MB), and a key that walks
    them in every statement costs more than the statement's device
    time. Not decodable: `loads` takes `dumps`' output only."""
    e = _Encoder(digest_schemas=True)
    root = e.enc(obj)
    return json.dumps({"v": 1, "slots": e.slots, "root": root})


def loads(blob: str) -> Any:
    payload = json.loads(blob)
    if payload.get("v") != 1:
        raise ValueError("unknown fragment wire version")
    return _Decoder(payload["slots"]).dec(payload["root"])


def dumps_bytes(obj: Any, pools=None, stats: Optional[dict] = None) -> bytes:
    """Version 2: the one body of bytes a stage's every task is posted.
    `pools` (a Catalog) names the string pools every node holds; `stats`,
    if given, takes what the encoding counted (span attributes)."""
    e = _Encoder(pools=pools, buffers=[])
    root = e.enc(obj)
    head = json.dumps({"v": 2, "slots": e.slots, "root": root}).encode()
    if stats is not None:
        stats.update(poolHandles=e.pool_handles, inlinePools=e.inline_pools,
                     inlinePoolBytes=e.inline_pool_bytes)
    return b"".join([_HEAD.pack(MAGIC, len(head)), head,
                     bytes(pad(_HEAD.size + len(head)))] + e.buffers)


def is_bytes_form(blob) -> bool:
    return not isinstance(blob, str) and bytes(blob[:4]) == MAGIC


def loads_bytes(body, pools=None, stats: Optional[dict] = None) -> Any:
    """Inverse of `dumps_bytes`; arrays are read-only views of `body`."""
    view = memoryview(body)
    if len(view) < _HEAD.size:
        raise ValueError("truncated fragment")
    magic, n = _HEAD.unpack_from(view)
    start = _HEAD.size + n
    start += pad(start)
    if magic != MAGIC:
        raise ValueError("not a version-2 fragment")
    if start > len(view):
        raise ValueError("truncated fragment")
    payload = json.loads(bytes(view[_HEAD.size:_HEAD.size + n]))
    if payload.get("v") != 2:
        raise ValueError("unknown fragment wire version")
    d = _Decoder(payload["slots"], pools=pools, buffers=view[start:])
    out = d.dec(payload["root"])
    if stats is not None:
        stats.update(resolvedPools=d.resolved_pools)
    return out
