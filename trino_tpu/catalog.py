"""Catalog registry — maps catalog names to connectors.

Reference: Trino's CatalogManager / connector loading
(metadata/CatalogManager.java, server/PluginManager.java). Connectors
implement a minimal duck-typed contract for now (schema_names/table_names/
get_table returning host TableData); the split-based scan SPI for
distributed execution layers on top in planner/physical.py.
"""

from __future__ import annotations

import hashlib
import threading
from typing import Dict, Optional

from .connectors.tpch.connector import TpchConnector


class PoolMismatchError(RuntimeError):
    """A plan fragment named a string pool of this catalog by handle and
    the pool held here is not the one the sender hashed: the strings
    behind the codes would differ, so the task must not run."""


def _pool_digest(pool: tuple) -> Optional[str]:
    """sha256 over the pool's strings, each with its length; None for a
    pool that holds anything but strings."""
    try:
        text = "".join(pool).encode("utf-8", "surrogatepass")
    except TypeError:
        return None
    import numpy as np
    h = hashlib.sha256()
    h.update(np.fromiter(map(len, pool), dtype=np.int64,
                         count=len(pool)).tobytes())
    h.update(text)
    return h.hexdigest()


class Catalog:
    def __init__(self):
        self._connectors: Dict[str, object] = {}
        self._stats_cache: Dict[tuple, object] = {}
        # string pools this catalog can name on the wire: id(pool) ->
        # (the pool, (catalog, schema, table, column)), noted as tables
        # of generator connectors are handed out; the entry holds the
        # pool, so a recycled id never aliases. Digests are made on first
        # use, once a pool object
        self._pool_names: Dict[int, tuple] = {}
        self._pool_digests: Dict[int, tuple] = {}
        self._noted_schemas: Dict[int, object] = {}
        self._pool_lock = threading.Lock()
        # monotonic catalog version: bumped on every DDL/write that goes
        # through the session (CREATE/DROP/INSERT/UPDATE/DELETE/MERGE).
        # The serving layer stamps every cached plan and result page with
        # the version it observed, so a write invalidates them all
        # without enumerating which tables changed.
        self.version = 0

    def bump_version(self) -> None:
        self.version += 1
        # table contents moved: cached plan-time stats are stale too
        self._stats_cache.clear()

    def register(self, name: str, connector) -> None:
        self._connectors[name] = connector

    def connector(self, name: str):
        if name not in self._connectors:
            raise KeyError(f"catalog {name!r} not found "
                           f"(have {sorted(self._connectors)})")
        return self._connectors[name]

    def get_table(self, catalog: str, schema: str, table: str):
        if schema == "information_schema":
            return self.information_schema_table(catalog, table)
        conn = self.connector(catalog)
        data = conn.get_table(schema, table)
        if hasattr(conn, "scale_for_schema") and \
                self._noted_schemas.get(id(data.schema)) is not data.schema:
            # generator connectors: every node makes the same table from
            # the schema's scale, so a fragment may name its pools
            self._noted_schemas[id(data.schema)] = data.schema
            for f in data.schema.fields:
                if f.dictionary is not None:
                    self._pool_names[id(f.dictionary)] = (
                        f.dictionary, (catalog, schema, table, f.name))
        return data

    def _digest_of(self, pool: tuple) -> Optional[str]:
        hit = self._pool_digests.get(id(pool))
        if hit is not None and hit[0] is pool:
            return hit[1]
        with self._pool_lock:          # four tasks decode at once
            hit = self._pool_digests.get(id(pool))
            if hit is None or hit[0] is not pool:
                hit = (pool, _pool_digest(pool))
                self._pool_digests[id(pool)] = hit
        return hit[1]

    def pool_handle(self, pool: tuple) -> Optional[tuple]:
        """(catalog, schema, table, column, digest) if `pool` IS the pool
        of a table column every node's catalog holds, else None: what a
        plan fragment writes in the pool's place (server/serde.py)."""
        hit = self._pool_names.get(id(pool))
        if hit is None or hit[0] is not pool:
            return None
        digest = self._digest_of(pool)
        return None if digest is None else hit[1] + (digest,)

    def resolve_pool(self, catalog: str, schema: str, table: str,
                     column: str, digest: str) -> tuple:
        """This catalog's own pool for a handle, checked against the
        sender's digest: never a silently different string."""
        name = f"{catalog}.{schema}.{table}.{column}"
        try:
            pool = self.get_table(catalog, schema, table) \
                .schema.field(column).dictionary
        except KeyError as e:
            raise PoolMismatchError(
                f"string pool {name}: not in this node's catalog "
                f"({e})") from e
        if pool is None or self._digest_of(pool) != digest:
            raise PoolMismatchError(
                f"string pool {name}: this node's differs from the "
                f"coordinator's (digest {digest[:12]} expected)")
        return pool

    def get_table_stats(self, catalog: str, schema: str, table: str):
        """TableStats for an already-materialized table, else None —
        plan-time stats must never trigger SF1000 generation
        (spi/statistics ConnectorTableStatistics role, cached)."""
        key = (catalog, schema, table)
        if key in self._stats_cache:
            return self._stats_cache[key]
        try:
            conn = self.connector(catalog)
            if hasattr(conn, "scale_for_schema"):
                # generator connectors: only stats for materialized scales
                scale = conn.scale_for_schema(schema)
                data = conn._cache.get(scale, {}).get(table)
            else:
                data = conn.get_table(schema, table)
        except Exception:
            data = None
        if data is None:
            return None
        from .stats import compute_table_stats
        stats = compute_table_stats(data)
        self._stats_cache[key] = stats
        return stats

    def information_schema_table(self, catalog: str, table: str):
        """Synthesize information_schema.{schemata,tables,columns} from
        connector metadata (reference: the engine-provided
        information_schema connector, connector/informationschema/)."""
        conn = self.connector(catalog)
        if table == "schemata":
            names = list(conn.schema_names())
            return _strings_table("schemata",
                                  [("catalog_name", [catalog] * len(names)),
                                   ("schema_name", names)])
        if table == "tables":
            cats, schemas, tables = [], [], []
            for s in conn.schema_names():
                for t in conn.table_names(s):
                    cats.append(catalog)
                    schemas.append(s)
                    tables.append(t)
            return _strings_table("tables",
                                  [("table_catalog", cats),
                                   ("table_schema", schemas),
                                   ("table_name", tables)])
        if table == "columns":
            get_schema = getattr(conn, "get_table_schema",
                                 lambda s, t: conn.get_table(s, t).schema)
            schemas, tables, cols, types, positions = [], [], [], [], []
            for s in conn.schema_names():
                for t in conn.table_names(s):
                    table_schema = get_schema(s, t)
                    for i, f in enumerate(table_schema):
                        schemas.append(s)
                        tables.append(t)
                        cols.append(f.name)
                        types.append(str(f.dtype))
                        positions.append(i + 1)
            out = _strings_table("columns",
                                 [("table_schema", schemas),
                                  ("table_name", tables),
                                  ("column_name", cols),
                                  ("data_type", types)])
            import numpy as np
            from .batch import Field, Schema
            from .types import BIGINT
            return type(out)(
                "columns",
                Schema(out.schema.fields + (Field("ordinal_position",
                                                  BIGINT),)),
                out.columns + [np.asarray(positions, dtype=np.int64)])
        raise KeyError(f"information_schema table {table!r} not found")


def _strings_table(name: str, cols):
    """Build a TableData of VARCHAR columns from python string lists."""
    import numpy as np
    from .batch import Field, Schema
    from .connectors.tpch.datagen import TableData
    from .types import VARCHAR
    fields = []
    arrays = []
    for col_name, values in cols:
        pool = sorted(set(values))
        index = {s: i for i, s in enumerate(pool)}
        fields.append(Field(col_name, VARCHAR, dictionary=tuple(pool)))
        arrays.append(np.array([index[v] for v in values],
                               dtype=np.int32))
    return TableData(name, Schema(tuple(fields)), arrays)


def default_catalog() -> Catalog:
    cat = Catalog()
    cat.register("tpch", TpchConnector())
    from .connectors.tpcds.connector import TpcdsConnector
    cat.register("tpcds", TpcdsConnector())
    from .connectors.memory import MemoryConnector
    cat.register("memory", MemoryConnector())
    return cat
