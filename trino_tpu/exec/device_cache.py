"""What stays on the device across statements: the one resident set
(scanned table columns, fact tables, pinned builds under one budget),
and the fact-column cache with range-compressed dtypes.

Reference role: Trino's memory-pinned page cache / the Hive split cache
keep hot table pages in RAM near the workers; the columnar formats
(ORC/Parquet) store integers bit-packed so the hot set fits. On TPU the
scarce tier is HBM and the host link is the bottleneck (a PCIe host
link is dwarfed by HBM bandwidth), so the same two ideas
move on-device: keep the fact table's scanned columns resident in HBM,
and store them in the NARROWEST integer dtype their value range allows
(connector stats or a one-time host min/max pass), widening to the
engine's int64 lanes chunk-by-chunk inside the jitted pipeline.

A 600M-row TPC-H SF100 lineitem q5 projection drops from 19.2 GB
(int64) to 7.8 GB (int32 keys/prices, int8 discount) — it fits a single
v5e chip's HBM, so steady-state queries never touch the host link at
all; the chunked driver (exec/chunked.py) then slices chunks directly
from the resident arrays.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import List, Optional

import numpy as np


# budget of a backend that reports no allocator limit (the CPU): finite,
# so a long-lived process stays bounded there too
HOST_RESIDENT_BYTES = 8 << 30


def default_resident_bytes(device=None) -> int:
    """Half of `device`'s own (the process's first device's, where none
    is given) `memory_stats()["bytes_limit"]`: the
    other half is a statement's (its projected columns, join LUTs, sort
    operands — TPC-H q18 at SF10 peaked 8.6 GB above its tables on a
    16 GB v5e, q3 2.2 GB)."""
    from .profiler import device_memory_stats
    limit = device_memory_stats(device).get("bytesLimit") or 0
    return limit // 2 if limit else HOST_RESIDENT_BYTES


class ResidentSet:
    """Everything an executor keeps on the device from one statement to
    the next — scanned table columns, the chunked driver's narrowed
    fact tables and its pinned builds — under ONE byte budget and one
    LRU order. Keys say what an entry IS (table and column, table
    version by the identity of the connector's TableData held in the
    value), never which statement asked for it.

    `max_bytes` None = `default_resident_bytes(device)`, resolved at
    first use (asking the device initialises the backend); `device` is
    the executor's own chip (`Executor.put_device`), None the process's
    first. An entry larger
    than the whole budget is not kept: accounted bytes never exceed it.
    Eviction drops the set's references; the buffers go back to the
    allocator as soon as no running statement holds them either.
    """

    def __init__(self, max_bytes: Optional[int] = None, device=None):
        self._max_bytes = max_bytes
        self.device = device
        # key -> (value, nbytes, on_evict)
        self._entries: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._total = 0
        # cumulative, for the `evict` span of whoever caused them
        self.evicted_entries = 0
        self.evicted_bytes = 0

    @property
    def max_bytes(self) -> int:
        if self._max_bytes is None:
            self._max_bytes = default_resident_bytes(self.device)
        return self._max_bytes

    @max_bytes.setter
    def max_bytes(self, value: Optional[int]) -> None:
        self._max_bytes = value
        if value is not None:
            self._evict_to(value)

    def __len__(self) -> int:
        return len(self._entries)

    def keys(self) -> list:
        return list(self._entries)

    def total_bytes(self) -> int:
        return self._total

    def get(self, key):
        hit = self._entries.get(key)
        if hit is None:
            return None
        self._entries.move_to_end(key)
        return hit[0]

    def put(self, key, value, nbytes: int, on_evict=None) -> bool:
        """Keep `value`, evicting least-recently-used entries to fit.
        False (and nothing kept under `key`) when it exceeds the whole
        budget."""
        self.pop(key)
        if nbytes > self.max_bytes:
            return False
        self._evict_to(self.max_bytes - nbytes)
        self._entries[key] = (value, nbytes, on_evict)
        self._total += nbytes
        return True

    def pop(self, key) -> int:
        """Drop one entry (not an eviction); returns its bytes."""
        hit = self._entries.pop(key, None)
        if hit is None:
            return 0
        self._total -= hit[1]
        if hit[2] is not None:
            hit[2]()
        return hit[1]

    def _evict_to(self, target: int) -> None:
        while self._entries and self._total > target:
            self.evicted_bytes += self.pop(next(iter(self._entries)))
            self.evicted_entries += 1

    def evict_kind(self, kind: str, target_bytes: int) -> int:
        """Evict `kind` entries (a key's first element), eldest first,
        until `target_bytes` are released; returns the bytes released."""
        freed = 0
        for key in [k for k in self._entries if k[0] == kind]:
            if freed >= target_bytes:
                break
            freed += self.pop(key)
            self.evicted_entries += 1
        self.evicted_bytes += freed
        return freed

    def clear(self) -> int:
        """Drop everything (DML invalidation); returns bytes released."""
        freed = self._total
        for key in list(self._entries):
            self.pop(key)
        return freed


class NarrowColumn:
    """One device-resident column: narrow-dtype data + optional validity."""

    __slots__ = ("data", "valid", "wide_dtype")

    def __init__(self, data, valid, wide_dtype):
        self.data = data          # jax.Array, narrowest safe dtype
        self.valid = valid        # jax.Array bool or None (all valid)
        self.wide_dtype = wide_dtype  # dtype the engine's lanes expect

    @property
    def nbytes(self) -> int:
        n = self.data.size * self.data.dtype.itemsize
        if self.valid is not None:
            n += self.valid.size
        return n


_INT_STEPS = (np.int8, np.int16, np.int32, np.int64)

# ---------------------------------------------------------------------------
# transfer encodings: written for a host link that compressed what it
# carried; whether they pay on a direct link is not measured. Sorted key
# columns delta-encode (mostly tiny repeated values); other multi-byte
# integers split into byte PLANES so the near-constant high bytes sit
# together. Decode happens ON DEVICE right after the put; steady state
# sees ordinary narrow columns.
# ---------------------------------------------------------------------------

def encode_transfer(narrow: np.ndarray):
    """-> (enc, payload ndarray, meta dict). enc: raw | delta8 | planes."""
    if narrow.dtype.itemsize == 1 or \
            not np.issubdtype(narrow.dtype, np.integer) or \
            narrow.size < 2:
        return "raw", narrow, {}
    d = np.diff(narrow)
    if d.size and int(d.min()) >= -128 and int(d.max()) <= 127:
        return "delta8", d.astype(np.int8), {
            "base": int(narrow[0]), "dtype": str(narrow.dtype)}
    k = narrow.dtype.itemsize
    planes = np.ascontiguousarray(
        narrow.view(np.uint8).reshape(-1, k).T)
    return "planes", planes, {"dtype": str(narrow.dtype)}


def decode_transfer(enc: str, payload, meta: dict):
    """Device-side decode (payload already device-resident)."""
    import jax
    import jax.numpy as jnp
    if enc == "raw":
        return payload
    dt = jnp.dtype(meta["dtype"])
    if enc == "delta8":
        base = meta["base"]
        acc = jnp.int64 if dt.itemsize > 4 else jnp.int32

        @jax.jit
        def _dec(d):
            cs = jnp.cumsum(d.astype(acc))
            full = jnp.concatenate(
                [jnp.zeros(1, acc), cs]) + jnp.asarray(base, acc)
            return full.astype(dt)
        return _dec(payload)

    @jax.jit
    def _dec_planes(p):
        u = jnp.uint64 if dt.itemsize > 4 else jnp.uint32
        word = p[0].astype(u)
        for j in range(1, p.shape[0]):
            word = word | (p[j].astype(u) << (8 * j))
        return jax.lax.bitcast_convert_type(
            word.astype(jnp.dtype(f"uint{dt.itemsize * 8}")), dt)
    return _dec_planes(payload)


# TRINO_TPU_CHUNK_PROFILE=1: per-phase walls to stderr (read at call
# time so the toggle works however late it is set); shared by the
# chunked driver and the ingest path
def profile_enabled() -> bool:
    import os
    return bool(os.environ.get("TRINO_TPU_CHUNK_PROFILE"))


def prof(msg: str) -> None:
    if profile_enabled():
        import sys
        import time
        print(f"[chunk {time.monotonic():.3f}] {msg}", file=sys.stderr,
              flush=True)


def _narrow_dtype(arr: np.ndarray, valid: Optional[np.ndarray]):
    """Smallest signed integer dtype holding the column's valid values."""
    if not np.issubdtype(arr.dtype, np.integer):
        return arr.dtype                       # floats/bools ship as-is
    if valid is not None:
        vals = arr[valid]
        if len(vals) == 0:
            return np.int8
        lo, hi = int(vals.min()), int(vals.max())
    elif len(arr) == 0:
        return np.int8
    else:
        lo, hi = int(arr.min()), int(arr.max())
    for dt in _INT_STEPS:
        info = np.iinfo(dt)
        if info.min <= lo and hi <= info.max:
            return dt
    return np.int64


class FactTableCache:
    """Device-resident narrowed fact tables of the chunked driver:
    entries of the executor's ResidentSet (one budget, one LRU order
    with the scanned columns), of kind "fact".

    Keys are (catalog, schema, table, column_indices); every DML drops
    the whole set, so a mutated memory-connector table never aliases a
    stale resident copy.
    """

    KIND = "fact"

    def __init__(self, resident: Optional[ResidentSet] = None):
        self.resident = resident if resident is not None else ResidentSet()

    @property
    def max_bytes(self) -> int:
        return self.resident.max_bytes

    def get(self, key) -> Optional[List[NarrowColumn]]:
        return self.resident.get((self.KIND,) + tuple(key))

    def invalidate(self) -> int:
        """Drop every fact table; returns bytes released."""
        return sum(self.resident.pop(k) for k in self.resident.keys()
                   if k[0] == self.KIND)

    def estimate_bytes(self, data, column_indices) -> int:
        """Cheap upper estimate WITHOUT the min/max pass: assumes int32
        narrowing for int64 (the common case for keys/prices) and adds
        validity bytes. Used to early-reject tables that cannot fit."""
        n = data.num_rows
        total = 0
        for i in column_indices:
            arr = np.asarray(data.columns[i])
            itemsize = min(arr.dtype.itemsize, 4) \
                if np.issubdtype(arr.dtype, np.integer) else \
                arr.dtype.itemsize
            total += n * itemsize
            if data.valids is not None and data.valids[i] is not None:
                total += n
        return total

    def _narrow_disk_dir(self, key) -> str:
        import hashlib
        import os as _os
        from ..connectors.diskcache import cache_root
        h = hashlib.sha256(repr(key).encode()).hexdigest()[:24]
        return _os.path.join(cache_root(), f"narrow_{h}")

    @staticmethod
    def _source_fingerprint(data, column_indices) -> str:
        """Cheap content fingerprint of the SOURCE columns: row count +
        per-column dtype + head/tail samples. Catches regenerated tables
        (same name, new data) without reading the full source."""
        import hashlib
        h = hashlib.sha256(str(data.num_rows).encode())
        for i in column_indices:
            arr = np.asarray(data.columns[i])
            h.update(str(arr.dtype).encode())
            h.update(np.ascontiguousarray(arr[:1024]).tobytes())
            h.update(np.ascontiguousarray(arr[-1024:]).tobytes())
        return h.hexdigest()

    def _load_narrow_disk(self, key, data, column_indices):
        """mmap previously-narrowed columns in their TRANSFER ENCODING
        (the astype + min/max + encode host passes over the full-width
        source cost ~45 s at SF100; the encoded form ships straight from
        the mmap)."""
        import json as _json
        import os as _os
        d = self._narrow_disk_dir(key)
        meta_p = _os.path.join(d, "meta.json")
        if not _os.path.isfile(meta_p):
            return None
        try:
            with open(meta_p) as f:
                meta = _json.load(f)
            if meta.get("v") != 2 or meta.get("fingerprint") != \
                    self._source_fingerprint(data, column_indices):
                return None           # format or table changed
            out = []
            for j, cm in enumerate(meta["cols"]):
                payload = np.load(_os.path.join(d, f"c{j}.npy"),
                                  mmap_mode="r")
                valid = None
                vp = _os.path.join(d, f"v{j}.npy")
                if _os.path.isfile(vp):
                    valid = np.load(vp, mmap_mode="r")
                out.append((cm, payload, valid))
            return out
        except Exception:     # noqa: BLE001 — corrupt cache = cold start
            return None

    def _save_narrow_disk(self, key, encoded, fingerprint) -> None:
        import json as _json
        import os as _os
        d = self._narrow_disk_dir(key)
        tmp = d + f".tmp{_os.getpid()}"
        try:
            _os.makedirs(tmp, exist_ok=True)
            cols = []
            for j, (cm, payload, valid) in enumerate(encoded):
                np.save(_os.path.join(tmp, f"c{j}.npy"), payload)
                if valid is not None:
                    np.save(_os.path.join(tmp, f"v{j}.npy"), valid)
                cols.append(cm)
            with open(_os.path.join(tmp, "meta.json"), "w") as f:
                _json.dump({"v": 2, "cols": cols,
                            "fingerprint": fingerprint}, f)
            if _os.path.isdir(d):     # os.replace cannot overwrite a
                import shutil          # non-empty directory
                shutil.rmtree(d, ignore_errors=True)
            _os.replace(tmp, d)
        except Exception:     # noqa: BLE001 — cache write is best-effort
            import shutil
            shutil.rmtree(tmp, ignore_errors=True)

    def load(self, key, data, column_indices, persist_ok=False) -> \
            Optional[List[NarrowColumn]]:
        """Narrow + ship `column_indices` of `data` to device, evicting
        LRU entries to fit. None if the table can't fit the budget.
        With persist_ok (deterministic catalogs only) the narrowed host
        arrays also cache on disk, so later processes mmap them straight
        to the device with no host passes."""
        import jax

        import os as _os
        import sys as _sys
        import time as _time
        from ..metrics import DEVICE_CACHE_HITS, DEVICE_CACHE_MISSES
        prof_on = profile_enabled()
        hit = self.get(key)
        if hit is not None:
            DEVICE_CACHE_HITS.inc()
            return hit
        DEVICE_CACHE_MISSES.inc()
        disk = self._load_narrow_disk(key, data, column_indices) \
            if persist_ok else None
        cols: List[NarrowColumn] = []
        total = 0
        to_persist = []
        for j, i in enumerate(column_indices):
            t0 = _time.monotonic()
            if disk is not None:
                cm, payload, valid_np = disk[j]
                enc, wide_dt = cm["enc"], np.dtype(cm["wide"])
                narrow_nbytes = data.num_rows * \
                    np.dtype(cm.get("dtype", "int8")).itemsize \
                    if enc != "raw" else payload.nbytes
            else:
                arr = np.asarray(data.columns[i])
                wide_dt = arr.dtype
                valid_np = None
                if data.valids is not None and data.valids[i] is not None:
                    valid_np = np.asarray(data.valids[i])
                dt = _narrow_dtype(arr, valid_np)
                narrow = arr if arr.dtype == dt else arr.astype(dt)
                if valid_np is not None and narrow is not arr:
                    # invalid slots may hold out-of-range garbage: zero
                    # them so the narrowed cast is well-defined
                    narrow = np.where(valid_np, narrow, np.zeros((), dt))
                enc, payload, em = encode_transfer(narrow)
                cm = dict(em, enc=enc, wide=str(wide_dt),
                          dtype=str(narrow.dtype))
                narrow_nbytes = narrow.nbytes
            total += narrow_nbytes + \
                (data.num_rows if valid_np is not None else 0)
            if total > self.max_bytes:
                return None
            t1 = _time.monotonic()
            dev_payload = jax.device_put(np.ascontiguousarray(payload),
                                         self.resident.device)
            d = decode_transfer(enc, dev_payload, cm)
            dv = None if valid_np is None else \
                jax.device_put(np.ascontiguousarray(valid_np),
                               self.resident.device)
            if prof_on:
                jax.block_until_ready(d)
                print(f"[ingest] col {i}: {payload.nbytes/1e6:.0f}MB "
                      f"enc={enc} host {t1-t0:.1f}s put+decode "
                      f"{_time.monotonic()-t1:.1f}s "
                      f"disk={disk is not None}",
                      file=_sys.stderr, flush=True)
            cols.append(NarrowColumn(d, dv, wide_dt))
            if persist_ok and disk is None:
                to_persist.append((cm, payload, valid_np))
        if to_persist:
            self._save_narrow_disk(key, to_persist,
                                   self._source_fingerprint(
                                       data, column_indices))
        self.resident.put((self.KIND,) + tuple(key), cols, total)
        return cols
