"""JIT-compile observability: the central compile-event recorder.

Reference: the reference engine keeps ExpressionCompiler/PageProcessor
codegen warm in long-lived caches and exposes their hit rates over JMX
(sql/gen/ExpressionCompiler.java:38 with its CacheStatsMBean); operator
wall times come from OperatorStats with explicit scheduled/blocked
splits. The XLA analog of codegen is jit tracing + compilation, and
under async dispatch its cost lands wherever the first blocking fetch
happens — invisible to host wall clocks unless measured at the jit
boundary itself.

Here: every jit site routes through `recorded_jit`/`instrument`, which
detect a fresh XLA compile by watching the jitted callable's cache size
across the call. Each compile (and each cache hit) is recorded with its
site, an argument-shape fingerprint (the jaxpr-identity proxy: same
tree of shapes/dtypes + statics => same trace => same program), and the
compile duration, into:

- the process-global `RECORDER` ring (served raw at `GET /v1/jit` and
  as `system.runtime.jit_cache`),
- Prometheus families (trino_tpu_jit_compiles_total{site},
  trino_tpu_jit_cache_hits_total{site}, trino_tpu_jit_compile_seconds),
- the thread-bound ExecStats (`jit_compiles` — the executor binds its
  stats object per dispatch thread, so per-executor counts attribute
  compiles to the executor whose dispatch triggered them),
- a per-thread compile-seconds accumulator the profiled dispatch path
  reads to split operator wall into device/host/compile components,
  and beside it a per-thread count of recorded calls (`thread_calls`),
- a `compile` span of the thread's active tracer (utils/tracing.py), so
  a traced query shows each compile inside the span that paid for it.

A compile is `shape`-keyed when the site has not seen the arguments'
array shapes and dtypes before (a new capacity bucket) and `literal`-keyed
when it has and only static arguments differ: two different repairs, told
apart here. `filter_project` takes its literals as operands
(`ir.parametrise`), so what is left `literal`-keyed is a static capacity
or domain that followed the data (`join.dense_join_compacted`), a
filter/project template that differs in shape proper (a NULL or VARCHAR
literal, an IN list's length, a function parameter) and the sites that
still take whole IR as a static argument: a join residual
(`ops/join.py`, `filter_mask`) and the chunked driver's fused program.

Design constraints: recording must never change execution (a wrapper
failure falls through to the raw call), must cost ~a cache-size probe
per call on the hot path, and must stay silent inside an outer trace
(a jitted kernel calling another jitted kernel records nothing — the
outer program owns the compile).
"""

from __future__ import annotations

import hashlib
import threading
import time
from collections import OrderedDict, deque
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple


@dataclass(frozen=True)
class CompileEvent:
    site: str
    fingerprint: str
    duration_s: float       # trace+compile wall for misses, 0.0 for hits
    hit: bool               # True = the program cache already had it
    when: float             # time.time() at record
    key: str = ""           # misses: "shape" or "literal" (see above)


def _hex(parts: list) -> str:
    return f"{hash(tuple(parts)) & 0xFFFFFFFFFFFFFFFF:016x}"


def _arg_fingerprint(args, kwargs) -> Tuple[str, str]:
    """Cheap jaxpr-identity proxy, in one pass over the leaves: the
    tree of array (shape, dtype) leaves plus static leaves, hashed, and
    the hash of the array leaves alone. Two calls with the same full
    fingerprint hit the same compiled program for a given jit site; two
    that differ only in the first differ only in static arguments.
    Built on Python's tuple hash (not a cryptographic digest) because
    this runs on EVERY instrumented dispatch — the fingerprint is an
    in-process cache key, not a cross-process identity."""
    import jax
    parts, shapes = [], []
    for leaf in jax.tree_util.tree_leaves((args, kwargs)):
        shape = getattr(leaf, "shape", None)
        if shape is not None:
            part = (shape, str(getattr(leaf, "dtype", "?")))
            parts.append(part)
            shapes.append(part)
        else:
            try:
                parts.append(hash(leaf))
            except TypeError:
                parts.append(repr(leaf)[:48])
    return _hex(parts), _hex(shapes)


class CompileRecorder:
    """Thread-safe compile-event ring + per-(site, fingerprint) cache
    aggregates. One per process (module-level RECORDER): jitted programs
    are process-global, so their compile ledger is too."""

    MAX_EVENTS = 512
    MAX_ENTRIES = 2048

    def __init__(self):
        self._lock = threading.Lock()
        self.events: "deque[CompileEvent]" = deque(maxlen=self.MAX_EVENTS)
        # (site, fingerprint) -> mutable aggregate dict
        self._entries: "OrderedDict[tuple, dict]" = OrderedDict()
        self.total_compiles = 0
        self.total_hits = 0
        self.total_compile_s = 0.0
        # misses by what keyed them: [count, seconds]
        self.by_key: Dict[str, list] = {"shape": [0, 0.0],
                                        "literal": [0, 0.0]}
        # shape-canonicalization signal: every hash of array shapes and
        # dtypes ever seen per site (survives the entry LRU — the lint
        # cares about distinct shapes produced, not about what is still
        # cached, and a new literal is not a new shape)
        self._site_shapes: Dict[str, set] = {}
        # (site, fingerprint) -> off-path compile seconds, pending the
        # first query-path hit that claims the saving
        self._prewarm_pending: Dict[tuple, float] = {}
        self.total_prewarmed = 0
        self.total_prewarm_hits = 0
        self.total_saved_s = 0.0
        self._tl = threading.local()

    # -- per-thread attribution --------------------------------------------

    def bind_stats(self, stats, device=None) -> None:
        """Attribute compiles recorded on THIS thread to `stats`
        (ExecStats.jit_compiles). The executor binds its stats object at
        dispatch entry; worker task threads each bind their own.
        `device` is the label of the executor's own (`device_label`;
        None: the default device): a program is compiled once a device,
        so the fingerprints of calls recorded on this thread say which
        (`thread_device`)."""
        self._tl.stats = stats
        self._tl.device = device

    def thread_device(self) -> Optional[str]:
        """The label of the device this thread's executor is bound to."""
        return getattr(self._tl, "device", None)

    def thread_compile_seconds(self) -> float:
        """Cumulative compile seconds recorded on this thread — the
        profiled dispatch path diffs this around a dispatch to isolate
        the compile component of an operator's wall."""
        return getattr(self._tl, "compile_s", 0.0)

    def thread_calls(self) -> int:
        """Recorded calls made on this thread so far, hits and misses:
        the programs it dispatched through `recorded_jit`. A split loop
        diffs this around `ex.run(root)` (`dispatches` on the `split`
        span, server/tasks.py)."""
        return getattr(self._tl, "calls", 0)

    @contextmanager
    def site_context(self, prefix: str):
        """Prefix every site recorded on this thread inside the block —
        the spill tier wraps its partition-wise re-runs so their kernel
        compiles attribute to the spill path, not the resident one."""
        prev = getattr(self._tl, "site_prefix", None)
        self._tl.site_prefix = prefix
        try:
            yield
        finally:
            self._tl.site_prefix = prev

    @contextmanager
    def prewarm_context(self):
        """Mark every compile recorded on this thread inside the block
        as an OFF-PATH prewarm compile (exec/prewarm.py): it counts as
        prewarm_compiles_total instead of charging the thread-bound
        ExecStats, and the first later query-path hit on the same
        (site, fingerprint) claims its wall as compile seconds saved."""
        prev = getattr(self._tl, "prewarm", False)
        self._tl.prewarm = True
        try:
            yield
        finally:
            self._tl.prewarm = prev

    # -- recording ---------------------------------------------------------

    def record(self, site: str, fingerprint: str, duration_s: float,
               hit: bool, shape: Optional[str] = None) -> CompileEvent:
        """`shape` is the hash of the call's array shapes and dtypes;
        None where the caller did not compute one (a hit under a fixed
        fingerprint: nothing to learn; a miss: the fingerprint stands
        in)."""
        tl = self._tl
        tl.calls = getattr(tl, "calls", 0) + 1
        prefix = getattr(tl, "site_prefix", None)
        if prefix:
            site = f"{prefix}:{site}"
        if shape is None and not hit:
            shape = fingerprint
        from ..metrics import (COMPILE_SECONDS_SAVED, JIT_CACHE_HITS,
                               JIT_COMPILES, JIT_COMPILE_SECONDS,
                               JIT_DISTINCT_SHAPES, PREWARM_COMPILES,
                               PREWARM_HITS)
        prewarming = getattr(self._tl, "prewarm", False)
        shape_count = None
        saved_s = None
        prewarm_hit = False
        with self._lock:
            shapes = self._site_shapes.setdefault(site, set())
            kind = "" if hit else \
                ("literal" if shape in shapes else "shape")
            ev = CompileEvent(site, fingerprint, duration_s if not hit
                              else 0.0, hit, time.time(), kind)
            self.events.append(ev)
            key = (site, fingerprint)
            e = self._entries.get(key)
            if e is None:
                if len(self._entries) >= self.MAX_ENTRIES:
                    self._entries.popitem(last=False)
                e = self._entries[key] = {
                    "site": site, "fingerprint": fingerprint,
                    "compiles": 0, "hits": 0, "compile_ms": 0.0,
                    "last_compile_ms": 0.0, "last_used": 0.0,
                    "prewarmed": False, "prewarm_hits": 0,
                    "shape": shape or "", "key": ""}
            if shape is not None and shape not in shapes:
                shapes.add(shape)
                shape_count = len(shapes)
            e["last_used"] = ev.when
            if hit:
                e["hits"] += 1
                self.total_hits += 1
                if e.get("prewarmed") and not prewarming:
                    prewarm_hit = True
                    e["prewarm_hits"] += 1
                    self.total_prewarm_hits += 1
                    # the first query-path hit claims the avoided
                    # compile wall; later hits were free anyway
                    saved_s = self._prewarm_pending.pop(key, None)
                    if saved_s is not None:
                        self.total_saved_s += saved_s
            else:
                e["compiles"] += 1
                e["compile_ms"] += duration_s * 1000
                e["last_compile_ms"] = duration_s * 1000
                e["key"] = kind
                self.total_compiles += 1
                self.total_compile_s += duration_s
                self.by_key[kind][0] += 1
                self.by_key[kind][1] += duration_s
                if prewarming:
                    e["prewarmed"] = True
                    self._prewarm_pending[key] = duration_s
                    self.total_prewarmed += 1
        if shape_count is not None:
            JIT_DISTINCT_SHAPES.set(shape_count, site=site)
        if hit:
            JIT_CACHE_HITS.inc(site=site)
            if prewarm_hit:
                PREWARM_HITS.inc()
            if saved_s is not None:
                COMPILE_SECONDS_SAVED.inc(saved_s)
        else:
            JIT_COMPILES.inc(site=site)
            JIT_COMPILE_SECONDS.observe(duration_s)
            if prewarming:
                PREWARM_COMPILES.inc()
            # per-thread attribution: the executor whose dispatch thread
            # triggered the compile owns it
            self._tl.compile_s = getattr(self._tl, "compile_s", 0.0) \
                + duration_s
            stats = getattr(self._tl, "stats", None)
            if stats is not None:
                stats.jit_compiles += 1
        return ev

    # -- read surface ------------------------------------------------------

    def snapshot(self) -> List[dict]:
        """Per-(site, fingerprint) aggregates, most-recently-used last —
        the /v1/jit and system.runtime.jit_cache payload."""
        with self._lock:
            return [dict(e) for e in self._entries.values()]

    def totals(self) -> dict:
        with self._lock:
            return {"compiles": self.total_compiles,
                    "hits": self.total_hits,
                    "compileSeconds": round(self.total_compile_s, 6),
                    "shapeKeyedCompiles": self.by_key["shape"][0],
                    "shapeKeyedCompileSeconds": round(
                        self.by_key["shape"][1], 6),
                    "literalKeyedCompiles": self.by_key["literal"][0],
                    "literalKeyedCompileSeconds": round(
                        self.by_key["literal"][1], 6),
                    "entries": len(self._entries),
                    "prewarmedPrograms": self.total_prewarmed,
                    "prewarmHits": self.total_prewarm_hits,
                    "compileSecondsSaved": round(self.total_saved_s, 6)}

    def site_shape_counts(self) -> Dict[str, int]:
        """Distinct array-shape hashes ever recorded per site — what
        the shape-canonicalization lint asserts ceilings over (a new
        literal in a static argument is not a new shape)."""
        with self._lock:
            return {s: len(fps) for s, fps in self._site_shapes.items()}

    def clear(self) -> None:
        from ..metrics import JIT_DISTINCT_SHAPES
        with self._lock:
            self.events.clear()
            self._entries.clear()
            self.total_compiles = 0
            self.total_hits = 0
            self.total_compile_s = 0.0
            self.by_key = {"shape": [0, 0.0], "literal": [0, 0.0]}
            sites = list(self._site_shapes)
            self._site_shapes.clear()
            self._prewarm_pending.clear()
            self.total_prewarmed = 0
            self.total_prewarm_hits = 0
            self.total_saved_s = 0.0
        for s in sites:
            JIT_DISTINCT_SHAPES.set(0, site=s)


RECORDER = CompileRecorder()


def _trace_clean() -> bool:
    try:
        import jax.core
        return jax.core.trace_state_clean()
    except Exception:        # noqa: BLE001 — recording is best-effort
        return True


def instrument(jitted: Callable, site: str,
               fingerprint: Optional[str] = None,
               recorder: Optional[CompileRecorder] = None) -> Callable:
    """Wrap an already-jitted callable with compile-event recording.
    Detection is a cache-size probe around the call; a fixed
    `fingerprint` (e.g. the fused pipeline's plan hash) skips the
    arg-shape hash. Calls made inside an outer trace bypass recording
    entirely (the outer program owns the compile), as does any probe
    failure — the wrapper can never change execution."""
    rec = recorder or RECORDER
    probe = getattr(jitted, "_cache_size", None)

    def wrapped(*args, **kwargs):
        if probe is None or not _trace_clean():
            return jitted(*args, **kwargs)
        try:
            before = probe()
        except Exception:        # noqa: BLE001 — probe is best-effort
            return jitted(*args, **kwargs)
        t0 = time.monotonic()
        out = jitted(*args, **kwargs)
        dt = time.monotonic() - t0
        try:
            hit = probe() == before
            if fingerprint is None:
                fp, shape = _arg_fingerprint(args, kwargs)
            else:
                # a fixed fingerprint skips the hash on the hot path; a
                # miss still learns what keyed it
                fp = fingerprint
                shape = None if hit else _arg_fingerprint(args, kwargs)[1]
            dev = rec.thread_device()
            if dev is not None:
                # one compile a device: a shape met on a second chip is
                # a new shape there, not a new literal
                fp = f"{fp}@{dev}"
                shape = shape and f"{shape}@{dev}"
            ev = rec.record(site, fp, dt, hit, shape)
            if not hit:
                # the span lands inside whatever span of this thread's
                # tracer paid for the compile (split, pin-builds, ...)
                from ..utils import tracing
                tracing.current().record(
                    "compile", t0, t0 + dt, site=ev.site,
                    fingerprint=fp, key=ev.key)
        except Exception:        # noqa: BLE001 — never break the call
            pass
        return out

    wrapped.__name__ = f"recorded[{site}]"
    wrapped.__wrapped__ = jitted
    return wrapped


def recorded_jit(site: Optional[str] = None, static_argnums=None,
                 static_argnames=None, **jit_kwargs) -> Callable:
    """Decorator: jax.jit + compile recording in one step — the drop-in
    replacement for `@functools.partial(jax.jit, static_argnums=...)`
    at every module-level jit site."""
    def deco(fn):
        import jax
        kw = dict(jit_kwargs)
        if static_argnums is not None:
            kw["static_argnums"] = static_argnums
        if static_argnames is not None:
            kw["static_argnames"] = static_argnames
        s = site or f"{fn.__module__.rsplit('.', 1)[-1]}.{fn.__name__}"
        return instrument(jax.jit(fn, **kw), s)
    return deco


def device_label(device) -> Optional[str]:
    """`<platform>:<id>` of a device (`worker-task.device`, the compile
    recorder's fingerprints); None for None."""
    return None if device is None else f"{device.platform}:{device.id}"


def device_memory_stats(device=None) -> dict:
    """Live device/HBM stats of `device` (a worker's own chip), of this
    process's first accelerator where none is given, in the /v1/status
    heartbeat shape. TPU/GPU backends report allocator stats; CPU
    returns platform-only (the fields read 0)."""
    try:
        import jax
        d = device if device is not None else jax.local_devices()[0]
        stats = None
        if hasattr(d, "memory_stats"):
            try:
                stats = d.memory_stats()
            except Exception:    # noqa: BLE001 — backend-dependent
                stats = None
        out = {"platform": d.platform, "deviceCount": jax.local_device_count()}
        if device is not None:
            out["device"] = device_label(device)
        if stats:
            out["bytesInUse"] = int(stats.get("bytes_in_use", 0))
            out["bytesLimit"] = int(stats.get("bytes_limit", 0))
            out["peakBytesInUse"] = int(stats.get("peak_bytes_in_use", 0))
        else:
            out["bytesInUse"] = 0
            out["bytesLimit"] = 0
            out["peakBytesInUse"] = 0
        return out
    except Exception:            # noqa: BLE001 — stats are best-effort
        return {}
