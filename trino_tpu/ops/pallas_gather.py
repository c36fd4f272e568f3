"""Pallas TPU tiled-gather kernel — the dense-join probe as a native kernel.

XLA's gather issued ~8-15 ns per gathered element regardless of table
size on the earlier v5e rig (not re-measured), and a probe site pays
that once PER PAYLOAD COLUMN.  This kernel restructures the probe around
what the hardware is actually good at — (8,128)-aligned VMEM tiles and
per-lane `take_along_axis` (the only gather form Mosaic lowers natively)
— and fuses the per-row index arithmetic (windowed-LUT offset, validity
mask, miss sentinel) with a MULTI-TABLE gather so each probe index is
decomposed once and every payload plane rides the same row/lane split.

Two kernel modes, one contract (`out[t][i] = tables[t][idx[i]]` for
`0 <= idx[i] < W`, `fill[t]` otherwise — bit-exact vs `jnp.take` on the
shared domain):

- **scan mode** (`gather_columns`): the table streams through VMEM in
  SLAB-row slabs on a second grid dimension; each probe tile tests its
  indices against every slab row and selects via a lane gather.  Per
  element the cost is ~W/(8*128) VPU ops, so it beats the XLA gather
  only for SMALL tables (dimension LUTs, validation words); above
  SCAN_MAX_ELEMS the wrapper falls back to `jnp.take` automatically.
- **windowed mode** (`gather_word_windowed`): for NEAR-SORTED probe keys
  (the chunked driver's fact scans — l_orderkey is ascending), each
  (8,128)-tile picks ONE WIN-sized window of the LUT via a
  scalar-prefetched block index (PrefetchScalarGridSpec: the per-tile
  minimum key, computed in XLA, selects the DMA'd block), then resolves
  all 1024 indices against that window in WIN_ROWS lane-gather rounds.
  Per element that is ~WIN/(8*128) VPU ops INDEPENDENT of table size —
  the sub-4 ns/element regime the round-5 break-even asks for.  Indices
  escaping their tile's window come back as misses and are COUNTED; the
  caller must treat a nonzero escape total exactly like the windowed-LUT
  escape flag it already owns (exec/chunked.py reruns the plain
  program), so correctness never rests on the near-sorted guess.

int64/float64 tables ride as two int32 bit-planes (Mosaic has no 64-bit
lanes; same trick as ops/pallas_agg.py); float32 bitcasts; narrow ints
and bools widen to one int32 plane.  Everything reassembles bit-exactly.

Reference role: Trino's compiled probe specialization — runtime bytecode
generation fusing the hash lookup with per-channel page building
(sql/gen/JoinProbeCompiler, PageJoiner.java:138) — re-expressed as a
hand-written TPU kernel, per the co-processing literature's finding that
probe-side gather/materialization is where accelerator joins win or
lose (PAPERS.md: Revisiting Co-Processing for Hash Joins; Global Hash
Tables Strike Back!).

Session wiring: `enable_pallas_gather` = auto (on for TPU backends) |
true (TPU: compiled; CPU: interpret mode — tier-1 runs the kernel logic
through the Pallas interpreter) | false.  Every call site keeps the
`jnp.take` path and falls back to it whenever the mode is off or the
shape is outside the kernel's win region.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB = 8                     # sublanes per probe tile
LANES = 128                 # lanes per probe tile
_LANE_BITS = 7              # row/lane splits are shifts and masks in-kernel
TILE = SUB * LANES          # probe indices resolved per grid step
SLAB_ROWS = 16              # scan mode: LUT rows (of LANES) per slab
SLAB = SLAB_ROWS * LANES
WIN_ROWS = 64               # windowed mode: rows per per-tile window
WIN = WIN_ROWS * LANES      # 8192 LUT entries per tile window
MAX_PLANES = 12             # int32 planes per pallas_call (VMEM budget)
# scan mode's per-element cost is ~W/(SUB*LANES) VPU ops; beyond this the
# XLA gather's flat ~8-15 ns/element wins (v5e break-even measurement)
SCAN_MAX_ELEMS = 1 << 16
# windowed indices are 32-bit in-kernel
MAX_WINDOWED_ELEMS = (1 << 31) - 1


def resolve_mode(setting) -> str:
    """Session-property value -> kernel mode: 'device' (compiled TPU
    kernel), 'interpret' (Pallas interpreter — the CPU/tier-1 path), or
    'off' (every site uses its jnp.take fallback)."""
    s = str(setting).lower()
    on_tpu = jax.default_backend() == "tpu"
    if s in ("true", "1"):
        return "device" if on_tpu else "interpret"
    if s == "auto":
        return "device" if on_tpu else "off"
    return "off"


# --------------------------------------------------------------------------
# int32 plane split / reassembly (bit-exact for every engine lane dtype)
# --------------------------------------------------------------------------

def plane_count(dtype) -> int:
    return 2 if jnp.dtype(dtype).itemsize == 8 else 1


def supports_tables(tables) -> bool:
    """Can every table ride int32 planes? Integer, bool and float32
    lanes can. float64 cannot on the chip: its split into two int32
    planes is a 64-bit bitcast the TPU compiler refuses ("While rewriting
    computation to not contain X64 element types ... bitcast-convert"),
    so DOUBLE tables take the jnp.take path by this gate."""
    for t in tables:
        dt = jnp.dtype(t.dtype)
        if not (jnp.issubdtype(dt, jnp.integer) or
                dt == jnp.dtype(jnp.float32) or dt == jnp.bool_):
            return False
        if dt.itemsize > 8:
            return False
    return True


def _split_planes(t: jax.Array) -> List[jax.Array]:
    """Table -> little-endian int32 planes ([lo, hi] for 8-byte lanes)."""
    dt = jnp.dtype(t.dtype)
    if dt.itemsize == 8:
        pair = jax.lax.bitcast_convert_type(t, jnp.int32)   # [..., 2]
        return [pair[..., 0], pair[..., 1]]
    if dt == jnp.dtype(jnp.float32):
        return [jax.lax.bitcast_convert_type(t, jnp.int32)]
    return [t.astype(jnp.int32)]


def _join_planes(planes: Sequence[jax.Array], dtype) -> jax.Array:
    """Inverse of _split_planes (bit-exact; narrow ints wrap like an
    ordinary astype round trip, which is the identity on their range)."""
    dt = jnp.dtype(dtype)
    if dt.itemsize == 8:
        pair = jnp.stack([planes[0], planes[1]], axis=-1)
        return jax.lax.bitcast_convert_type(pair, dt)
    if dt == jnp.dtype(jnp.float32):
        return jax.lax.bitcast_convert_type(planes[0], dt)
    return planes[0].astype(dt)


def _fill_planes(fill, dtype) -> Tuple[int, ...]:
    """Static per-plane int32 fill words for a table-dtype fill value."""
    arr = np.zeros(1, dtype=jnp.dtype(dtype).name)
    arr[0] = fill
    if arr.dtype.itemsize == 8:
        lo, hi = arr.view(np.int32)
        return (int(lo), int(hi))
    if arr.dtype == np.float32:
        return (int(arr.view(np.int32)[0]),)
    # narrow ints extend like the _split_planes astype, then wrap to the
    # int32 two's-complement range
    v = int(arr.astype(np.int64)[0])
    return (((v + (1 << 31)) % (1 << 32)) - (1 << 31),)


# --------------------------------------------------------------------------
# scan-mode kernel: LUT slabs stream on grid dim 1, output revisited
# --------------------------------------------------------------------------

def _scan_kernel(n_planes: int, fills: tuple):
    def kernel(idx_ref, planes_ref, out_ref):
        s = pl.program_id(1)
        local = idx_ref[...]                             # [SUB, LANES]
        row = jnp.where(local >= 0, local >> _LANE_BITS, -1)
        lane = jnp.where(local >= 0, local & (LANES - 1), 0)
        accs = [jnp.where(s == 0,
                          jnp.full((SUB, LANES), fills[p], jnp.int32),
                          out_ref[p]) for p in range(n_planes)]
        base = s * SLAB_ROWS
        for r in range(SLAB_ROWS):
            hit = row == base + r
            for p in range(n_planes):
                src = planes_ref[p, r, :]                # [LANES]
                g = jnp.take_along_axis(
                    jnp.broadcast_to(src[None, :], (SUB, LANES)), lane,
                    axis=1)
                accs[p] = jnp.where(hit, g, accs[p])
        for p in range(n_planes):
            out_ref[p] = accs[p]
    return kernel


def _scan_gather_planes(idx32: jax.Array, planes: jax.Array,
                        fills: tuple, interpret: bool) -> jax.Array:
    """idx32 [n_pad] int32 (pad/miss = -1), planes [P, W_pad] int32 ->
    gathered [P, n_pad] int32."""
    P, W = planes.shape
    n = idx32.shape[0]
    nb, n_slabs = n // TILE, W // SLAB
    # traced with 64-bit off (kernel body AND index maps): the package
    # enables jax_enable_x64 and Mosaic has no 64-bit lanes
    with jax.enable_x64(False):
        out = pl.pallas_call(
            _scan_kernel(P, fills),
            grid=(nb, n_slabs),
            in_specs=[
                pl.BlockSpec((SUB, LANES), lambda i, s: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((P, SLAB_ROWS, LANES), lambda i, s: (0, s, 0),
                             memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((P, SUB, LANES), lambda i, s: (0, i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((P, nb * SUB, LANES),
                                           jnp.int32),
            interpret=interpret,
        )(idx32.reshape(nb * SUB, LANES),
          planes.reshape(P, W // LANES, LANES))
    return out.reshape(P, n)


# --------------------------------------------------------------------------
# windowed-mode kernel: per-tile window block via scalar prefetch
# --------------------------------------------------------------------------

def _window_kernel(n_planes: int, fills: tuple):
    """Each tile resolves against TWO adjacent WIN blocks (its minimum
    index's aligned window plus the next), so alignment never causes an
    escape — only a tile whose true key span exceeds WIN does."""
    def kernel(base_ref, idx_ref, lo_win_ref, hi_win_ref, out_ref,
               esc_ref):
        i = pl.program_id(0)
        local = idx_ref[...]
        base = base_ref[i] * WIN               # lo window element offset
        rel = jnp.where(local >= 0, local - base, -1)
        in_win = (rel >= 0) & (rel < 2 * WIN)
        row = jnp.where(in_win, rel >> _LANE_BITS, -1)
        lane = jnp.where(in_win, rel & (LANES - 1), 0)

        @pl.when(i == 0)
        def _():
            esc_ref[...] = jnp.zeros((SUB, LANES), jnp.int32)

        esc_ref[...] += ((local >= 0) & ~in_win).astype(jnp.int32)
        accs = [jnp.full((SUB, LANES), fills[p], jnp.int32)
                for p in range(n_planes)]
        for r in range(2 * WIN_ROWS):
            hit = row == r
            win_ref = lo_win_ref if r < WIN_ROWS else hi_win_ref
            for p in range(n_planes):
                src = win_ref[p, r % WIN_ROWS, :]
                g = jnp.take_along_axis(
                    jnp.broadcast_to(src[None, :], (SUB, LANES)), lane,
                    axis=1)
                accs[p] = jnp.where(hit, g, accs[p])
        for p in range(n_planes):
            out_ref[p] = accs[p]
    return kernel


def _window_gather_planes(idx32: jax.Array, base_blocks: jax.Array,
                          planes: jax.Array, fills: tuple,
                          interpret: bool):
    """idx32 [n_pad] int32 (miss = -1), base_blocks [nb] int32 (per-tile
    WIN-block index, <= n_blocks - 2), planes [P, W_pad] int32 ->
    ([P, n_pad] int32, total escape count int32)."""
    P, W = planes.shape
    n = idx32.shape[0]
    nb = n // TILE
    reshaped = planes.reshape(P, W // LANES, LANES)
    # 64-bit off for the kernel body and the index maps (see the scan
    # kernel). Escapes accumulate per lane position in ONE (8, 128) VMEM
    # tile every grid step revisits, summed in XLA afterwards: per-tile
    # (1, 1) SMEM blocks of an (nb, 1) array are refused by the TPU
    # lowering (neither (8, 128)-divisible nor the whole array), and an
    # in-kernel reduction to a scalar is re-traced at lowering time with
    # the package's 64-bit mode back on ("64-bit types are not
    # supported").
    with jax.enable_x64(False):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((SUB, LANES), lambda i, base: (i, 0)),
                pl.BlockSpec((P, WIN_ROWS, LANES),
                             lambda i, base: (0, base[i], 0)),
                pl.BlockSpec((P, WIN_ROWS, LANES),
                             lambda i, base: (0, base[i] + 1, 0))],
            out_specs=[
                pl.BlockSpec((P, SUB, LANES), lambda i, base: (0, i, 0)),
                pl.BlockSpec((SUB, LANES), lambda i, base: (0, 0))])
        out, esc = pl.pallas_call(
            _window_kernel(P, fills),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((P, nb * SUB, LANES), jnp.int32),
                jax.ShapeDtypeStruct((SUB, LANES), jnp.int32)],
            interpret=interpret,
        )(base_blocks, idx32.reshape(nb * SUB, LANES), reshaped, reshaped)
    return out.reshape(P, n), jnp.sum(esc, dtype=jnp.int32)


# --------------------------------------------------------------------------
# public wrappers (usable inside surrounding jits; all shapes static)
# --------------------------------------------------------------------------

def _sanitize_idx(idx: jax.Array, limit: int) -> jax.Array:
    """Clamp to the fill contract: anything outside [0, limit) becomes
    the -1 miss sentinel BEFORE the int32 narrowing (a wild int64 index
    must not wrap into a valid row)."""
    ok = (idx >= 0) & (idx < limit)
    return jnp.where(ok, idx, -1).astype(jnp.int32)


def _pad_to(x: jax.Array, mult: int, value):
    pad = (-x.shape[-1]) % mult
    if pad == 0:
        return x
    width = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, width, constant_values=value)


def gather_supported(tables, n_rows: Optional[int] = None,
                     max_elems: int = SCAN_MAX_ELEMS) -> bool:
    """Shape gate shared by every call site's auto-fallback."""
    if not tables or not supports_tables(tables):
        return False
    w = tables[0].shape[0]
    if any(t.shape[0] != w for t in tables) or w > max_elems:
        return False
    return True


def _xla_gather(tables, idx, fills):
    """The fallback (and the parity reference): clip-free take with the
    same miss-fill contract as the kernels."""
    w = tables[0].shape[0]
    ok = (idx >= 0) & (idx < w)
    idx_c = jnp.clip(idx, 0, w - 1)
    return [jnp.where(ok, jnp.take(t, idx_c, axis=0),
                      jnp.asarray(f, dtype=t.dtype))
            for t, f in zip(tables, fills)]


def gather_columns(tables, idx, fills=None, *, mode: str = "off"):
    """Fused multi-table gather: out[t][i] = tables[t][idx[i]] when
    0 <= idx[i] < W, else fills[t].  Bit-exact vs the jnp.take path;
    falls back to it when mode is 'off' or the shape gate fails.
    `mode` and all shapes must be static (call under jit is fine).

    This is also the SHARD-LOCAL entry point: inside a shard_map body
    (the mesh-partitioned join's per-chip probe) every shape it sees is
    the per-shard local shape, so the kernel gathers against the 1/N
    table slice resident on its own chip — no cross-chip traffic."""
    tables = list(tables)
    if fills is None:
        fills = [0] * len(tables)
    if mode == "off" or not gather_supported(tables):
        return _xla_gather(tables, idx, fills)
    interpret = mode == "interpret"
    w = tables[0].shape[0]
    n = idx.shape[0]
    idx32 = _pad_to(_sanitize_idx(idx, w), TILE, -1)

    # split every table into int32 planes, group into VMEM-sized calls
    plane_list: List[jax.Array] = []
    plane_fills: List[int] = []
    spans: List[Tuple[int, int, object]] = []   # (start, count, dtype)
    for t, f in zip(tables, fills):
        ps = _split_planes(t)
        spans.append((len(plane_list), len(ps), t.dtype))
        plane_list.extend(_pad_to(p, SLAB, 0) for p in ps)
        plane_fills.extend(_fill_planes(f, t.dtype))

    gathered: List[jax.Array] = []
    for g0 in range(0, len(plane_list), MAX_PLANES):
        group = plane_list[g0:g0 + MAX_PLANES]
        gf = tuple(plane_fills[g0:g0 + MAX_PLANES])
        out = _scan_gather_planes(idx32, jnp.stack(group), gf, interpret)
        gathered.extend(out[p] for p in range(len(group)))

    results = []
    for start, count, dtype in spans:
        results.append(_join_planes(gathered[start:start + count],
                                    dtype)[:n])
    return results


def window_base_blocks(idx32: jax.Array, n_blocks: int) -> jax.Array:
    """Per-(8,128)-tile window choice: the tile's minimum in-range index
    rounded down to a WIN block (computed in XLA, prefetched as scalars
    so the BlockSpec index_map can steer the window DMA).  Clipped to
    n_blocks - 2 because the kernel fetches base and base + 1."""
    nb = idx32.shape[0] // TILE
    tiles = idx32.reshape(nb, TILE)
    sentinel = jnp.int32(2147483647)
    lo = jnp.min(jnp.where(tiles >= 0, tiles, sentinel), axis=1)
    return jnp.clip(lo // WIN, 0, max(n_blocks - 2, 0)).astype(jnp.int32)


def prepare_word_planes(lut: jax.Array) -> jax.Array:
    """One-time prep of a value-packed LUT for gather_word_windowed:
    int32 planes, padded to whole windows (at least two — the kernel
    always fetches a pair).  The chunked driver calls this ONCE per
    pinned LUT so the per-chunk program only streams the windows it
    touches (re-splitting per chunk would re-read the whole domain-sized
    table every chunk)."""
    planes = [_pad_to(p, WIN, 0) for p in _split_planes(lut)]
    if planes[0].shape[0] < 2 * WIN:
        planes = [_pad_to(p, 2 * WIN, 0) for p in planes]
    return jnp.stack(planes)


def gather_word_windowed(planes: jax.Array, idx, word_dtype: str,
                         *, mode: str):
    """Windowed single-word gather off prepared planes (see
    prepare_word_planes): returns (words int64, escaped int64) where
    escaped counts in-range indices that fell outside their tile's
    window — those rows come back as 0 (the packed-LUT miss word) and
    the CALLER MUST rerun via its escape machinery when escaped > 0.
    `word_dtype` is the original LUT dtype (static)."""
    P, W = planes.shape
    n = idx.shape[0]
    idx32 = _pad_to(_sanitize_idx(idx, W), TILE, -1)
    base = window_base_blocks(idx32, W // WIN)
    fills = _fill_planes(0, word_dtype)
    out, esc = _window_gather_planes(idx32, base, planes, fills,
                                     mode == "interpret")
    word = _join_planes([out[p] for p in range(P)],
                        word_dtype)[:n].astype(jnp.int64)
    return word, esc.astype(jnp.int64)


# --------------------------------------------------------------------------
# pre-jitted, compile-recorded entry points. Inside an executor kernel the
# ENCLOSING jit owns the compile (the recorder stays silent under an open
# trace), so these exist for the eager boundary: the gather microbench and
# any ad-hoc top-level kernel use route their XLA compiles through the
# central recorder (exec/profiler.py) like every other jit site.
# --------------------------------------------------------------------------

from ..exec.profiler import instrument as _instrument  # noqa: E402

gather_columns_jit = _instrument(
    jax.jit(gather_columns, static_argnames=("fills", "mode")),
    site="pallas_gather.gather_columns")
gather_word_windowed_jit = _instrument(
    jax.jit(gather_word_windowed, static_argnames=("word_dtype", "mode")),
    site="pallas_gather.gather_word_windowed")
