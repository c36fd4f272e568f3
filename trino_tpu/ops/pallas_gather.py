"""Pallas TPU scan gather: a multi-table gather from a SMALL table.

`out[t][i] = tables[t][idx[i]]` for `0 <= idx[i] < W`, 0 otherwise,
bit-exact with `jnp.take`. XLA's gather pays by the index
and the 32-bit plane whatever the table's size (PERF.md section 6); this
kernel streams the table through VMEM in SLAB-row slabs on a second grid
dimension, tests each (8,128) probe tile against every slab row and
selects by a lane gather (`take_along_axis`, the only gather form Mosaic
lowers natively), and decomposes each index ONCE for every plane. Its
cost grows with the table (about W / 1,024 VPU operations an element),
so it is taken only on a TPU and only for tables of at most
SCAN_MAX_ELEMS entries: platform and table size decide, no option does.
The one site is a dense join's payload gathers over a small pinned
build (`ops/join._gather_build_payload`): a worker Q18's `lineitem`
stage, 241 laps a statement (PERF.md section 6, PR 46).

int64 tables ride as two int32 bit-planes (Mosaic has no 64-bit lanes);
float32 bitcasts; narrow ints and bools widen to one int32 plane.
float64 takes the `jnp.take` path (the TPU compiler refuses its split).
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB = 8                     # sublanes per probe tile
LANES = 128                 # lanes per probe tile
_LANE_BITS = 7              # row/lane splits are shifts and masks in-kernel
TILE = SUB * LANES          # probe indices resolved per grid step
SLAB_ROWS = 16              # table rows (of LANES) per slab
SLAB = SLAB_ROWS * LANES
MAX_PLANES = 12             # int32 planes per pallas_call (VMEM budget)
# beyond this the XLA gather's flat cost an index wins
SCAN_MAX_ELEMS = 1 << 16


# --------------------------------------------------------------------------
# int32 plane split / reassembly (bit-exact for every engine lane dtype)
# --------------------------------------------------------------------------

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


# --------------------------------------------------------------------------
# the kernel: table slabs stream on grid dim 1, output revisited
# --------------------------------------------------------------------------

def _scan_kernel(n_planes: int):
    def kernel(idx_ref, planes_ref, out_ref):
        s = pl.program_id(1)
        local = idx_ref[...]                             # [SUB, LANES]
        row = jnp.where(local >= 0, local >> _LANE_BITS, -1)
        lane = jnp.where(local >= 0, local & (LANES - 1), 0)
        # an index no slab row answers (a miss, -1) reads 0
        accs = [jnp.where(s == 0, jnp.zeros((SUB, LANES), jnp.int32),
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
                        interpret: bool) -> jax.Array:
    """idx32 [n_pad] int32 (pad/miss = -1), planes [P, W_pad] int32 ->
    gathered [P, n_pad] int32."""
    P, W = planes.shape
    n = idx32.shape[0]
    nb, n_slabs = n // TILE, W // SLAB
    # traced with 64-bit off (kernel body AND index maps): the package
    # enables jax_enable_x64 and Mosaic has no 64-bit lanes
    with jax.enable_x64(False):
        out = pl.pallas_call(
            _scan_kernel(P),
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


def gather_supported(tables, interpret: bool = False) -> bool:
    """Whether the kernel takes these tables: on a TPU (or in the
    interpreter, which is how a test runs the kernel's logic), lanes that
    ride int32 planes, one length, at most SCAN_MAX_ELEMS entries."""
    if not tables or not supports_tables(tables):
        return False
    if not interpret and jax.default_backend() != "tpu":
        return False
    w = tables[0].shape[0]
    return all(t.shape[0] == w for t in tables) and w <= SCAN_MAX_ELEMS


def gather_columns(tables, idx, *, interpret: bool = False):
    """Fused multi-table gather: out[t][i] = tables[t][idx[i]] when
    0 <= idx[i] < W, else 0. Bit-exact with jnp.take on the shared
    domain. The caller asks
    gather_supported first and keeps its plain gathers for the rest. All
    shapes must be static (a call under jit is fine)."""
    tables = list(tables)
    assert gather_supported(tables, interpret)
    w = tables[0].shape[0]
    n = idx.shape[0]
    idx32 = _pad_to(_sanitize_idx(idx, w), TILE, -1)

    # split every table into int32 planes, group into VMEM-sized calls
    plane_list: List[jax.Array] = []
    spans: List[Tuple[int, int, object]] = []   # (start, count, dtype)
    for t in tables:
        ps = _split_planes(t)
        spans.append((len(plane_list), len(ps), t.dtype))
        plane_list.extend(_pad_to(p, SLAB, 0) for p in ps)

    gathered: List[jax.Array] = []
    for g0 in range(0, len(plane_list), MAX_PLANES):
        group = plane_list[g0:g0 + MAX_PLANES]
        out = _scan_gather_planes(idx32, jnp.stack(group), interpret)
        gathered.extend(out[p] for p in range(len(group)))

    results = []
    for start, count, dtype in spans:
        results.append(_join_planes(gathered[start:start + count],
                                    dtype)[:n])
    return results
