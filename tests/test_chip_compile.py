"""Compile the main path's kernels for a described v5e, without the chip.

The TPU's compiler is installed here and compiles for a chip that is
described and not attached (`jax.experimental.topologies`). These tests
hand the join and aggregate kernels, the one `pallas_call` wrapper and the
q1 stage the shapes the TPC-H SF10 queries produce and ask only: does the
chip's compiler accept the program, and is the kernel in it
(`tpu_custom_call`) exactly where the engine means it to be? Nothing runs,
so they say nothing about results or times — `chip_smoke.py` on the chip
does.

This is the ONLY test file that touches the TPU compiler: one process at
a time may load the TPU's library, and the xdist worker that is given
this file is the one that loads it. The topology is described inside a
module-scoped fixture (never at import or collection time), so every
worker collects the same tests.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

SF10_LINEITEM = 59_986_052  # tpch sf10 lineitem rows (60M)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without a chip: keep it off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — any failure = no TPU
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _batch(one_chip, length, *dtypes):
    from trino_tpu.batch import Batch, Column

    def shape(dtype):
        return jax.ShapeDtypeStruct((length,), dtype, sharding=one_chip)
    return Batch(tuple(Column(shape(d), shape(jnp.bool_)) for d in dtypes),
                 shape(jnp.bool_))


# ---------------------------------------------------------------------------
# the one Pallas kernel (ops/pallas_gather.py: a small build's payload):
# P = 2 (one int64 table) and P = MAX_PLANES, at a split's 262,144 probes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("planes", [2, 12])
def test_scan_gather_compiles(one_chip, planes):
    from trino_tpu.ops import pallas_gather as pg
    assert planes <= pg.MAX_PLANES
    i32 = jnp.int32
    args = [jax.ShapeDtypeStruct(shape, i32, sharding=one_chip)
            for shape in ((262_144,), (planes, pg.SCAN_MAX_ELEMS))]
    compiled = jax.jit(
        lambda idx, p: pg._scan_gather_planes(idx, p, False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_float64_planes_are_refused_and_gated_off(one_chip, monkeypatch):
    """DOUBLE tables never reach the kernel (supports_tables), because
    the chip's compiler refuses their split into int32 planes."""
    from trino_tpu.ops import pallas_gather as pg
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    f64 = jax.ShapeDtypeStruct((1024,), jnp.float64)
    assert not pg.supports_tables([f64])
    assert not pg.gather_supported([f64])
    with pytest.raises(Exception, match="X64 element types"):
        jax.jit(pg._split_planes).lower(jax.ShapeDtypeStruct(
            (1024,), jnp.float64, sharding=one_chip)).compile()


# ---------------------------------------------------------------------------
# the gather sites at the sizes where a hand-written kernel stood in for
# them until PR 46 (tables of at most 65,536 entries; direct aggregates of
# 12 groups and more). On a TPU a small build's payload still rides the
# kernel; every other site is an XLA program at every size
# ---------------------------------------------------------------------------

def small_table_join(one_chip):
    from trino_tpu.ops.join import dense_join_with_lut
    probe = _batch(one_chip, 262_144, jnp.int64, jnp.int64)
    build = _batch(one_chip, 1_024, jnp.int64, jnp.int64, jnp.int32)
    lut = jax.ShapeDtypeStruct((4_097,), jnp.int32, sharding=one_chip)
    return dense_join_with_lut.__wrapped__.lower(
        probe, build, lut, (0,), (0,), "inner")


def small_group_read_back(one_chip):
    from trino_tpu.ops.aggregate import AggSpec, sort_group_aggregate
    return sort_group_aggregate.__wrapped__.lower(
        _batch(one_chip, 2_048, jnp.int64, jnp.int32, jnp.int64), (0, 1),
        (AggSpec("sum", 2), AggSpec("count_star", None)), 2_048)


def sixteen_group_direct_aggregate(one_chip):
    from trino_tpu.ops.aggregate import AggSpec, direct_group_aggregate
    return direct_group_aggregate.__wrapped__.lower(
        _batch(one_chip, 262_144, jnp.int32, jnp.int64, jnp.int64), (0,),
        (16,), (AggSpec("sum", 1), AggSpec("sum", 2), AggSpec("count", 1),
                AggSpec("count_star", None)))


@pytest.mark.parametrize("program", [small_table_join,
                                     small_group_read_back,
                                     sixteen_group_direct_aggregate])
def test_what_a_small_gather_site_compiles_to(one_chip, program,
                                              monkeypatch):
    # the gate asks the process's backend, which here is the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = program(one_chip).compile().as_text()
    gathers = len(re.findall(r" gather\(", text))
    if program is small_table_join:
        # the LUT's probe is XLA's gather, the 1,024-row build's validity
        # word and two columns are one kernel call
        assert text.count("tpu_custom_call") >= 1 and gathers == 1
    else:
        assert "tpu_custom_call" not in text
        assert (gathers == 0) == (program is sixteen_group_direct_aggregate)
        assert " scatter(" not in text or program is small_group_read_back


# ---------------------------------------------------------------------------
# the plain XLA path: the q1 stage at 60M rows fits one chip
# ---------------------------------------------------------------------------

def test_q1_stage_compiles_at_60m_rows(one_chip):
    import __graft_entry__ as graft
    fn, args = graft.entry()
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            (SF10_LINEITEM + (-SF10_LINEITEM) % 1024,) + x.shape[1:],
            x.dtype, sharding=one_chip), args)
    compiled = jax.jit(fn).lower(*shapes).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes + \
        mem.output_size_in_bytes
    assert used < 16 * 10**9, f"q1 at 60M rows needs {used:,} bytes"


# ---------------------------------------------------------------------------
# the packed sort aggregate with Q18's statics (one 28-bit key, a decimal
# sum's two limbs): what the chip's compiler makes of its three forms. At
# 16,384 rows each compiles in 4 to 6 s here; the same program took 141 s
# at 262,144 rows and 113 s at 60M, which no test should pay
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("form", ["in-place", "dense", "permutation"])
def test_packed_sort_aggregate_gathers_by_form(one_chip, form):
    from trino_tpu.ops.aggregate import (AggSpec,
                                         packed_sort_group_aggregate)
    n, capacity = 16_384, 2_048

    def shape(dtype, length=n):
        return jax.ShapeDtypeStruct((length,), dtype, sharding=one_chip)
    batch = _batch(one_chip, n, jnp.int64, jnp.int64, jnp.int64)
    aggs = (AggSpec("sum", 1), AggSpec("sum", 2))
    carried = form != "permutation"

    def program(batch, kmins, vmins):
        return packed_sort_group_aggregate(
            batch, kmins, (0,), (28,), aggs, capacity, ((0, 1),),
            vmins if carried else None, (2, 16) if carried else None,
            form == "in-place")
    text = jax.jit(program).lower(
        batch, shape(jnp.int64, 1), shape(jnp.int64, 2)).compile().as_text()
    lengths = [int(m) for m in re.findall(
        r"= \w+\[(\d+)\]\S* gather\(", text)]
    scatters = len(re.findall(r" scatter\(", text))
    if form == "in-place":
        assert (lengths, scatters) == ([], 0)
    elif form == "dense":
        # the groups' read-back, a plane each, and nothing at the input's
        assert lengths and set(lengths) == {capacity} and scatters == 0
    else:
        # what the value-carrying form is rid of: planes fetched through
        # the sort's permutation at the input's length, then at the
        # group capacity, and the segment-start scatter
        assert lengths.count(n) >= 6 and lengths.count(capacity) >= 13
        assert scatters == 1


# ---------------------------------------------------------------------------
# a split's join over a pinned build, at worker.join's shapes: a 250,000-row
# lineitem split probing the 60M-key LUT of a 1,572,864-row `orders` build
# with two payload columns. The row-id form gathers once for the row and
# once for each payload plane; the packed form gathers its word
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("form", ["packed-int32", "packed-int64", "rows"])
def test_a_split_join_gathers_once_when_its_lut_is_packed(one_chip, form):
    from trino_tpu.batch import bucket_capacity
    from trino_tpu.ops.join import dense_join_packed, dense_join_with_lut
    n, domain, build_rows = bucket_capacity(250_000), 60_000_000, 1_572_864

    def shape(dtype, length):
        return jax.ShapeDtypeStruct((length,), dtype, sharding=one_chip)
    probe = _batch(one_chip, n, jnp.int64, jnp.int64, jnp.int64)
    if form == "rows":
        build = _batch(one_chip, build_rows, jnp.int64, jnp.int32, jnp.int32)
        text = dense_join_with_lut.__wrapped__.lower(
            probe, build, shape(jnp.int32, domain + 1), (0,), (0,),
            "inner").compile().as_text()
    else:
        word = jnp.int32 if form == "packed-int32" else jnp.int64
        meta = ((1, 16, 1, 17), (2, 8, 18, 26))
        text = dense_join_packed.__wrapped__.lower(
            probe, shape(word, domain + 1), shape(jnp.int64, 2), (0,),
            meta, 0, ("int64", "int32", "int32"), "inner").compile().as_text()
    gathers = re.findall(r"= \w+\[(\d+)\]\S* gather\(", text)
    assert set(gathers) == {str(n)}
    # the compiler gathers a 32-bit plane at a time: an int64 word is
    # two, and the row-id form's five are the row, the validity word's
    # two planes and the two columns
    assert len(gathers) == {"packed-int32": 1, "packed-int64": 2,
                            "rows": 5}[form]


# ---------------------------------------------------------------------------
# filter_project with its literals as operands: q6's filter at a 250,000-row
# split's capacity, and a decimal comparison whose LITERAL has the larger
# scale (the traced scalar is the side _decimal_compare floor-divides)
# ---------------------------------------------------------------------------

def test_filter_project_with_operand_literals_compiles(one_chip):
    from trino_tpu import ir
    from trino_tpu.batch import Batch, Column
    from trino_tpu.ops.project import filter_project
    from trino_tpu.types import BIGINT, DATE, VARCHAR, decimal
    n = 262_144
    d122 = decimal(12, 2)
    qty, price, disc = (ir.ColumnRef(i, d122) for i in range(3))
    ship, seg = ir.ColumnRef(3, DATE), ir.ColumnRef(4, VARCHAR)

    def q6(day0, day1, lo, hi, quantity, lut):
        return (ir.Logical('and', (
            ir.Compare('>=', ship, ir.Literal(day0, DATE)),
            ir.Compare('<', ship, ir.Literal(day1, DATE)),
            ir.Between(disc, ir.Literal(lo, decimal(1, 2)),
                       ir.Literal(hi, decimal(2, 2))),
            ir.Compare('<', qty, ir.Literal(quantity, BIGINT)),
            ir.Compare('<', price, ir.Literal(quantity * 10 ** 6,
                                              decimal(9, 6))),
            ir.DictPredicate(seg, lut))),
            (ir.arith('*', price, disc),
             ir.arith('*', price, ir.arith(
                 '-', ir.Literal(1, decimal(1, 0)), disc))))

    ta, va = ir.parametrise(q6(8766, 9131, 5, 7, 24, (True,) + (False,) * 4))
    tb, vb = ir.parametrise(q6(9862, 10227, 8, 10, 25,
                               (False,) * 4 + (True,)))
    assert ta == tb and ir.slot_count(va) == 8

    def shape(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def column(dtype):
        return Column(
            data=jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip),
            valid=jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip))

    batch = Batch(columns=(column(jnp.int64),) * 3
                  + (column(jnp.int32),) * 2,
                  live=jax.ShapeDtypeStruct((n,), jnp.bool_,
                                            sharding=one_chip))
    compiled = filter_project.__wrapped__.lower(
        batch, jax.tree_util.tree_map(shape, va), *ta).compile()
    mem = compiled.memory_analysis()
    # the operands: one int64 vector of 7 slots and a 5-entry table
    assert mem.argument_size_in_bytes < 5 * n * 9 + n + 4096
