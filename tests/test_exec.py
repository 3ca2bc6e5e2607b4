"""Physical operator tests: project / filter / range via the plan-level
dual-run harness (reference: basicPhysicalOperators tests — SURVEY.md §4)."""
import pyarrow as pa
import pytest

from spark_rapids_tpu import datatypes as dt
from spark_rapids_tpu.exec import (HostBatchSourceExec, TpuFilterExec,
                                   TpuProjectExec, TpuRangeExec)
from spark_rapids_tpu.expr import (Add, Alias, And, Cast, GreaterThan,
                                   IsNotNull, LessThan, Literal, Multiply,
                                   UnresolvedColumn as col)

from asserts import assert_tpu_and_cpu_plan_equal
from data_gen import (BooleanGen, DoubleGen, FloatGen, IntegerGen, LongGen,
                      StringGen, all_basic_gens, gen_table)


def source(gens, n=256, seed=1234, names=None):
    return HostBatchSourceExec([gen_table(gens, n, seed, names)])


def test_project_arithmetic():
    plan = TpuProjectExec(
        [Alias(Add(col("c0"), col("c1")), "s"),
         Alias(Multiply(col("c0"), Literal(3)), "m")],
        source([IntegerGen(), IntegerGen()]))
    assert_tpu_and_cpu_plan_equal(plan)


def test_project_identity_all_types():
    gens = all_basic_gens
    names = [f"c{i}" for i in range(len(gens))]
    plan = TpuProjectExec([col(n) for n in names], source(gens, names=names))
    assert_tpu_and_cpu_plan_equal(plan)


def test_filter_simple():
    plan = TpuFilterExec(
        GreaterThan(col("c0"), Literal(0)),
        source([IntegerGen(), StringGen(), DoubleGen()]))
    assert_tpu_and_cpu_plan_equal(plan)


def test_filter_null_predicate_drops():
    # Nullable comparison: null predicate rows must be dropped, not kept.
    plan = TpuFilterExec(
        LessThan(col("c0"), col("c1")),
        source([IntegerGen(null_frac=0.3), IntegerGen(null_frac=0.3)]))
    assert_tpu_and_cpu_plan_equal(plan)


def test_filter_compound_and_project():
    src = source([IntegerGen(), DoubleGen(), StringGen()])
    filt = TpuFilterExec(
        And(IsNotNull(col("c1")), GreaterThan(col("c0"), Literal(-100))),
        src)
    plan = TpuProjectExec(
        [Alias(Add(col("c0"), Literal(1)), "a"), col("c2")], filt)
    assert_tpu_and_cpu_plan_equal(plan)


def test_filter_none_pass():
    plan = TpuFilterExec(Literal(False), source([IntegerGen()]))
    assert_tpu_and_cpu_plan_equal(plan)


def test_filter_all_pass():
    plan = TpuFilterExec(Literal(True), source([IntegerGen(), StringGen()]))
    assert_tpu_and_cpu_plan_equal(plan)


def test_filter_strings_compact():
    plan = TpuFilterExec(col("c1"),
                         source([StringGen(null_frac=0.2), BooleanGen()]))
    assert_tpu_and_cpu_plan_equal(plan)


def test_range_basic():
    assert_tpu_and_cpu_plan_equal(TpuRangeExec(0, 1000))


def test_range_step_negative():
    assert_tpu_and_cpu_plan_equal(TpuRangeExec(100, -5, -3))


def test_range_multi_batch():
    assert_tpu_and_cpu_plan_equal(
        TpuRangeExec(0, 5000, 7, max_rows_per_batch=1024))


def test_range_empty():
    assert_tpu_and_cpu_plan_equal(TpuRangeExec(10, 10))


def test_range_filter_project_q6_shape():
    # TPC-H q6 shape over range data: scan -> filter -> project.
    rng = TpuRangeExec(0, 4096)
    filt = TpuFilterExec(
        And(GreaterThan(col("id"), Literal(100, dt.INT64)),
            LessThan(col("id"), Literal(4000, dt.INT64))), rng)
    plan = TpuProjectExec(
        [Alias(Multiply(Cast(col("id"), dt.FLOAT64), Literal(0.07)), "rev")],
        filt)
    assert_tpu_and_cpu_plan_equal(plan, approx_float=True)


def test_multi_batch_source():
    rbs = [gen_table([IntegerGen(), StringGen()], n, seed=s)
           for n, s in [(100, 1), (57, 2), (300, 3)]]
    plan = TpuFilterExec(GreaterThan(col("c0"), Literal(0)),
                         HostBatchSourceExec(rbs))
    assert_tpu_and_cpu_plan_equal(plan)


# --- union / expand / sample ----------------------------------------------

def test_union_all():
    from spark_rapids_tpu.exec import TpuUnionExec
    kids = [HostBatchSourceExec([gen_table([IntegerGen(), StringGen()],
                                           n, seed=s)])
            for n, s in [(80, 1), (50, 2), (120, 3)]]
    plan = TpuUnionExec(kids)
    assert_tpu_and_cpu_plan_equal(plan)


def test_expand_grouping_sets_shape():
    from spark_rapids_tpu.exec import TpuExpandExec
    from spark_rapids_tpu.expr import Literal
    src = HostBatchSourceExec(
        [gen_table([IntegerGen(min_val=0, max_val=5),
                    IntegerGen(min_val=0, max_val=3),
                    LongGen()], 150, seed=4)])
    # ROLLUP(c0, c1)-style projections with a grouping-id literal
    projections = [
        [col("c0"), col("c1"), col("c2"), Literal(0, dt.INT32)],
        [col("c0"), Literal(None, dt.INT32), col("c2"),
         Literal(1, dt.INT32)],
        [Literal(None, dt.INT32), Literal(None, dt.INT32), col("c2"),
         Literal(3, dt.INT32)],
    ]
    plan = TpuExpandExec(projections, ["c0", "c1", "c2", "gid"], src)
    assert_tpu_and_cpu_plan_equal(plan)


def test_expand_feeds_rollup_aggregate():
    from spark_rapids_tpu.exec import TpuExpandExec
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.expr import Alias, Literal
    from spark_rapids_tpu.expr.aggregates import Sum
    src = HostBatchSourceExec(
        [gen_table([IntegerGen(min_val=0, max_val=4), LongGen()], 200,
                   seed=6)])
    exp = TpuExpandExec(
        [[col("c0"), col("c1"), Literal(0, dt.INT32)],
         [Literal(None, dt.INT32), col("c1"), Literal(1, dt.INT32)]],
        ["c0", "c1", "gid"], src)
    plan = TpuHashAggregateExec([col("c0"), col("gid")],
                                [Alias(Sum(col("c1")), "s")], exp)
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True)


@pytest.mark.parametrize("fraction", [0.0, 0.3, 1.0])
def test_sample(fraction):
    from spark_rapids_tpu.exec import TpuSampleExec
    src = HostBatchSourceExec(
        [gen_table([IntegerGen(), StringGen()], 150, seed=s)
         for s in (1, 2)])
    plan = TpuSampleExec(fraction, seed=42, child=src)
    got = assert_tpu_and_cpu_plan_equal(plan)
    if fraction == 0.0:
        assert got.num_rows == 0
    if fraction == 1.0:
        assert got.num_rows == 300


def test_sample_deterministic():
    from spark_rapids_tpu.exec import TpuSampleExec
    from spark_rapids_tpu.exec.base import ExecCtx, collect_arrow
    src = HostBatchSourceExec([gen_table([IntegerGen()], 200, seed=9)])
    a = collect_arrow(TpuSampleExec(0.5, 7, src), ExecCtx())
    b = collect_arrow(TpuSampleExec(0.5, 7, src), ExecCtx())
    assert a.to_pylist() == b.to_pylist()
