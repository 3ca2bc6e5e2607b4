"""Group-by / aggregate tests via the dual-run harness
(reference: hash_aggregate_test.py — SURVEY.md §4.1)."""
import pyarrow as pa
import pytest

from spark_rapids_tpu import datatypes as dt
from spark_rapids_tpu.exec import HostBatchSourceExec
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.expr import Alias, UnresolvedColumn as col
from spark_rapids_tpu.expr.aggregates import (Average, Count, First, Last,
                                              Max, Min, StddevPop,
                                              StddevSamp, Sum, VariancePop,
                                              VarianceSamp)

from asserts import assert_tpu_and_cpu_plan_equal
from data_gen import (BooleanGen, ByteGen, DateGen, DecimalGen, DoubleGen,
                      FloatGen, IntegerGen, LongGen, ShortGen, StringGen,
                      TimestampGen, gen_table)


def source(gens, n=256, seed=1234, names=None):
    return HostBatchSourceExec([gen_table(gens, n, seed, names)])


def kv_source(key_gen, val_gen, n=512, seed=7):
    return source([key_gen, val_gen], n, seed)


def agg_plan(src, keys, aggs):
    return TpuHashAggregateExec(keys, aggs, src)


key_gens = [IntegerGen(min_val=0, max_val=10), LongGen(),
            StringGen(max_len=6), DateGen(), BooleanGen(),
            DoubleGen(null_frac=0.2)]


@pytest.mark.parametrize("kg", key_gens,
                         ids=lambda g: g.dtype.simple_string())
def test_groupby_count_star(kg):
    plan = agg_plan(kv_source(kg, IntegerGen()), [col("c0")],
                    [Alias(Count(), "cnt")])
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True)


@pytest.mark.parametrize("vg", [ByteGen(), ShortGen(), IntegerGen(),
                                LongGen(), FloatGen(dt.FLOAT32),
                                DoubleGen()],
                         ids=lambda g: g.dtype.simple_string())
def test_groupby_sum(vg):
    plan = agg_plan(
        kv_source(IntegerGen(min_val=0, max_val=20), vg),
        [col("c0")], [Alias(Sum(col("c1")), "s")])
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True,
                                  approx_float=True)


def test_groupby_sum_decimal():
    plan = agg_plan(
        kv_source(IntegerGen(min_val=0, max_val=10),
                  DecimalGen(precision=7, scale=2)),
        [col("c0")], [Alias(Sum(col("c1")), "s")])
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True)


@pytest.mark.parametrize("vg", [IntegerGen(null_frac=0.3), LongGen(),
                                DoubleGen(), DateGen(), TimestampGen(),
                                BooleanGen()],
                         ids=lambda g: g.dtype.simple_string())
def test_groupby_min_max(vg):
    plan = agg_plan(
        kv_source(IntegerGen(min_val=0, max_val=15), vg),
        [col("c0")],
        [Alias(Min(col("c1")), "mn"), Alias(Max(col("c1")), "mx")])
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True)


def test_groupby_avg():
    plan = agg_plan(
        kv_source(IntegerGen(min_val=0, max_val=12), LongGen()),
        [col("c0")], [Alias(Average(col("c1")), "a")])
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True,
                                  approx_float=True)


def test_groupby_avg_decimal():
    plan = agg_plan(
        kv_source(IntegerGen(min_val=0, max_val=5),
                  DecimalGen(precision=4, scale=1)),
        [col("c0")], [Alias(Average(col("c1")), "a")])
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True)


def test_groupby_count_column():
    plan = agg_plan(
        kv_source(IntegerGen(min_val=0, max_val=8),
                  IntegerGen(null_frac=0.4)),
        [col("c0")],
        [Alias(Count(col("c1")), "c"), Alias(Count(), "cs")])
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True)


def test_groupby_first_last():
    # first/last are order-dependent: make values unique per key via a
    # single-batch source with ignore_nulls both ways
    plan = agg_plan(
        kv_source(IntegerGen(min_val=0, max_val=6, nullable=False),
                  IntegerGen(null_frac=0.5), n=64),
        [col("c0")],
        [Alias(First(col("c1"), ignore_nulls=True), "f"),
         Alias(Last(col("c1"), ignore_nulls=True), "l")])
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True)


def test_groupby_stddev_variance():
    plan = agg_plan(
        kv_source(IntegerGen(min_val=0, max_val=10),
                  DoubleGen(special=False)),
        [col("c0")],
        [Alias(StddevSamp(col("c1")), "ss"),
         Alias(StddevPop(col("c1")), "sp"),
         Alias(VarianceSamp(col("c1")), "vs"),
         Alias(VariancePop(col("c1")), "vp")])
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True,
                                  approx_float=True)


def test_groupby_multi_key():
    plan = agg_plan(
        source([IntegerGen(min_val=0, max_val=4), StringGen(max_len=3),
                BooleanGen(), LongGen()], n=512),
        [col("c0"), col("c1"), col("c2")],
        [Alias(Sum(col("c3")), "s"), Alias(Count(), "c")])
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True)


def test_groupby_float_key_specials():
    # NaN groups as one; -0.0 and 0.0 group together
    plan = agg_plan(
        kv_source(DoubleGen(null_frac=0.2), IntegerGen()),
        [col("c0")], [Alias(Count(), "c")])
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True)


def test_groupby_null_keys_group():
    plan = agg_plan(
        kv_source(IntegerGen(null_frac=0.5), LongGen()),
        [col("c0")], [Alias(Sum(col("c1")), "s"), Alias(Count(), "c")])
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True)


def test_global_agg():
    plan = agg_plan(
        source([IntegerGen(), DoubleGen()], n=300), [],
        [Alias(Sum(col("c0")), "s"), Alias(Count(), "c"),
         Alias(Min(col("c0")), "mn"), Alias(Max(col("c1")), "mx"),
         Alias(Average(col("c0")), "a")])
    assert_tpu_and_cpu_plan_equal(plan, approx_float=True)


def test_global_agg_empty_input():
    empty = pa.record_batch(
        {"c0": pa.array([], pa.int32()), "c1": pa.array([], pa.float64())})
    plan = agg_plan(HostBatchSourceExec([empty]), [],
                    [Alias(Sum(col("c0")), "s"), Alias(Count(), "c"),
                     Alias(Min(col("c1")), "mn")])
    assert_tpu_and_cpu_plan_equal(plan)


def test_groupby_empty_input():
    empty = pa.record_batch(
        {"c0": pa.array([], pa.int32()), "c1": pa.array([], pa.int64())})
    plan = agg_plan(HostBatchSourceExec([empty]), [col("c0")],
                    [Alias(Sum(col("c1")), "s")])
    assert_tpu_and_cpu_plan_equal(plan)


def test_groupby_multi_batch_merge():
    rbs = [gen_table([IntegerGen(min_val=0, max_val=10), LongGen()],
                     n, seed=s) for n, s in [(200, 1), (150, 2), (300, 3)]]
    plan = agg_plan(HostBatchSourceExec(rbs), [col("c0")],
                    [Alias(Sum(col("c1")), "s"), Alias(Count(), "c"),
                     Alias(Min(col("c1")), "mn"),
                     Alias(Max(col("c1")), "mx")])
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True)


def test_groupby_string_keys_multi_batch():
    rbs = [gen_table([StringGen(max_len=4), IntegerGen()], n, seed=s)
           for n, s in [(120, 5), (180, 6)]]
    plan = agg_plan(HostBatchSourceExec(rbs), [col("c0")],
                    [Alias(Count(), "c"), Alias(Sum(col("c1")), "s")])
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True)


@pytest.mark.parametrize("batch_rows,final_capacity", [
    (1 << 20, 1024),  # default bound: capacity-bounded concat (256+256+512)
    (512, 128),       # capacities past the bound: sized by the groups
])
@pytest.mark.parametrize("key_gen", [IntegerGen(min_val=0, max_val=10),
                                     StringGen(max_len=2, charset="abcdefghij",
                                               special=False)],
                         ids=["int_key", "string_key"])
def test_final_is_sized_by_live_groups_past_the_batch_bound(
        key_gen, batch_rows, final_capacity):
    """A partial keeps its input's capacity however few groups it holds;
    once the partials' capacities add up to more than batchSizeRows the
    final runs over their live groups, not over the padding."""
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.exec.base import ExecCtx
    rbs = [gen_table([key_gen, LongGen()], n, seed=s)
           for n, s in [(200, 1), (150, 2), (300, 3)]]
    plan = agg_plan(HostBatchSourceExec(rbs), [col("c0")],
                    [Alias(Sum(col("c1")), "s"), Alias(Count(), "c")])
    conf = RapidsConf({"spark.rapids.sql.batchSizeRows": str(batch_rows)})
    [out] = list(plan.execute(ExecCtx(conf)))
    assert out.capacity == final_capacity
    assert_tpu_and_cpu_plan_equal(plan, conf=conf, ignore_order=True)


def test_groupby_computed_key_with_nulls():
    # Regression: null==null must hold for computed group keys whose data
    # lane holds garbage under nulls.
    from spark_rapids_tpu.expr import Add
    plan = agg_plan(
        kv_source(IntegerGen(null_frac=0.4), IntegerGen(null_frac=0.4)),
        [Alias(Add(col("c0"), col("c1")), "k")],
        [Alias(Count(), "c")])
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True)


def test_stddev_large_mean_no_cancellation():
    # Regression: sum/sumsq formulation catastrophically cancels when the
    # mean is large relative to the spread; Welford (n, mean, M2) must not.
    vals = [1e9, 1e9 + 1, 1e9 + 2, 1e9 + 3] * 3
    rb = pa.record_batch({"k": pa.array([0, 0, 0, 1, 1, 1] * 2, pa.int32()),
                          "v": pa.array(vals, pa.float64())})
    plan = agg_plan(HostBatchSourceExec([rb]), [col("k")],
                    [Alias(VarianceSamp(col("v")), "vs"),
                     Alias(StddevSamp(col("v")), "ss")])
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True,
                                  approx_float=True)


def test_stddev_samp_single_element_group_is_null():
    """Spark 3.1+ (legacy.statisticalAggregate=false): sample stddev/var
    of a single value is NULL, not NaN (advisor round-1 medium)."""
    rb = pa.RecordBatch.from_arrays(
        [pa.array([1, 2, 2], pa.int32()),
         pa.array([5.0, 7.0, 9.0], pa.float64())], names=["c0", "c1"])
    for fn in (StddevSamp, VarianceSamp):
        plan = agg_plan(HostBatchSourceExec([rb]), [col("c0")],
                        [Alias(fn(col("c1")), "v")])
        assert_tpu_and_cpu_plan_equal(plan, ignore_order=True,
                                      approx_float=True)
        from spark_rapids_tpu.exec.base import ExecCtx, collect_arrow
        out = collect_arrow(plan)
        by_key = dict(zip(out.column(0).to_pylist(),
                          out.column(1).to_pylist()))
        assert by_key[1] is None  # single-element group
        assert by_key[2] is not None


def test_decimal_sum_overflow_semantics():
    """Sum over wide decimals: the oracle follows Spark (overflow vs the
    REAL result precision p+10, up to 38), the device caps at 18 digits
    and flags itself unsupported for wider results (advisor round-1)."""
    import decimal
    from spark_rapids_tpu.expr.base import BoundReference, EvalCtx, ExprError
    # result decimal(28,0): device-unsupported, oracle returns true sum
    big = decimal.Decimal("900000000000000000")  # 9e17, precision 18
    rb = pa.RecordBatch.from_arrays(
        [pa.array([1, 1], pa.int32()),
         pa.array([big, big], pa.decimal128(18, 0))], names=["c0", "c1"])
    plan = agg_plan(HostBatchSourceExec([rb]), [col("c0")],
                    [Alias(Sum(col("c1")), "s")])
    assert plan.tpu_supported() is not None  # falls back, oracle rules
    from spark_rapids_tpu.exec.base import collect_arrow_cpu
    out = collect_arrow_cpu(plan)
    assert out.column(1).to_pylist() == [decimal.Decimal(2) * big]
    # direct oracle: overflow past precision 38 -> NULL / ANSI error
    s38 = Sum(BoundReference(0, dt.DecimalType(28, 0), True))
    huge = decimal.Decimal(10) ** 37 * 9  # 9e37; two of them pass 10^38
    assert s38.cpu_agg([huge, huge]) is None
    try:
        s38.cpu_agg([huge, huge], EvalCtx(ansi=True))
        assert False, "expected ExprError"
    except ExprError:
        pass
    # long sum ANSI overflow -> error; non-ANSI wraps like java
    slong = Sum(BoundReference(0, dt.INT64, True))
    wrap = slong.cpu_agg([2 ** 62, 2 ** 62])
    assert wrap == -(2 ** 63)
    try:
        slong.cpu_agg([2 ** 62, 2 ** 62], EvalCtx(ansi=True))
        assert False, "expected ExprError"
    except ExprError:
        pass


# --- collect_list / collect_set (single-pass, array results) ---------------

def _collect_plan(agg_cls, val_gen, n=200):
    from spark_rapids_tpu.expr.aggregates import CollectList, CollectSet
    from data_gen import gen_table
    src = HostBatchSourceExec(
        [gen_table([IntegerGen(min_val=0, max_val=6, null_frac=0.1),
                    val_gen], n, seed=31 + i) for i in range(2)])
    return TpuHashAggregateExec(
        [col("c0")], [Alias(agg_cls(col("c1")), "vals")], src)


@pytest.mark.parametrize("val_gen", [LongGen(null_frac=0.2),
                                     StringGen(max_len=5, null_frac=0.2),
                                     DoubleGen(null_frac=0.2)],
                         ids=["long", "string", "double"])
def test_collect_list(val_gen):
    from spark_rapids_tpu.expr.aggregates import CollectList
    assert_tpu_and_cpu_plan_equal(_collect_plan(CollectList, val_gen),
                                  ignore_order=True)


@pytest.mark.parametrize("val_gen", [LongGen(null_frac=0.2),
                                     StringGen(max_len=4, null_frac=0.2),
                                     DoubleGen(null_frac=0.2)],
                         ids=["long", "string", "double"])
def test_collect_set(val_gen):
    from spark_rapids_tpu.expr.aggregates import CollectSet
    assert_tpu_and_cpu_plan_equal(_collect_plan(CollectSet, val_gen),
                                  ignore_order=True)


def test_collect_mixed_with_other_aggs():
    from spark_rapids_tpu.expr.aggregates import CollectList
    from data_gen import gen_table
    src = HostBatchSourceExec(
        [gen_table([IntegerGen(min_val=0, max_val=4, null_frac=0.0),
                    LongGen(null_frac=0.1)], 150, seed=3)])
    plan = TpuHashAggregateExec(
        [col("c0")],
        [Alias(CollectList(col("c1")), "vals"),
         Alias(Sum(col("c1")), "s"), Alias(Count(), "n")], src)
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True)


def test_collect_global_no_keys():
    from spark_rapids_tpu.expr.aggregates import CollectList, CollectSet
    from data_gen import gen_table
    src = HostBatchSourceExec(
        [gen_table([IntegerGen(min_val=0, max_val=9, null_frac=0.3)], 80,
                   seed=8)])
    for cls in (CollectList, CollectSet):
        plan = TpuHashAggregateExec([], [Alias(cls(col("c0")), "vals")],
                                    src)
        assert_tpu_and_cpu_plan_equal(plan)


# --- approx_percentile (SURVEY.md:177; exact sort-based build) ------------

def _percentile_plan(gen, pcts, n=300, keys=True):
    from spark_rapids_tpu.expr.aggregates import ApproxPercentile
    src = HostBatchSourceExec(
        [gen_table([IntegerGen(min_val=0, max_val=6, nullable=False),
                    gen], n, seed=17, names=["k", "v"])])
    keyexprs = [col("k")] if keys else []
    return TpuHashAggregateExec(
        keyexprs, [Alias(ApproxPercentile(col("v"), pcts), "p")], src)


@pytest.mark.parametrize("gen", [IntegerGen(null_frac=0.2), LongGen(),
                                 DoubleGen(null_frac=0.1),
                                 FloatGen(dt.FLOAT32)],
                         ids=lambda g: g.dtype.simple_string())
def test_approx_percentile_scalar(gen):
    plan = _percentile_plan(gen, 0.5)
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True)


def test_approx_percentile_list_and_edges():
    plan = _percentile_plan(DoubleGen(null_frac=0.15),
                            [0.0, 0.25, 0.5, 0.9, 1.0])
    assert_tpu_and_cpu_plan_equal(plan, ignore_order=True)


def test_approx_percentile_global_and_all_null():
    from spark_rapids_tpu.expr.aggregates import ApproxPercentile
    import pyarrow as pa
    rb = pa.record_batch({"v": pa.array([None] * 8, pa.float64())})
    src = HostBatchSourceExec([rb])
    plan = TpuHashAggregateExec(
        [], [Alias(ApproxPercentile(col("v"), [0.5, 0.9]), "p")], src)
    assert_tpu_and_cpu_plan_equal(plan)
    plan2 = _percentile_plan(LongGen(nullable=False), 0.99, keys=False)
    assert_tpu_and_cpu_plan_equal(plan2)


def test_approx_percentile_rejects_strings():
    from spark_rapids_tpu.expr.aggregates import ApproxPercentile
    src = HostBatchSourceExec([gen_table([StringGen()], 10, 1,
                                         names=["s"])])
    plan = TpuHashAggregateExec(
        [], [Alias(ApproxPercentile(col("s"), 0.5), "p")], src)
    from spark_rapids_tpu.planner import TpuOverrides
    pp = TpuOverrides().apply(plan)
    assert pp.fallback_nodes(), "string percentile must fall back"


# --- mergeable percentile sketch (VERDICT r4 #6) ---------------------------

def _sketch_conf():
    from spark_rapids_tpu.config import RapidsConf
    return RapidsConf({"spark.rapids.sql.approxPercentile.exact":
                       "false"})


def _rank_error(got, data, p):
    """|rank(got) - p*n| / n, with rank = count of values <= got."""
    import numpy as np
    d = np.sort(np.asarray([v for v in data if v is not None]))
    n = len(d)
    lo = np.searchsorted(d, got, side="left")
    hi = np.searchsorted(d, got, side="right")
    target = max(int(np.ceil(p * n)) - 1, 0)
    if lo <= target < hi:
        return 0.0
    return min(abs(lo - target), abs(hi - 1 - target)) / max(n, 1)


def test_approx_percentile_mergeable_multibatch_rank_bound():
    """Sketch mode: percentile partials/merges across MANY batches; the
    result's rank error stays within the summary's bound (~2/K with one
    merge level)."""
    import numpy as np
    from spark_rapids_tpu.exec.base import ExecCtx
    from spark_rapids_tpu.expr.aggregates import ApproxPercentile
    from spark_rapids_tpu.columnar.arrow_bridge import device_to_arrow
    import pyarrow as pa
    rng = np.random.default_rng(11)
    # 8 batches, skewed distribution, 2 group keys
    batches, all_vals = [], {0: [], 1: []}
    for b in range(8):
        k = rng.integers(0, 2, 500).astype(np.int32)
        v = (rng.lognormal(0, 2, 500) * 100).astype(np.int64)
        for kk, vv in zip(k, v):
            all_vals[int(kk)].append(int(vv))
        batches.append(pa.record_batch({"k": pa.array(k),
                                        "v": pa.array(v)}))
    src = HostBatchSourceExec(batches)
    agg = ApproxPercentile(col("v"), [0.1, 0.5, 0.9, 0.99])
    plan = TpuHashAggregateExec([col("k")], [Alias(agg, "p")], src)
    ctx = ExecCtx(_sketch_conf())
    outs = [device_to_arrow(b) for b in plan.execute(ctx)]
    t = pa.Table.from_batches(outs).to_pydict()
    assert sorted(t["k"]) == [0, 1]
    bound = 2.5 / agg.K  # one merge level + evaluate snap
    for kk, plist in zip(t["k"], t["p"]):
        for p, got in zip(agg.percentages, plist):
            err = _rank_error(got, all_vals[kk], p)
            assert err <= bound, (kk, p, got, err, bound)
            # sketch points are actual data values, never interpolated
            assert got in all_vals[kk]


def test_approx_percentile_mergeable_global_scalar():
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.exec.base import ExecCtx
    from spark_rapids_tpu.expr.aggregates import ApproxPercentile
    from spark_rapids_tpu.columnar.arrow_bridge import device_to_arrow
    rng = np.random.default_rng(5)
    data = rng.normal(0, 1000, 3000)
    batches = [pa.record_batch({"v": pa.array(data[i::3])})
               for i in range(3)]
    agg = ApproxPercentile(col("v"), 0.5)
    plan = TpuHashAggregateExec([], [Alias(agg, "p")],
                                HostBatchSourceExec(batches))
    ctx = ExecCtx(_sketch_conf())
    outs = [device_to_arrow(b) for b in plan.execute(ctx)]
    got = outs[0].column("p")[0].as_py()
    assert _rank_error(got, list(data), 0.5) <= 2.5 / agg.K


def test_approx_percentile_sketch_exact_when_small():
    """n <= K per group: the summary holds every value, so even the
    sketch path reproduces the exact Spark rank answer."""
    import pyarrow as pa
    from spark_rapids_tpu.exec.base import ExecCtx
    from spark_rapids_tpu.expr.aggregates import ApproxPercentile
    from spark_rapids_tpu.columnar.arrow_bridge import device_to_arrow
    vals = [5, 1, 9, 3, 7, None, 2]
    rb = pa.record_batch({"v": pa.array(vals, pa.int64())})
    agg = ApproxPercentile(col("v"), [0.0, 0.5, 1.0])
    plan = TpuHashAggregateExec([], [Alias(agg, "p")],
                                HostBatchSourceExec([rb]))
    ctx = ExecCtx(_sketch_conf())
    outs = [device_to_arrow(b) for b in plan.execute(ctx)]
    assert outs[0].column("p")[0].as_py() == [1, 3, 9]
