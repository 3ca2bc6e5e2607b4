"""Sort / TopN / limit operator tests via the dual-run harness
(reference: sort_test.py, limit_test.py — SURVEY.md §4.1)."""
import pyarrow as pa
import pytest

from spark_rapids_tpu import datatypes as dt
from spark_rapids_tpu.exec import (HostBatchSourceExec, TpuProjectExec)
from spark_rapids_tpu.exec.sort import (SortOrder, TpuGlobalLimitExec,
                                        TpuLocalLimitExec, TpuSortExec,
                                        TpuTopNExec)
from spark_rapids_tpu.expr import Alias, UnresolvedColumn as col

from asserts import assert_tpu_and_cpu_plan_equal
from data_gen import (BooleanGen, ByteGen, DateGen, DecimalGen, DoubleGen,
                      FloatGen, IntegerGen, LongGen, ShortGen, StringGen,
                      TimestampGen, gen_table)


def source(gens, n=256, seed=1234, names=None):
    return HostBatchSourceExec([gen_table(gens, n, seed, names)])


sortable_gens = [ByteGen(), ShortGen(), IntegerGen(), LongGen(),
                 FloatGen(dt.FLOAT32), DoubleGen(), BooleanGen(),
                 StringGen(), DateGen(), TimestampGen(), DecimalGen()]


@pytest.mark.parametrize("gen", sortable_gens,
                         ids=lambda g: g.dtype.simple_string())
@pytest.mark.parametrize("asc", [True, False])
def test_sort_single_key(gen, asc):
    # c1 tie-break makes the expected order total (stability-independent).
    plan = TpuSortExec(
        [SortOrder(col("c0"), ascending=asc),
         SortOrder(col("c1"))],
        source([gen, LongGen(nullable=False)]))
    assert_tpu_and_cpu_plan_equal(plan)


@pytest.mark.parametrize("nulls_first", [True, False])
def test_sort_null_placement(nulls_first):
    plan = TpuSortExec(
        [SortOrder(col("c0"), ascending=True, nulls_first=nulls_first),
         SortOrder(col("c1"))],
        source([IntegerGen(null_frac=0.3), LongGen(nullable=False)]))
    assert_tpu_and_cpu_plan_equal(plan)


def test_sort_multi_key_mixed_directions():
    plan = TpuSortExec(
        [SortOrder(col("c0"), ascending=False),
         SortOrder(col("c1"), ascending=True, nulls_first=False),
         SortOrder(col("c2"))],
        source([IntegerGen(min_val=0, max_val=5), StringGen(max_len=4),
                LongGen(nullable=False)]))
    assert_tpu_and_cpu_plan_equal(plan)


def test_sort_strings_long():
    # strings longer than one 7-byte refinement window, with shared prefixes
    plan = TpuSortExec(
        [SortOrder(col("c0")), SortOrder(col("c1"))],
        source([StringGen(max_len=40, charset="ab"),
                LongGen(nullable=False)]))
    assert_tpu_and_cpu_plan_equal(plan)


def test_sort_float_specials():
    # NaN sorts largest; -0.0 ties 0.0 (broken by c1)
    plan = TpuSortExec(
        [SortOrder(col("c0")), SortOrder(col("c1"))],
        source([DoubleGen(null_frac=0.2), LongGen(nullable=False)]))
    assert_tpu_and_cpu_plan_equal(plan)
    plan = TpuSortExec(
        [SortOrder(col("c0"), ascending=False), SortOrder(col("c1"))],
        source([DoubleGen(null_frac=0.2), LongGen(nullable=False)]))
    assert_tpu_and_cpu_plan_equal(plan)


def test_sort_global_multi_batch():
    rbs = [gen_table([IntegerGen(), LongGen(nullable=False)], n, seed=s)
           for n, s in [(100, 1), (57, 2), (300, 3)]]
    plan = TpuSortExec(
        [SortOrder(col("c0")), SortOrder(col("c1"))],
        HostBatchSourceExec(rbs))
    assert_tpu_and_cpu_plan_equal(plan)


def test_sort_local_per_batch():
    rbs = [gen_table([IntegerGen(nullable=False),
                      LongGen(nullable=False)], n, seed=s)
           for n, s in [(64, 1), (32, 2)]]
    plan = TpuSortExec([SortOrder(col("c0")), SortOrder(col("c1"))],
                       HostBatchSourceExec(rbs), global_sort=False)
    assert_tpu_and_cpu_plan_equal(plan)


def test_sort_strings_multi_batch_concat():
    rbs = [gen_table([StringGen(max_len=12), LongGen(nullable=False)],
                     n, seed=s) for n, s in [(80, 4), (120, 5)]]
    plan = TpuSortExec([SortOrder(col("c0")), SortOrder(col("c1"))],
                       HostBatchSourceExec(rbs))
    assert_tpu_and_cpu_plan_equal(plan)


def test_local_limit():
    rbs = [gen_table([IntegerGen(), StringGen()], n, seed=s)
           for n, s in [(100, 1), (100, 2), (100, 3)]]
    for lim in (0, 50, 100, 150, 299, 300, 500):
        plan = TpuLocalLimitExec(lim, HostBatchSourceExec(rbs))
        assert_tpu_and_cpu_plan_equal(plan, label=f"limit {lim}")


def test_local_limit_early_exit():
    """LIMIT n over a long stream stops pulling the child after the
    periodic counter sync confirms the limit is reached (ADVICE r4: the
    sync-free path did O(input) work)."""
    from spark_rapids_tpu.exec.base import ExecCtx

    rbs = [gen_table([IntegerGen(nullable=False)], 100, seed=s)
           for s in range(64)]
    pulled = []

    class CountingSource(HostBatchSourceExec):
        def execute(self, ctx):
            for i, b in enumerate(super().execute(ctx)):
                pulled.append(i)
                yield b

    plan = TpuLocalLimitExec(50, CountingSource(rbs))
    list(plan.execute(ExecCtx()))
    # limit hit in batch 0; the every-8-batches sync must break the
    # stream well before all 64 batches are decoded/uploaded
    assert len(pulled) <= TpuLocalLimitExec._SYNC_EVERY
    pulled.clear()
    assert_tpu_and_cpu_plan_equal(
        TpuLocalLimitExec(50, CountingSource(rbs)), label="early-exit")


def test_topn():
    plan = TpuTopNExec(
        10, [SortOrder(col("c0"), ascending=False), SortOrder(col("c2"))],
        source([IntegerGen(), StringGen(), LongGen(nullable=False)]))
    assert_tpu_and_cpu_plan_equal(plan)


def test_topn_with_project():
    plan = TpuTopNExec(
        7, [SortOrder(col("c0")), SortOrder(col("c2"))],
        source([IntegerGen(), StringGen(), LongGen(nullable=False)]),
        project=[col("c1"), Alias(col("c0"), "k")])
    assert_tpu_and_cpu_plan_equal(plan)


def test_limit_after_sort():
    plan = TpuGlobalLimitExec(
        25, TpuSortExec([SortOrder(col("c0")), SortOrder(col("c1"))],
                        source([DateGen(), LongGen(nullable=False)])))
    assert_tpu_and_cpu_plan_equal(plan)


def test_sort_computed_key_with_nulls():
    # Regression: computed keys leave garbage in null rows' data lane;
    # null ordering must not depend on it.
    from spark_rapids_tpu.expr import Add
    plan = TpuSortExec(
        [SortOrder(Add(col("c0"), col("c1"))), SortOrder(col("c2"))],
        source([IntegerGen(null_frac=0.4), IntegerGen(null_frac=0.4),
                LongGen(nullable=False)]))
    assert_tpu_and_cpu_plan_equal(plan)


# --- packed sort keys and the off-CPU float64 key (PR 21) ---------------------

@pytest.mark.parametrize("seed", range(6))
def test_lex_sort_packs_lanes_without_changing_the_order(seed):
    """``lex_sort`` sorts bit-packed uint32 words instead of one operand
    per lane; permutation and boundaries must equal the plain
    lexicographic ``lax.sort`` over (lanes..., row index)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.ops.sort_keys import lex_sort
    rng = np.random.default_rng(seed)
    for _ in range(12):
        n = int(rng.choice([1, 2, 7, 64, 1000, 4096]))
        lanes, one_bit = [], []
        for i in range(int(rng.integers(1, 7))):
            kind = rng.choice(["bit", "i8", "i16", "i32", "i64"])
            if kind == "bit":
                lanes.append(rng.integers(0, 2, n).astype(np.int8))
                one_bit.append(i)
                continue
            t = {"i8": np.int8, "i16": np.int16, "i32": np.int32,
                 "i64": np.int64}[kind]
            info = np.iinfo(t)
            if rng.random() < 0.5:  # few distinct values incl. extremes
                lanes.append(rng.choice(
                    [info.min, info.max, 0, -1, 1], n).astype(t))
            else:
                lanes.append(rng.integers(info.min, info.max, n,
                                          dtype=t, endpoint=True))
        jl = [jnp.asarray(x) for x in lanes]
        perm, boundary = lex_sort(jl, one_bit)
        ref = jax.lax.sort(tuple(jl) + (jnp.arange(n, dtype=jnp.int32),),
                           num_keys=len(jl) + 1)
        assert (np.asarray(perm) == np.asarray(ref[-1])).all()
        want = np.zeros(n, bool)
        want[0] = True
        for lane in ref[:-1]:
            lane = np.asarray(lane)
            want[1:] |= lane[1:] != lane[:-1]
        assert (np.asarray(boundary) == want).all()


def test_float64_key_off_the_cpu_orders_below_float32_precision(
        monkeypatch):
    """Off the CPU float64 is a pair of float32 and f64->s64 bitcasts are
    refused, so the key is built from (hi, lo): same order as the IEEE
    key, keys 2^-40 apart stay apart, and the inverse recovers the value
    to the pair's precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu import datatypes as dt
    from spark_rapids_tpu.columnar.column import TpuColumnVector
    from spark_rapids_tpu.ops.sort_keys import (orderable_int,
                                                orderable_int_to_float)
    vals = np.array([0.0, -0.0, 1.0, 1 + 2.0 ** -40, 1 + 2.0 ** -39,
                     -1 - 2.0 ** -40, -1.0, np.inf, -np.inf, np.nan, 1e30,
                     -1e30, 3.5e-20, 0.1, 1 / 3, -0.1, 123456789.125])
    col = TpuColumnVector(dt.FLOAT64, data=jnp.asarray(vals),
                          validity=jnp.ones(len(vals), bool))
    ieee = np.asarray(orderable_int(col))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pair = orderable_int(col)
    assert pair.dtype == jnp.int64
    assert (np.argsort(ieee, kind="stable")
            == np.argsort(np.asarray(pair), kind="stable")).all()
    back = np.asarray(orderable_int_to_float(pair, jnp.float64))
    finite = np.isfinite(vals) & (vals != 0)
    assert np.max(np.abs(back[finite] - vals[finite])
                  / np.abs(vals[finite])) < 2.0 ** -44
    assert np.isnan(back[9]) and back[7] == np.inf and back[8] == -np.inf


def test_float64_min_max_off_the_cpu_branch(monkeypatch):
    """Min/Max reduce over the ordering key and map the winner back —
    through the pair key too (the old int32-bits key could not be bitcast
    back to float64 at all)."""
    import jax
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.base import HostBatchSourceExec, collect_arrow
    from spark_rapids_tpu.expr import Alias, UnresolvedColumn as col
    from spark_rapids_tpu.expr.aggregates import Max, Min
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = np.array([1 + 2.0 ** -30, 1 + 2.0 ** -31, -2.5, 7.0, 1.0, -2.5 - 2.0 ** -30])
    k = np.array([0, 0, 1, 1, 0, 1], np.int32)
    rb = pa.record_batch({"k": pa.array(k), "x": pa.array(x)})
    got = collect_arrow(TpuHashAggregateExec(
        [col("k")], [Alias(Min(col("x")), "lo"), Alias(Max(col("x")), "hi")],
        HostBatchSourceExec([rb]))).to_pandas().sort_values("k")
    assert got["lo"].tolist() == [1.0, -2.5 - 2.0 ** -30]
    assert got["hi"].tolist() == [1 + 2.0 ** -30, 7.0]
