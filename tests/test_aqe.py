"""AQE tests: stats-driven partition coalescing and skew splitting at
the materialized shuffle stage boundary (reference: AQE integration +
GpuShuffleCoalesceExec / skew join handling — SURVEY.md:161, 228)."""
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.exec import HostBatchSourceExec
from spark_rapids_tpu.exec.aqe import (TpuAQEShuffleReadExec,
                                       plan_partition_groups)
from spark_rapids_tpu.exec.base import ExecCtx, collect_arrow_cpu
from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
from spark_rapids_tpu.expr import UnresolvedColumn as col
from spark_rapids_tpu.planner import TpuOverrides
from spark_rapids_tpu.shuffle import HashPartitioning

from data_gen import IntegerGen, LongGen, StringGen, gen_table


# --- pure planning --------------------------------------------------------

def test_plan_groups_coalesces_small_runs():
    stats = [10, 10, 10, 100, 10, 10]
    groups = plan_partition_groups(stats, advisory=35, skew_factor=50,
                                   skew_threshold=1 << 40, coalesce=True)
    flat = [p for _, ms in groups for p in ms]
    assert flat == list(range(6))  # order preserved, nothing dropped
    assert ("coalesced", [0, 1, 2]) in groups
    assert ("coalesced", [4, 5]) in groups


def test_plan_groups_detects_skew():
    stats = [10, 10, 1000, 10]
    groups = plan_partition_groups(stats, advisory=50, skew_factor=5,
                                   skew_threshold=100, coalesce=True)
    kinds = {tuple(ms): k for k, ms in groups}
    assert kinds[(2,)] == "skewed"
    flat = [p for _, ms in groups for p in ms]
    assert flat == [0, 1, 2, 3]


def test_plan_groups_no_coalesce_flag():
    groups = plan_partition_groups([1, 1, 1], advisory=100, skew_factor=5,
                                   skew_threshold=1 << 40, coalesce=False)
    assert all(k == "plain" and len(ms) == 1 for k, ms in groups)


def test_plan_groups_empty_and_zero():
    assert plan_partition_groups([], 10, 5, 100, True) == []
    groups = plan_partition_groups([0, 0], 10, 5, 100, True)
    assert [p for _, ms in groups for p in ms] == [0, 1]


# --- end-to-end through the planner ---------------------------------------

def _skewed_source(n=4000, hot_frac=0.8, seed=7):
    """90% of rows share one key -> one hot partition."""
    rng = np.random.default_rng(seed)
    hot = rng.random(n) < hot_frac
    keys = np.where(hot, 3, rng.integers(0, 64, n)).astype(np.int32)
    vals = rng.integers(0, 10**6, n).astype(np.int64)
    rb = pa.record_batch({"k": pa.array(keys), "v": pa.array(vals)})
    return HostBatchSourceExec([rb])


def _aqe_conf(**extra):
    base = {
        "spark.sql.adaptive.enabled": "true",
        # let the local transport sync for exact stats
        "spark.rapids.sql.adaptive.freeStatsOnly": "false",
        # tiny thresholds so test-sized data triggers both paths
        "spark.sql.adaptive.advisoryPartitionSizeInBytes": "4096",
        "spark.sql.adaptive.skewJoin.skewedPartitionThresholdInBytes":
            "4096",
    }
    base.update(extra)
    return RapidsConf(base)


def test_aqe_inserted_by_planner_and_results_correct():
    conf = _aqe_conf()
    ex = TpuShuffleExchangeExec(HashPartitioning([col("k")], 8),
                                _skewed_source())
    from spark_rapids_tpu.exec.basic import TpuProjectExec
    from spark_rapids_tpu.expr import Alias, Add, Literal
    from spark_rapids_tpu import datatypes as dt
    top = TpuProjectExec([Alias(Add(col("v"), Literal(1, dt.INT64)),
                                "v1")], ex)
    plan = TpuOverrides(conf).apply(top)
    reader = plan.root.children[0]
    assert isinstance(reader, TpuAQEShuffleReadExec), plan.root
    got = plan.collect().to_pandas().sort_values("v1").reset_index(
        drop=True)
    want = collect_arrow_cpu(top).to_pandas().sort_values(
        "v1").reset_index(drop=True)
    import pandas.testing as pdt
    pdt.assert_frame_equal(got, want, check_dtype=False)
    kinds = [k for k, _ in reader.last_groups]
    assert "skewed" in kinds, reader.last_groups
    assert "coalesced" in kinds, reader.last_groups


def test_aqe_skew_split_bounds_batch_bytes():
    conf = _aqe_conf()
    ex = TpuShuffleExchangeExec(HashPartitioning([col("k")], 8),
                                _skewed_source())
    reader = TpuAQEShuffleReadExec(ex)
    ctx = ExecCtx(conf)
    batches = list(reader.execute(ctx))
    advisory = 4096
    skew = ctx.metrics[reader.node_label()]["numSkewSplits"].value
    assert skew > 0
    # skewed pieces were capacity-halved under the advisory byte bound
    # (plain/coalesced views keep the shared map-batch capacity)
    assert min(b.device_size_bytes() for b in batches) <= advisory
    # no rows lost or duplicated across the split/coalesce reshaping
    from spark_rapids_tpu.columnar.arrow_bridge import device_to_arrow
    got = sorted(v for b in batches
                 for v in device_to_arrow(b).column("v").to_pylist())
    want = sorted(v for rb in collect_arrow_cpu(ex).to_batches()
                  for v in rb.column(1).to_pylist())
    assert got == want


def test_aqe_disabled_no_reader():
    ex = TpuShuffleExchangeExec(HashPartitioning([col("k")], 4),
                                _skewed_source(500))
    from spark_rapids_tpu.exec.basic import TpuFilterExec
    from spark_rapids_tpu.expr import GreaterThan, Literal
    from spark_rapids_tpu import datatypes as dt
    top = TpuFilterExec(GreaterThan(col("v"), Literal(0, dt.INT64)), ex)
    plan = TpuOverrides(RapidsConf(
        {"spark.sql.adaptive.enabled": "false"})).apply(top)
    assert not isinstance(plan.root.children[0], TpuAQEShuffleReadExec)


def test_aqe_default_on_free_stats_engage_local():
    """AQE defaults ON and the local transport now records writer-side
    partition stats during the map phase, so the adaptive reader
    ENGAGES on the default path under freeStatsOnly (ROADMAP item 4:
    adaptivity on the default path with zero read-side syncs)."""
    ex = TpuShuffleExchangeExec(HashPartitioning([col("k")], 4),
                                _skewed_source(500))
    from spark_rapids_tpu.exec.basic import TpuFilterExec
    from spark_rapids_tpu.expr import GreaterThan, Literal
    from spark_rapids_tpu import datatypes as dt
    top = TpuFilterExec(GreaterThan(col("v"), Literal(-1, dt.INT64)), ex)
    plan = TpuOverrides(RapidsConf()).apply(top)
    reader = plan.root.children[0]
    assert isinstance(reader, TpuAQEShuffleReadExec)
    got = plan.collect()
    # writer-side stats were served: the reader planned groups
    assert reader.last_groups is not None
    assert [p for _, ms in reader.last_groups for p in ms] == [0, 1, 2, 3]
    want = collect_arrow_cpu(top)
    assert sorted(got.column("v").to_pylist()) == \
        sorted(want.column("v").to_pylist())


def test_aqe_local_free_stats_skew_and_coalesce():
    """The skewed source through the LOCAL transport with tiny
    thresholds: writer-side stats alone (freeStatsOnly left at the
    default TRUE) must be enough for both skew split and coalesce to
    fire."""
    conf = _aqe_conf()
    conf.set("spark.rapids.sql.adaptive.freeStatsOnly", "true")
    ex = TpuShuffleExchangeExec(HashPartitioning([col("k")], 8),
                                _skewed_source())
    reader = TpuAQEShuffleReadExec(ex)
    ctx = ExecCtx(conf)
    batches = list(reader.execute(ctx))
    kinds = {k for k, _ in reader.last_groups}
    assert "skewed" in kinds and "coalesced" in kinds, reader.last_groups
    from spark_rapids_tpu.columnar.arrow_bridge import device_to_arrow
    got = sorted(v for b in batches
                 for v in device_to_arrow(b).column("v").to_pylist())
    want = sorted(v for rb in collect_arrow_cpu(ex).to_batches()
                  for v in rb.column(1).to_pylist())
    assert got == want


def test_aqe_local_stats_off_without_adaptive():
    """With AQE disabled the exchange never enables writer-side
    recording, so a later free-stats probe reports None (no silent
    write-path overhead when nobody will read the stats)."""
    conf = RapidsConf({"spark.sql.adaptive.enabled": "false"})
    ex = TpuShuffleExchangeExec(HashPartitioning([col("k")], 4),
                                _skewed_source(400))
    ctx = ExecCtx(conf)
    handle = ex.materialize(ctx)
    try:
        assert handle.partition_stats(free_only=True) is None
    finally:
        handle.close()


def test_aqe_host_transport_free_stats_no_device_touch(monkeypatch):
    """The host transport's writer-side byte counts serve
    partition_stats(free_only=True) WITHOUT touching device memory or
    syncing: assert by making every device readback explode during the
    stats call, then check coalesce/skew planning over those stats."""
    import jax
    from spark_rapids_tpu.shuffle.host import HostShuffleTransport
    conf = _aqe_conf()
    t = HostShuffleTransport(conf, threads=0)
    try:
        ex = TpuShuffleExchangeExec(HashPartitioning([col("k")], 8),
                                    _skewed_source(), transport=t)
        ctx = ExecCtx(conf)
        handle = ex.materialize(ctx)

        def boom(*a, **k):
            raise AssertionError("free stats touched the device")
        monkeypatch.setattr(jax, "device_get", boom)
        monkeypatch.setattr(jax, "block_until_ready", boom)
        stats = handle.partition_stats(free_only=True)
        monkeypatch.undo()
        assert stats is not None and len(stats) == 8
        assert sum(stats) > 0
        # the hot partition dominates: planning over these stats splits
        groups = plan_partition_groups(stats, advisory=4096,
                                       skew_factor=5,
                                       skew_threshold=4096,
                                       coalesce=True)
        assert any(k == "skewed" for k, _ in groups), (stats, groups)
        handle.close()
    finally:
        t.close()


def test_aqe_host_transport_stats_via_reader():
    """End to end: exchange on the HOST transport + adaptive reader
    under default freeStatsOnly — stats engage, rows exact."""
    from spark_rapids_tpu.shuffle.host import HostShuffleTransport
    conf = _aqe_conf()
    conf.set("spark.rapids.sql.adaptive.freeStatsOnly", "true")
    t = HostShuffleTransport(conf, threads=0)
    try:
        ex = TpuShuffleExchangeExec(HashPartitioning([col("k")], 8),
                                    _skewed_source(), transport=t)
        reader = TpuAQEShuffleReadExec(ex)
        ctx = ExecCtx(conf)
        batches = list(reader.execute(ctx))
        kinds = {k for k, _ in reader.last_groups}
        assert "skewed" in kinds, reader.last_groups
        from spark_rapids_tpu.columnar.arrow_bridge import device_to_arrow
        got = sorted(v for b in batches
                     for v in device_to_arrow(b).column("v").to_pylist())
        want = sorted(v for rb in collect_arrow_cpu(ex).to_batches()
                      for v in rb.column(1).to_pylist())
        assert got == want
    finally:
        t.close()


# --- runtime join-strategy switch (VERDICT r4 #4) --------------------------

def _join_with_exchanges(n_stream=3000, n_build=50, nparts=4,
                         two_batches=False):
    from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
    rng = np.random.default_rng(3)
    fact = pa.record_batch({
        "fk": pa.array(rng.integers(0, n_build, n_stream)
                       .astype(np.int32)),
        "amt": pa.array(rng.integers(0, 1000, n_stream)
                        .astype(np.int64))})
    dim = pa.record_batch({
        "dk": pa.array(np.arange(n_build, dtype=np.int32)),
        "dv": pa.array(np.arange(n_build, dtype=np.int64) * 7)})
    fsrc = HostBatchSourceExec([fact.slice(0, n_stream // 2),
                                fact.slice(n_stream // 2)]
                               if two_batches else [fact])
    dsrc = HostBatchSourceExec([dim])
    lex = TpuShuffleExchangeExec(HashPartitioning([col("fk")], nparts),
                                 fsrc)
    rex = TpuShuffleExchangeExec(HashPartitioning([col("dk")], nparts),
                                 dsrc)
    return TpuShuffledHashJoinExec([col("fk")], [col("dk")], "inner",
                                   lex, rex)


def test_aqe_join_demotes_to_broadcast():
    """Small build side -> the shuffled join re-plans to broadcast at
    runtime: the stream-side exchange is skipped, results unchanged."""
    from spark_rapids_tpu.exec.aqe import TpuAQEJoinExec
    join = _join_with_exchanges()
    plan = TpuOverrides(RapidsConf()).apply(join)
    assert isinstance(plan.root, TpuAQEJoinExec), plan.root
    got = plan.collect()
    assert plan.root.last_strategy == "broadcast"
    m = plan.last_ctx.metrics[plan.root.node_label()]
    assert m["numBroadcastDemotions"].value == 1
    want = collect_arrow_cpu(join)
    assert sorted(tuple(d.values()) for d in got.to_pylist()) == \
        sorted(tuple(d.values()) for d in want.to_pylist())


def test_aqe_join_keeps_shuffled_over_threshold():
    from spark_rapids_tpu.exec.aqe import TpuAQEJoinExec
    join = _join_with_exchanges()
    conf = RapidsConf({"spark.sql.autoBroadcastJoinThreshold": "1"})
    plan = TpuOverrides(conf).apply(join)
    assert isinstance(plan.root, TpuAQEJoinExec)
    got = plan.collect()
    assert plan.root.last_strategy == "shuffled"
    want = collect_arrow_cpu(join)
    assert sorted(tuple(d.values()) for d in got.to_pylist()) == \
        sorted(tuple(d.values()) for d in want.to_pylist())


def test_aqe_exchange_reuse_self_join():
    """The SAME exchange instance consumed by both join sides
    materializes once (ReusedExchangeExec analog): the transport sees
    one shuffle id; results match the oracle."""
    from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
    rng = np.random.default_rng(4)
    rb = pa.record_batch({
        "k": pa.array(np.arange(40, dtype=np.int32)),
        "v": pa.array(rng.integers(0, 100, 40).astype(np.int64))})
    src = HostBatchSourceExec([rb])
    ex = TpuShuffleExchangeExec(HashPartitioning([col("k")], 4), src)
    join = TpuShuffledHashJoinExec([col("k")], [col("k")], "inner",
                                   ex, ex)
    plan = TpuOverrides(RapidsConf()).apply(join)
    assert ex.shared, "planner must flag the doubly-consumed exchange"
    calls = []
    orig = TpuShuffleExchangeExec.materialize

    def counting(self, ctx):
        calls.append(1)
        return orig(self, ctx)
    TpuShuffleExchangeExec.materialize = counting
    try:
        got = plan.collect()
    finally:
        TpuShuffleExchangeExec.materialize = orig
    assert len(calls) == 1, "shared exchange must materialize once"
    want = collect_arrow_cpu(join)
    assert sorted(tuple(d.values()) for d in got.to_pylist()) == \
        sorted(tuple(d.values()) for d in want.to_pylist())


def test_aqe_passthrough_without_stats():
    class NoStatsExchange(TpuShuffleExchangeExec):
        def materialize(self, ctx):
            h = super().materialize(ctx)
            h.transport = _NoStats(h.transport)
            return h

    class _NoStats:
        def __init__(self, inner):
            self._inner = inner

        def read_partition(self, sid, p):
            return self._inner.read_partition(sid, p)

        def unregister_shuffle(self, sid):
            return self._inner.unregister_shuffle(sid)

    ex = NoStatsExchange(HashPartitioning([col("k")], 4),
                         _skewed_source(600))
    reader = TpuAQEShuffleReadExec(ex)
    ctx = ExecCtx(_aqe_conf())
    from spark_rapids_tpu.columnar.arrow_bridge import device_to_arrow
    got = sorted(v for b in reader.execute(ctx)
                 for v in device_to_arrow(b).column("v").to_pylist())
    want = sorted(v for rb in collect_arrow_cpu(ex).to_batches()
                  for v in rb.column(1).to_pylist())
    assert got == want
    assert reader.last_groups is None  # passthrough path
