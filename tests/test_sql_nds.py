"""NDS corpus from SQL text: every SQL_QUERIES entry compiles through
``session.sql`` and dual-runs row-for-row equal to its hand-built
Python plan (the acceptance bar for the SQL frontend: the corpus stops
being a transcription and becomes the real thing)."""
import numpy as np
import pytest

from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.tools.nds import (QUERIES, SQL_QUERIES,
                                        build_query, build_query_sql,
                                        gen_tables)

TABLES = gen_tables(n_sales=1 << 14)


def _assert_frames_equal(got, want, name):
    assert list(got.columns) == list(want.columns), \
        (name, got.columns, want.columns)
    assert len(got) == len(want), (name, len(got), len(want))
    for c in got.columns:
        g = got[c].to_numpy()
        w = want[c].to_numpy()
        if np.issubdtype(np.asarray(w).dtype, np.floating):
            # device float aggregation across differing plan shapes can
            # reassociate; row ORDER must still match exactly
            assert np.allclose(g.astype(float), w.astype(float),
                               rtol=1e-9, atol=1e-9, equal_nan=True), \
                (name, c)
        else:
            assert (g == w).all(), (name, c)


def test_sql_corpus_is_complete():
    # every hand-built corpus query has a SQL text and vice versa, and
    # the corpus satisfies the >= 20-query acceptance bar
    assert set(SQL_QUERIES) == set(QUERIES)
    assert len(SQL_QUERIES) >= 20


@pytest.mark.parametrize("name", sorted(SQL_QUERIES))
def test_sql_dual_runs_hand_built(name):
    s = TpuSession()
    hand = build_query(name, s, TABLES).collect().to_pandas()
    sql = build_query_sql(name, s, TABLES).collect().to_pandas()
    _assert_frames_equal(sql.reset_index(drop=True),
                         hand.reset_index(drop=True), name)


@pytest.mark.parametrize("name", sorted(SQL_QUERIES))
def test_sql_corpus_plans_fully_on_device(name):
    # zero unexpected fallbacks: SQL-originated plans place every
    # operator on TPU exactly like the hand-built ones
    from spark_rapids_tpu.planner import TpuOverrides
    s = TpuSession()
    df = build_query_sql(name, s, TABLES)
    pp = TpuOverrides(s.conf).apply(df._node)
    assert not pp.fallback_nodes(), \
        f"{name}: {pp.explain('NOT_ON_GPU')}"


def test_sql_q3_on_the_process_cluster_equals_the_local_run():
    """A SQL-built plan crosses to OS worker processes whole: q3 (two
    joins, group-by, order-by, limit) on a 2-worker cluster returns the
    rows of the in-process run, which the dual runs hold to the
    oracle. One shuffle partition keeps the sort + limit global."""
    from spark_rapids_tpu.cluster import TpuProcessCluster
    s = TpuSession(conf={"spark.sql.shuffle.partitions": "1"})
    df = build_query_sql("q3", s, TABLES)
    with TpuProcessCluster(n_workers=2) as c:
        got = c.run_query(df._node).to_pandas()
    _assert_frames_equal(got.reset_index(drop=True),
                         df.collect().to_pandas().reset_index(drop=True),
                         "q3")


def test_sql_corpus_explains():
    # EXPLAIN over a corpus text returns plan text without executing
    s = TpuSession()
    from spark_rapids_tpu.tools import nds as _nds
    _nds._frames(s, TABLES)
    text = s.sql("EXPLAIN " + SQL_QUERIES["q3"])
    assert "will run on TPU" in text
