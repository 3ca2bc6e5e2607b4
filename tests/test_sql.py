"""SQL frontend unit tests: dialect edges, error model, EXPLAIN.

Covers the satellite checklist explicitly: quoted/keyword-colliding
identifiers, operator precedence (NOT/AND/OR, unary minus), NULL-
literal typing, CTE shadowing, ambiguous-column and unknown-function
negatives asserting the named error slugs, caret-annotated parse
errors, and event-log evidence for failures."""
import json
import os

import pyarrow as pa
import pytest

from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.sql import SqlAnalysisError, SqlParseError


@pytest.fixture
def s():
    sess = TpuSession()
    sess.register_table("t", pa.table({
        "k": pa.array([1, 1, 2, 2, 3], pa.int32()),
        "v": pa.array([10, 20, 30, 40, None], pa.int64()),
        "x": pa.array([1.5, -2.5, 3.5, None, 5.5], pa.float64()),
        "name": pa.array(["apple", "banana", "cherry", "apricot",
                          None]),
    }))
    sess.register_table("d", pa.table({
        "k": pa.array([1, 2, 3], pa.int32()),
        "label": pa.array(["one", "two", "three"]),
    }))
    # a table whose column names collide with keywords
    sess.register_table("kw", pa.table({
        "order": pa.array([3, 1, 2], pa.int32()),
        "select": pa.array(["a", "b", "c"]),
    }))
    return sess


def rows(df):
    return df.collect().to_pylist()


# --- dialect edges --------------------------------------------------------

def test_quoted_keyword_identifiers(s):
    got = rows(s.sql('SELECT "order", `select` FROM kw '
                     'ORDER BY "order"'))
    assert got == [{"order": 1, "select": "b"},
                   {"order": 2, "select": "c"},
                   {"order": 3, "select": "a"}]


def test_reserved_word_unquoted_is_parse_error(s):
    with pytest.raises(SqlParseError):
        s.sql("SELECT order FROM kw")


def test_not_and_or_precedence(s):
    # NOT binds tighter than AND, AND tighter than OR:
    # a OR b AND NOT c == a OR (b AND (NOT c))
    got = rows(s.sql(
        "SELECT k FROM t WHERE k = 3 OR k = 1 AND NOT v = 20 "
        "ORDER BY k, v"))
    assert [r["k"] for r in got] == [1, 3]


def test_unary_minus_precedence(s):
    got = rows(s.sql("SELECT -2 + 3 AS a, 2 * -3 AS b, -(1 + 2) AS c"))
    assert got == [{"a": 1, "b": -6, "c": -3}]


def test_comparison_chain_and_between(s):
    got = rows(s.sql(
        "SELECT v FROM t WHERE v BETWEEN 15 AND 35 ORDER BY v"))
    assert [r["v"] for r in got] == [20, 30]
    got = rows(s.sql(
        "SELECT v FROM t WHERE v NOT BETWEEN 15 AND 35 ORDER BY v"))
    assert [r["v"] for r in got] == [10, 40]


def test_null_literal_typing(s):
    # NULL adopts the branch/sibling type instead of staying NullType
    got = s.sql("SELECT CASE WHEN v > 25 THEN NULL ELSE name END AS n, "
                "coalesce(v, NULL, -1) AS c FROM t ORDER BY k, v") \
        .collect()
    assert got.schema.field("n").type == pa.string()
    assert got.schema.field("c").type == pa.int64()
    assert got.to_pylist()[4]["c"] == -1  # v NULL -> -1


def test_null_comparisons_and_in(s):
    got = rows(s.sql("SELECT k FROM t WHERE v IS NULL"))
    assert [r["k"] for r in got] == [3]
    got = rows(s.sql(
        "SELECT k, v IN (10, 40, NULL) AS m FROM t ORDER BY k, v"))
    # null-in-list semantics: non-match -> NULL, match -> TRUE
    assert [r["m"] for r in got] == [True, None, None, True, None]


def test_cte_shadowing(s):
    # a CTE named like a catalog table shadows it...
    got = rows(s.sql(
        "WITH t AS (SELECT k + 100 AS k FROM d) "
        "SELECT k FROM t ORDER BY k"))
    assert [r["k"] for r in got] == [101, 102, 103]
    # ...and an inner WITH shadows an outer CTE of the same name
    got = rows(s.sql(
        "WITH c AS (SELECT 1 AS a), "
        "outerq AS (WITH c AS (SELECT 2 AS a) SELECT a FROM c) "
        "SELECT a FROM outerq"))
    assert got == [{"a": 2}]


def test_cte_multi_reference_and_chaining(s):
    got = rows(s.sql(
        "WITH base AS (SELECT k, v FROM t WHERE v IS NOT NULL), "
        "agg AS (SELECT k, SUM(v) AS sv FROM base GROUP BY k) "
        "SELECT a.k, a.sv, b.sv AS other "
        "FROM agg a JOIN agg b ON a.k = b.k ORDER BY a.k"))
    assert [r["sv"] for r in got] == [30, 70]
    assert [r["other"] for r in got] == [30, 70]


def test_string_ops_and_concat(s):
    got = rows(s.sql(
        "SELECT upper(name) || '!' AS u FROM t "
        "WHERE name LIKE 'ap%' ORDER BY name"))
    assert [r["u"] for r in got] == ["APPLE!", "APRICOT!"]


def test_distinct(s):
    got = rows(s.sql("SELECT DISTINCT k FROM t ORDER BY k"))
    assert [r["k"] for r in got] == [1, 2, 3]


def test_join_family(s):
    # left outer: unmatched right side is NULL
    got = rows(s.sql(
        "SELECT t.k, label FROM t LEFT JOIN d ON t.k = d.k AND "
        "d.k < 3 ORDER BY t.k, v"))
    assert [r["label"] for r in got] == ["one", "one", "two", "two",
                                        None]
    got = rows(s.sql(
        "SELECT k FROM d LEFT ANTI JOIN t ON d.k = t.k AND v >= 30 "
        "ORDER BY k"))
    assert [r["k"] for r in got] == [1, 3]


def test_order_by_expression_not_in_select(s):
    # sort key outside the output plans the sort under the projection
    got = rows(s.sql("SELECT name FROM t WHERE v IS NOT NULL "
                     "ORDER BY v DESC LIMIT 2"))
    assert [r["name"] for r in got] == ["apricot", "cherry"]


def test_group_by_position_and_alias(s):
    got = rows(s.sql("SELECT k * 10 AS kk, COUNT(*) AS n FROM t "
                     "GROUP BY 1 ORDER BY kk"))
    assert got == [{"kk": 10, "n": 2}, {"kk": 20, "n": 2},
                   {"kk": 30, "n": 1}]
    got2 = rows(s.sql("SELECT k * 10 AS kk, COUNT(*) AS n FROM t "
                      "GROUP BY kk ORDER BY kk"))
    assert got2 == got


def test_window_frame_rows(s):
    got = rows(s.sql(
        "SELECT k, v, SUM(v) OVER (ORDER BY k, v ROWS BETWEEN "
        "1 PRECEDING AND CURRENT ROW) AS rsum FROM t "
        "WHERE v IS NOT NULL ORDER BY k, v"))
    assert [r["rsum"] for r in got] == [10, 30, 50, 70]


def test_date_literal(s):
    got = rows(s.sql("SELECT DATE '2001-03-04' AS d"))
    import datetime
    assert got == [{"d": datetime.date(2001, 3, 4)}]


# --- negatives: named slugs -----------------------------------------------

def test_ambiguous_column_negative(s):
    with pytest.raises(SqlAnalysisError) as ei:
        s.sql("SELECT k FROM t JOIN d ON t.k = d.k")
    assert ei.value.slug == "sql_analysis_error"
    assert ei.value.detail == "ambiguous_column"
    assert ei.value.line > 0 and ei.value.col > 0


def test_unknown_function_negative(s):
    with pytest.raises(SqlAnalysisError) as ei:
        s.sql("SELECT frobnicate(k) FROM t")
    assert ei.value.slug == "sql_analysis_error"
    assert ei.value.detail == "unknown_function"


def test_unknown_column_negative(s):
    with pytest.raises(SqlAnalysisError) as ei:
        s.sql("SELECT nope FROM t")
    assert ei.value.detail == "unknown_column"


def test_unknown_table_negative(s):
    with pytest.raises(SqlAnalysisError) as ei:
        s.sql("SELECT 1 FROM missing_table")
    assert ei.value.detail == "unknown_table"


def test_missing_aggregation_negative(s):
    with pytest.raises(SqlAnalysisError) as ei:
        s.sql("SELECT v, COUNT(*) FROM t GROUP BY k")
    assert ei.value.detail == "missing_aggregation"


def test_aggregate_in_where_negative(s):
    with pytest.raises(SqlAnalysisError) as ei:
        s.sql("SELECT k FROM t WHERE SUM(v) > 10")
    assert ei.value.detail == "misplaced_aggregate"


def test_count_distinct_unsupported(s):
    with pytest.raises(SqlAnalysisError) as ei:
        s.sql("SELECT COUNT(DISTINCT k) FROM t")
    assert ei.value.detail == "unsupported_feature"


def test_join_without_on_is_parse_error(s):
    # a forgotten ON must not silently become a cartesian product
    for q in ("SELECT t.k, label FROM t JOIN d",
              "SELECT t.k FROM t LEFT JOIN d",
              "SELECT t.k FROM t LEFT SEMI JOIN d"):
        with pytest.raises(SqlParseError, match="ON clause"):
            s.sql(q)
    # explicit cartesian product still available
    assert s.sql("SELECT t.k FROM t CROSS JOIN d").count() == 15


def test_malformed_hint_anchored_to_statement(s):
    with pytest.raises(SqlParseError) as ei:
        s.sql("SELECT /*+ UNIQUE(;) */ k\nFROM t")
    # location points at the hint token in the REAL statement, not
    # into the hint-body substring
    assert ei.value.line == 1 and ei.value.col == 8
    assert "malformed hint" in str(ei.value)


def test_parse_error_carries_caret_snippet(s):
    with pytest.raises(SqlParseError) as ei:
        s.sql("SELECT k\nFROM t\nWHERE k >")
    e = ei.value
    assert e.slug == "sql_parse_error"
    assert e.line == 3
    assert "^" in str(e) and "WHERE k >" in str(e)


def test_type_error_has_location(s):
    with pytest.raises(SqlAnalysisError) as ei:
        s.sql("SELECT k FROM t WHERE name > 5")
    assert ei.value.detail == "type_error"


# --- error evidence + EXPLAIN ---------------------------------------------

def test_sql_errors_logged_to_event_log(tmp_path):
    sess = TpuSession(conf={"spark.rapids.eventLog.dir": str(tmp_path)})
    sess.register_table("t", pa.table({"a": pa.array([1])}))
    with pytest.raises(SqlParseError):
        sess.sql("SELEKT 1")
    with pytest.raises(SqlAnalysisError):
        sess.sql("SELECT missing FROM t")
    events = []
    for fn in os.listdir(tmp_path):
        with open(os.path.join(tmp_path, fn)) as f:
            events += [json.loads(ln) for ln in f if ln.strip()]
    kinds = sorted(e["type"] for e in events)
    assert kinds == ["sql_analysis_error", "sql_parse_error"]
    par = next(e for e in events if e["type"] == "sql_parse_error")
    assert par["line"] == 1
    ana = next(e for e in events if e["type"] == "sql_analysis_error")
    assert ana["detail"] == "unknown_column"
    assert ana["line"] == 1 and ana["col"] > 0
    assert "^" in ana["snippet"]
    assert "missing" in ana["sql"]


def test_explain_returns_plan_text_without_executing(s):
    text = s.sql("EXPLAIN SELECT k, SUM(v) AS sv FROM t GROUP BY k")
    assert isinstance(text, str)
    assert "will run on TPU" in text
    assert "HashAggregateExec" in text
    fmt = s.sql("EXPLAIN FORMATTED SELECT k FROM t ORDER BY k")
    assert isinstance(fmt, str)
    assert "SortExec" in fmt and "ProjectExec" in fmt


def test_sql_plans_flow_through_verifier(s):
    # SQL-originated plans hit the same pre-execution contract pass
    from spark_rapids_tpu.planner import TpuOverrides
    df = s.sql("SELECT t.k, label, SUM(v) AS sv FROM t "
               "JOIN d ON t.k = d.k GROUP BY t.k, label")
    pp = TpuOverrides(s.conf).apply(df._node)
    assert not pp.fallback_nodes()


def test_union_type_widening(s):
    got = s.sql("SELECT k FROM t UNION ALL SELECT v FROM t "
                "WHERE v IS NOT NULL ORDER BY 1").collect()
    assert got.schema.field("k").type == pa.int64()
    assert len(got) == 9


def test_hints_parse_and_are_inert_when_unknown(s):
    got = rows(s.sql("SELECT /*+ BROADCAST(d) */ t.k, label FROM t "
                     "JOIN d ON t.k = d.k WHERE v = 10"))
    assert got == [{"k": 1, "label": "one"}]
