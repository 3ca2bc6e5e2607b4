"""Required-column pushdown (exec/pruning.py): every query answered three
ways — the engine with the pass, the engine with the pass replaced by the
identity, and a plain pandas/numpy computation that shares nothing with
the engine — equal row for row (DOUBLE sums to 1e-12 relative, keys
exact). Beside the answers each case asserts the SHAPE of the pruned
plan: what the scans read, how wide the joins come out, and that the
static verifier finds nothing (a stale ordinal is a rejected plan, never
a wrong answer)."""
import importlib.util
import json
import math
import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.analysis.plan_verifier import verify_plan
from spark_rapids_tpu.exec import pruning
from spark_rapids_tpu.exec.joins import _BaseJoinExec
from spark_rapids_tpu.io import TpuFileScanExec
from spark_rapids_tpu.planner import TpuOverrides
from spark_rapids_tpu.session import TpuSession

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CONF = {"spark.sql.shuffle.partitions": "1"}


# --- seeded tables ----------------------------------------------------------

def _fact(rng, n=3000):
    k = rng.integers(0, 260, n)
    return pd.DataFrame({
        "k": k.astype(np.int64),
        "g": rng.integers(0, 7, n).astype(np.int32),
        "s": rng.choice(["ant", "bee", "cat", "dog"], n),
        "x": np.round(rng.uniform(0, 100, n), 2),
        "y": np.round(rng.uniform(0, 10, n), 2),
        "pad_text": rng.choice(["lorem ipsum dolor", "sit amet", ""], n),
        "pad_int": rng.integers(0, 1 << 40, n).astype(np.int64),
        "pad_dbl": rng.uniform(0, 1, n)})


def _dim(rng):
    k = np.concatenate([np.arange(0, 200), np.arange(300, 340)])
    n = len(k)
    return pd.DataFrame({
        "k": k.astype(np.int64),
        "name": [f"name{v % 37}" for v in k],
        "w": np.round(rng.uniform(0, 100, n), 2),
        "flag": rng.integers(0, 3, n).astype(np.int32),
        "pad_a": rng.choice(["alpha", "beta"], n),
        "pad_b": rng.integers(0, 99, n).astype(np.int32)})


@pytest.fixture(scope="module")
def tables():
    rng = np.random.default_rng(20261002)
    return {"fact": _fact(rng), "dim": _dim(rng)}


@pytest.fixture(scope="module")
def files(tables, tmp_path_factory):
    """The same tables as Parquet: ``fact`` in two files, and once more
    partitioned by ``g`` (hive layout, so ``g`` is a partition-value
    column) with a list column, which the device decoder leaves to the
    host (a host-fallback column)."""
    root = tmp_path_factory.mktemp("pruning")
    fact, dim = tables["fact"], tables["dim"]
    paths = {"fact": [], "dim": [str(root / "dim.parquet")],
             "part": []}
    half = len(fact) // 2
    for i, part in enumerate((fact.iloc[:half], fact.iloc[half:])):
        p = str(root / f"fact{i}.parquet")
        pq.write_table(pa.Table.from_pandas(part, preserve_index=False), p)
        paths["fact"].append(p)
    pq.write_table(pa.Table.from_pandas(dim, preserve_index=False),
                   paths["dim"][0])
    for g, part in fact.groupby("g"):
        d = root / "part" / f"g={g}"
        d.mkdir(parents=True)
        t = pa.Table.from_pandas(part.drop(columns=["g"]),
                                 preserve_index=False)
        t = t.append_column("tags", pa.array(
            [[int(v) % 3, 7] for v in part["k"]], pa.list_(pa.int64())))
        pq.write_table(t, str(d / "part.parquet"))
        paths["part"].append(str(d / "part.parquet"))
    return paths


def _session(tables, files, source):
    s = TpuSession(conf=dict(CONF))
    if source == "memory":
        for name, df in tables.items():
            s.register_table(name, pa.Table.from_pandas(
                df, preserve_index=False))
    else:
        s.register_table("fact", s.read_parquet(files["fact"]))
        s.register_table("dim", s.read_parquet(files["dim"]))
        s.register_table("part", s.read_parquet(files["part"]))
    return s


# --- the three ways ---------------------------------------------------------

def _walk(node):
    yield node
    for c in node.children:
        yield from _walk(c)
    for inner in ("_out",):  # TopN's internal pipeline
        if hasattr(node, inner):
            yield from _walk(getattr(node, inner))


def _plan(session, query):
    node = session.sql(query)._node if isinstance(query, str) \
        else query(session)._node
    return TpuOverrides(session.conf).apply(node)


def _rows(table: pa.Table, ordered: bool):
    rows = [tuple(r.values()) for r in table.to_pylist()]
    if not ordered:
        rows.sort(key=lambda r: tuple(
            (v is None, "" if v is None else v) if not isinstance(v, float)
            else (False, round(v, 6)) for v in r))
    return rows


def _same(got, want, what):
    assert len(got) == len(want), (what, len(got), len(want))
    for a, b in zip(got, want):
        assert len(a) == len(b), what
        for u, v in zip(a, b):
            if isinstance(u, float) and isinstance(v, float):
                assert (math.isnan(u) and math.isnan(v)) or \
                    abs(u - v) <= 1e-12 * max(abs(v), 1e-300), (what, a, b)
            else:
                assert u == v, (what, a, b)


def three_ways(monkeypatch, session_of, query, plain, ordered=False):
    """Run ``query`` (SQL text or a DataFrame builder) with the pass and
    with the pass as the identity; hold both against ``plain``. Returns
    the pruned physical plan for the shape assertions."""
    session = session_of()
    pp = _plan(session, query)
    report = verify_plan(pp.root, session.conf)
    assert report.ok, report.summary()
    assert "schema_mismatch" not in report.reasons()
    pruned = _rows(pp.collect(), ordered)
    with monkeypatch.context() as m:
        m.setattr(pruning, "prune_plan", lambda plan: plan)
        whole = _rows(_plan(session_of(), query).collect(), ordered)
    want = _rows(pa.Table.from_pandas(plain, preserve_index=False),
                 ordered)
    _same(pruned, want, "pruned plan against the plain computation")
    _same(whole, want, "unpruned plan against the plain computation")
    return pp


def scans(pp):
    return {tuple(n.output_schema.names) for n in _walk(pp.root)
            if isinstance(n, TpuFileScanExec)}


def joins(pp):
    return [n for n in _walk(pp.root) if isinstance(n, _BaseJoinExec)]


# --- TPC-DS q3 over 23 / 28 / 22 columns ------------------------------------

def _wide_table(schema_file, rng, rows, fixed):
    """A table with every column of a benchmark schema file, filled by
    type; the columns in ``fixed`` hold the given values."""
    with open(os.path.join(BENCH, "schemas", schema_file)) as f:
        schema = json.load(f)
    kinds = {c["name"]: c["type"] for c in schema["columns"]}
    cols = {}
    order = schema.get("order") or [c["name"] for c in schema["columns"]
                                    if not c.get("hidden")]
    for name in order:
        if name in fixed:
            cols[name] = fixed[name]
        elif kinds[name] == "string":
            cols[name] = pa.array(rng.choice(
                ["able", "ought", "pri ese", "anti bar callyn st"], rows))
        elif kinds[name] == "double":
            cols[name] = pa.array(np.round(rng.uniform(0, 500, rows), 2))
        elif kinds[name] == "date":
            cols[name] = pa.array(rng.integers(9000, 12000, rows)
                                  .astype(np.int32), pa.int32()) \
                .cast(pa.date32())
        elif kinds[name] == "long":
            cols[name] = pa.array(rng.integers(1, 1 << 33, rows)
                                  .astype(np.int64))
        else:
            cols[name] = pa.array(rng.integers(1, 1000, rows)
                                  .astype(np.int32))
    return pa.table(cols)


@pytest.fixture(scope="module")
def q3_files(tmp_path_factory):
    rng = np.random.default_rng(3)
    root = tmp_path_factory.mktemp("q3")
    nd, ni, ns = 400, 300, 4000
    brand_id = rng.integers(1, 40, ni).astype(np.int32)
    date_dim = _wide_table("tpcds/date_dim.json", rng, nd, {
        "d_date_sk": pa.array(np.arange(1, nd + 1, dtype=np.int32)),
        "d_year": pa.array(rng.integers(1998, 2003, nd).astype(np.int32)),
        "d_moy": pa.array(rng.choice([11, 11, 5, 12], nd)
                          .astype(np.int32))})
    item = _wide_table("tpcds/item.json", rng, ni, {
        "i_item_sk": pa.array(np.arange(1, ni + 1, dtype=np.int32)),
        "i_brand_id": pa.array(brand_id),
        "i_brand": pa.array([f"brand #{b}" for b in brand_id]),
        "i_manufact_id": pa.array(rng.choice([128, 128, 7, 900], ni)
                                  .astype(np.int32))})
    sales = _wide_table("tpcds/store_sales.json", rng, ns, {
        "ss_sold_date_sk": pa.array(rng.integers(1, nd + 1, ns)
                                    .astype(np.int32)),
        "ss_item_sk": pa.array(rng.integers(1, ni + 1, ns)
                               .astype(np.int32)),
        "ss_ext_sales_price": pa.array(
            np.round(rng.uniform(0, 20000, ns), 2))})
    assert (sales.num_columns, date_dim.num_columns,
            item.num_columns) == (23, 28, 22)
    paths = {"store_sales": [], "date_dim": [str(root / "date_dim.parquet")],
             "item": [str(root / "item.parquet")]}
    pq.write_table(date_dim, paths["date_dim"][0])
    pq.write_table(item, paths["item"][0])
    for i in range(2):
        p = str(root / f"store_sales{i}.parquet")
        pq.write_table(sales.slice(i * ns // 2, ns // 2), p)
        paths["store_sales"].append(p)
    return paths


def _benchmark_file(*parts):
    return os.path.join(BENCH, *parts)


def _q3_text():
    with open(_benchmark_file("queries", "tpcds", "q3.sql")) as f:
        return "\n".join(ln for ln in f.read().splitlines()
                         if not ln.lstrip().startswith("--"))


def test_q3_text_over_the_full_width_star(monkeypatch, q3_files):
    spec = importlib.util.spec_from_file_location(
        "plain_q3", _benchmark_file("references", "tpcds", "q3.py"))
    plain_q3 = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(plain_q3)
    want = plain_q3.reference(q3_files).to_pandas()
    assert len(want) > 20

    def session_of():
        s = TpuSession(conf=dict(CONF))
        for table, paths in q3_files.items():
            s.register_table(table, s.read_parquet(paths))
        return s

    pp = three_ways(monkeypatch, session_of, _q3_text(), want,
                    ordered=True)
    assert scans(pp) == {
        ("ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price"),
        ("d_date_sk", "d_year", "d_moy"),
        ("i_item_sk", "i_brand_id", "i_brand", "i_manufact_id")}
    # no string column but i_brand enters a join, and a column that is
    # only a key is not gathered
    widths = sorted(len(j.output_schema.fields) for j in joins(pp))
    assert widths == [3, 4]
    carried = {f.name for j in joins(pp) for c in j.children
               for f in c.output_schema.fields}
    assert carried == {"d_date_sk", "d_year", "ss_sold_date_sk",
                       "ss_item_sk", "ss_ext_sales_price", "i_item_sk",
                       "i_brand_id", "i_brand"}
    assert not pp.fallback_nodes()
    text = pp.explain("ALL")
    assert "ReadSchema=[d_date_sk, d_year, d_moy] (3 of 28 columns)" in text


# --- one shape a case -------------------------------------------------------

def _join_plain(tables, how):
    f, d = tables["fact"], tables["dim"]
    if how in ("semi", "anti"):
        f2 = f.reset_index().rename(columns={"index": "rid"})
        m = f2.merge(d, on="k")
        matched = set(m[m.x > m.w].rid)
        keep = f2.rid.isin(matched) if how == "semi" \
            else ~f2.rid.isin(matched)
        return f2[keep][["g", "x"]]
    f2 = f.reset_index().rename(columns={"index": "rid"})
    d2 = d.reset_index().rename(columns={"index": "did"})
    m = f2.merge(d2, on="k")
    m = m[m.x > m.w]
    out = m[["g", "x", "name", "rid", "did"]]
    if how in ("left", "full"):
        rest = f2[~f2.rid.isin(m.rid)]
        out = pd.concat([out, pd.DataFrame({
            "g": rest.g, "x": rest.x, "name": None, "rid": rest.rid})])
    if how in ("right", "full"):
        rest = d2[~d2.did.isin(m.did)]
        out = pd.concat([out, pd.DataFrame({
            "g": None, "x": None, "name": rest.name, "did": rest.did})])
    out = out[["g", "x", "name"]]
    out["g"] = out["g"].astype("Int32")
    return out


JOIN_SQL = {"inner": "join", "left": "left join", "right": "right join",
            "full": "full outer join", "semi": "left semi join",
            "anti": "left anti join"}


@pytest.mark.parametrize("how", sorted(JOIN_SQL))
def test_join_with_a_condition_on_a_payload_column(monkeypatch, tables,
                                                   files, how):
    cols = "f.g, f.x" if how in ("semi", "anti") else "f.g, f.x, d.name"
    text = (f"select {cols} from fact f {JOIN_SQL[how]} dim d "
            f"on f.k = d.k and f.x > d.w")
    pp = three_ways(monkeypatch,
                    lambda: _session(tables, files, "files"), text,
                    _join_plain(tables, how))
    assert scans(pp) == {("k", "g", "x"), ("k", "name", "w")} \
        if how not in ("semi", "anti") else \
        scans(pp) == {("k", "g", "x"), ("k", "w")}
    (j,) = joins(pp)
    # the payload: g, x (+ name) and the condition's w; never k, never
    # a pad column
    assert "k" not in j._cond_schema.names
    assert len(j.output_schema.fields) <= 4
    assert not any(n.startswith("pad") for c in j.children
                   for n in c.output_schema.names)


def _case_key_selected(tables):
    f, d = tables["fact"], tables["dim"]
    m = f.merge(d, on="k")
    return ("select f.k, d.k dk, d.flag from fact f join dim d "
            "on f.k = d.k",
            pd.DataFrame({"k": m.k, "dk": m.k, "flag": m.flag}),
            {("k",), ("k", "flag")}, False, [3])


def _case_two_aliases(tables):
    f = tables["fact"]
    a = f[f.g == 1][["k", "x"]]
    b = f[f.g == 2][["k", "s"]]
    m = a.merge(b, on="k")
    return ("select a.x, b.s from fact a join fact b on a.k = b.k "
            "where a.g = 1 and b.g = 2",
            m[["x", "s"]], {("k", "g", "x"), ("k", "g", "s")}, False,
            [4])  # the filter on both g's sits above an explicit JOIN


def _case_select_star(tables):
    d = tables["dim"]
    return ("select * from dim where flag = 1", d[d.flag == 1],
            {tuple(d.columns)}, False)


def _case_count_star(tables):
    f = tables["fact"]
    return ("select count(*) n from fact",
            pd.DataFrame({"n": [len(f)]}), {("g",)}, False)


def _case_filter_not_selected(tables):
    f = tables["fact"]
    return ("select s, y from fact where x < 25 and g <> 3",
            f[(f.x < 25) & (f.g != 3)][["s", "y"]],
            {("g", "s", "x", "y")}, False)


def _case_computed_group_key(tables):
    f = tables["fact"]
    g = f.assign(b=f.k % 5).groupby("b", as_index=False).agg(
        t=("x", "sum"), n=("x", "size"))
    return ("select k % 5 b, sum(x) t, count(*) n from fact "
            "group by k % 5", g, {("k", "x")}, False)


def _case_order_by_unselected(tables):
    f = tables["fact"]
    o = f[f.g == 4].sort_values(["pad_int", "x"], kind="stable")
    return ("select s, x from fact where g = 4 order by pad_int, x",
            o[["s", "x"]], {("g", "s", "x", "pad_int")}, True)


def _case_union_all(tables):
    f, d = tables["fact"], tables["dim"]
    u = pd.concat([
        f[f.g == 0][["k", "s"]].rename(columns={"s": "label"}),
        d[["k", "name"]].rename(columns={"name": "label"})])
    g = u.groupby("label", as_index=False).agg(n=("k", "size"))
    return ("select label, count(*) n from (select k, s label, x from "
            "fact where g = 0 union all select k, name label, w x from "
            "dim) u group by label", g,
            # k and x are selected inside the union and read by nothing
            {("g", "s"), ("name",)}, False)


def _case_cte_twice(tables):
    f = tables["fact"]
    t = f[f.x > 50].groupby("g", as_index=False).agg(t=("y", "sum"))
    m = t.merge(t, on="g")
    return ("with t as (select g, sum(y) t, max(s) top from fact where "
            "x > 50 group by g) select a.g, a.t + b.t tt from t a join "
            "t b on a.g = b.g",
            pd.DataFrame({"g": m.g, "tt": m.t_x + m.t_y}),
            {("g", "s", "x", "y")}, False, [3])


def _case_payload_from_one_side(tables):
    f, d = tables["fact"], tables["dim"]
    m = f.merge(d, on="k")
    return ("select d.name from fact f join dim d on f.k = d.k",
            m[["name"]], {("k",), ("k", "name")}, False, [1])


def _case_count_over_a_join(tables):
    f, d = tables["fact"], tables["dim"]
    return ("select count(*) n from fact f join dim d on f.k = d.k",
            pd.DataFrame({"n": [len(f.merge(d, on="k"))]}),
            # the rows still count: the narrowest column of the join's
            # output (g, an int) is kept as its one payload column
            {("k", "g"), ("k",)}, False, [1])


CASES = {
    "join_payload_from_one_side": _case_payload_from_one_side,
    "count_over_a_join": _case_count_over_a_join,
    "join_key_also_selected": _case_key_selected,
    "same_table_two_aliases": _case_two_aliases,
    "select_star": _case_select_star,
    "count_star": _case_count_star,
    "filter_on_unselected_column": _case_filter_not_selected,
    "group_by_computed_key": _case_computed_group_key,
    "order_by_unselected_column": _case_order_by_unselected,
    "union_all": _case_union_all,
    "cte_used_twice": _case_cte_twice,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_sql_shape(monkeypatch, tables, files, name):
    text, plain, want_scans, ordered, *widths = CASES[name](tables)
    pp = three_ways(monkeypatch,
                    lambda: _session(tables, files, "files"), text, plain,
                    ordered=ordered)
    assert scans(pp) == want_scans
    assert [len(j.output_schema.fields) for j in joins(pp)] \
        == (widths[0] if widths else [])


def test_q6_reads_four_of_sixteen_columns(monkeypatch, tmp_path):
    import sys
    sys.path.insert(0, BENCH)
    try:
        import datagen
    finally:
        sys.path.remove(BENCH)
    paths, _, _ = datagen.make_tables(
        _benchmark_file("configs", "tpch-sf1.json"), str(tmp_path), 7,
        4000)
    with open(_benchmark_file("queries", "tpch", "q6.sql")) as f:
        text = "\n".join(ln for ln in f.read().splitlines()
                         if not ln.lstrip().startswith("--"))
    li = pa.concat_tables(pq.read_table(p) for p in paths["lineitem"]) \
        .to_pandas()
    lo, hi = np.datetime64("1994-01-01"), np.datetime64("1995-01-01")
    ship = li.l_shipdate.to_numpy().astype("datetime64[D]")
    keep = (ship >= lo) & (ship < hi) & (li.l_discount >= 0.05) \
        & (li.l_discount <= 0.07) & (li.l_quantity < 24)
    want = pd.DataFrame({"revenue": [math.fsum(
        li.l_extendedprice[keep] * li.l_discount[keep])]})

    def session_of():
        s = TpuSession(conf=dict(CONF))
        s.register_table("lineitem", s.read_parquet(paths["lineitem"]))
        return s

    pp = three_ways(monkeypatch, session_of, text, want)
    assert scans(pp) == {("l_quantity", "l_extendedprice", "l_discount",
                          "l_shipdate")}
    pp.collect()
    read = pruned = 0
    for m in pp.last_ctx.metrics.values():
        read += m["columnsRead"].value if "columnsRead" in m else 0
        pruned += m["columnsPruned"].value if "columnsPruned" in m else 0
    assert (read, pruned) == (4 * len(paths["lineitem"]),
                              12 * len(paths["lineitem"]))
    assert "columnsRead=" in pp.explain_analyze()
    # dbgen-like data holds no null: every chunk read ran without a
    # definition-level pass, and EXPLAIN ANALYZE shows the counter
    assert f"nullFreeChunks={read}" in pp.explain_analyze()


def test_window_and_expand_above_a_join(monkeypatch, tables, files):
    """Pruning stops at a window (it states no requirement) and passes
    an expand; below both the join and the scans are still narrowed by
    what the operators above the barrier read."""
    f, d = tables["fact"], tables["dim"]
    m = f[f.g == 5].merge(d, on="k").sort_values(
        ["flag", "x", "pad_int"], kind="stable")
    m = m.assign(r=(m.groupby("flag").cumcount() + 1).astype(np.int32))
    text = ("select flag, x, r from (select flag, x, row_number() over "
            "(partition by flag order by x, pad_int) r from fact f join "
            "dim d on f.k = d.k where f.g = 5) t where r <= 3")
    pp = three_ways(monkeypatch,
                    lambda: _session(tables, files, "files"), text,
                    m[m.r <= 3][["flag", "x", "r"]])
    assert scans(pp) == {("k", "g", "x", "pad_int"), ("k", "flag")}

    def expand(session):
        from spark_rapids_tpu.exec.misc import TpuExpandExec
        from spark_rapids_tpu.expr.base import Literal, UnresolvedColumn
        from spark_rapids_tpu import datatypes as dt
        from spark_rapids_tpu.session import DataFrame
        j = session.table("fact").join(session.table("dim"), on="k")
        col = UnresolvedColumn
        node = TpuExpandExec(
            [[col("g"), col("name"), col("x")],
             [col("g"), Literal(None, dt.STRING), col("x")]],
            ["g", "name", "x"], j._node)
        return DataFrame(node, session)

    j = f.merge(d, on="k")
    plain = pd.concat([j[["g", "name", "x"]],
                       j[["g", "x"]].assign(name=None)[["g", "name", "x"]]])
    pp = three_ways(monkeypatch,
                    lambda: _session(tables, files, "files"), expand, plain)
    assert scans(pp) == {("k", "g", "x"), ("k", "name")}
    (jn,) = joins(pp)
    assert jn.output_schema.names == ["g", "x", "name"]


def test_dataframe_api_plan(monkeypatch, tables, files):
    """A plan the SQL compiler never saw: the same pass serves it."""
    from spark_rapids_tpu.expr import (Alias, GreaterThan, Literal,
                                       UnresolvedColumn as col)
    from spark_rapids_tpu.expr.aggregates import Sum
    f, d = tables["fact"], tables["dim"]
    m = f[f.x > 40.0].merge(d, on="k")
    plain = m.groupby("name", as_index=False).agg(t=("y", "sum"))

    def build(session):
        return (session.table("fact")
                .filter(GreaterThan(col("x"), Literal(40.0)))
                .join(session.table("dim"), on="k")
                .group_by("name").agg(Alias(Sum(col("y")), "t")))

    pp = three_ways(monkeypatch,
                    lambda: _session(tables, files, "files"), build, plain)
    assert scans(pp) == {("k", "x", "y"), ("k", "name")}
    (j,) = joins(pp)
    assert j.output_schema.names == ["y", "name"]
    # the same DataFrame planned twice: the same pruned operators, so
    # what they compiled is kept
    s = _session(tables, files, "files")
    df = build(s)
    assert joins(df._plan())[0] is joins(df._plan())[0]


def test_partition_values_and_a_host_fallback_column(monkeypatch, tables,
                                                     files):
    f = tables["fact"]
    want = f[f.x > 90].assign(t0=[int(v) % 3 for v in f[f.x > 90].k])
    want = want.groupby(["g", "t0"], as_index=False).agg(n=("x", "size"))
    want["g"] = want["g"].astype(np.int64)  # a partition value is a long

    def build(session):  # the SQL subset has no array subscript
        from spark_rapids_tpu.expr import (Alias, GetArrayItem,
                                           GreaterThan, Literal,
                                           UnresolvedColumn as col)
        from spark_rapids_tpu.expr.aggregates import Count
        return (session.table("part")
                .filter(GreaterThan(col("x"), Literal(90.0)))
                .select(col("g"), Alias(GetArrayItem(
                    col("tags"), Literal(0)), "t0"))
                .group_by("g", "t0").agg(Alias(Count(), "n")))

    pp = three_ways(monkeypatch,
                    lambda: _session(tables, files, "files"), build, want)
    assert scans(pp) == {("x", "tags", "g")}
    pp.collect()
    chunks = sum(m["fallbackChunks"].value
                 for m in pp.last_ctx.metrics.values()
                 if "fallbackChunks" in m)
    assert chunks == len(files["part"])  # tags, once a row group


def test_users_projection_meets_the_pass(tables, files):
    s = TpuSession(conf=dict(CONF))
    df = s.read_parquet(files["fact"], columns=["x", "s", "k"])
    assert df.columns == ["k", "s", "x"]  # file order
    s.register_table("fact", df)
    pp = _plan(s, "select s from fact where x < 1")
    assert scans(pp) == {("s", "x")}
    with pytest.raises(KeyError):
        s.read_parquet(files["fact"], columns=["nope"])
    text = s.sql("explain select s from fact where x < 1")
    assert "ReadSchema=[s, x] (2 of 3 columns)" in text, text


def test_a_stale_ordinal_is_a_rejected_plan(monkeypatch, tables, files):
    """A pass that narrowed a scan and forgot to re-bind what reads it
    is caught by the static verifier before any kernel runs."""
    from spark_rapids_tpu.analysis.plan_verifier import \
        PlanVerificationError
    monkeypatch.setattr(pruning, "remap", lambda expr, mapping: expr)
    s = _session(tables, files, "files")
    with pytest.raises(PlanVerificationError) as e:
        _plan(s, "select s from fact where pad_dbl > 0.5")
    assert "schema_mismatch" in e.value.report.reasons()
