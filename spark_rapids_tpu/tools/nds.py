"""NDS subset: TPC-DS-shaped query corpus over a generated star schema.

Reference: the integration_tests NDS/TPC-DS job definitions + the
NDS SF3K benchmark suite (SURVEY.md §6, :215; reference mount empty).
A full NDS run needs a SQL frontend; this subset re-expresses twelve
representative query SHAPES — date-dim filter joins over store_sales
(q3/q42/q52/q55), multi-join averages (q7), count-distinct-ish multi
filters (q96), cross-period customer semi/anti (q97 flavor), string
LIKE category scans, percentile and pivot reports — through the
`TpuSession` DataFrame API, each paired with a pandas oracle that is
also the HOST BASELINE the driver-facing geomean compares against
(pandas merge/groupby is the strongest commonly-available single-node
host engine for these shapes).

Used by tests (dual-run correctness, tests/test_nds.py) and by
chip_smoke.py's NDS phase.
"""
from __future__ import annotations

import numpy as np
import pyarrow as pa

__all__ = ["gen_tables", "QUERIES", "SQL_QUERIES", "build_query",
           "build_query_sql", "pandas_oracle", "register_frames"]


def gen_tables(n_sales: int = 1 << 15, seed: int = 42):
    """Star schema as pyarrow tables (deterministic)."""
    rng = np.random.default_rng(seed)
    n_dates = 730  # two years
    n_items = max(200, n_sales // 128)
    n_cust = max(500, n_sales // 64)
    n_stores = 25

    date_dim = pa.table({
        "d_date_sk": pa.array(np.arange(n_dates, dtype=np.int64)),
        "d_year": pa.array((2000 + np.arange(n_dates) // 365)
                           .astype(np.int32)),
        "d_moy": pa.array(((np.arange(n_dates) % 365) // 31 + 1)
                          .clip(1, 12).astype(np.int32)),
        "d_qoy": pa.array((((np.arange(n_dates) % 365) // 92) + 1)
                          .clip(1, 4).astype(np.int32)),
    })
    item = pa.table({
        "i_item_sk": pa.array(np.arange(n_items, dtype=np.int64)),
        "i_brand_id": pa.array(rng.integers(1, 60, n_items)
                               .astype(np.int32)),
        "i_category_id": pa.array(rng.integers(1, 11, n_items)
                                  .astype(np.int32)),
        "i_manufact_id": pa.array(rng.integers(1, 100, n_items)
                                  .astype(np.int32)),
        "i_category": pa.array(rng.choice(
            ["Electronics", "Home", "Sports", "Books", "Music",
             "Jewelry"], n_items).tolist()),
        "i_current_price": pa.array(rng.uniform(0.5, 300, n_items)
                                    .astype(np.float64)),
    })
    store = pa.table({
        "s_store_sk": pa.array(np.arange(n_stores, dtype=np.int64)),
        "s_state": pa.array(rng.choice(["CA", "TX", "NY", "WA", "TN"],
                                       n_stores).tolist()),
    })
    customer = pa.table({
        "c_customer_sk": pa.array(np.arange(n_cust, dtype=np.int64)),
        "c_birth_year": pa.array(rng.integers(1930, 2005, n_cust)
                                 .astype(np.int32)),
    })
    qty = rng.integers(1, 100, n_sales).astype(np.int32)
    price = rng.uniform(1, 200, n_sales).astype(np.float64)
    store_sales = pa.table({
        "ss_sold_date_sk": pa.array(rng.integers(0, n_dates, n_sales)
                                    .astype(np.int64)),
        "ss_item_sk": pa.array(rng.integers(0, n_items, n_sales)
                               .astype(np.int64)),
        "ss_customer_sk": pa.array(rng.integers(0, n_cust, n_sales)
                                   .astype(np.int64)),
        "ss_store_sk": pa.array(rng.integers(0, n_stores, n_sales)
                                .astype(np.int64)),
        "ss_quantity": pa.array(qty),
        "ss_sales_price": pa.array(price),
        "ss_ext_sales_price": pa.array((qty * price).astype(np.float64)),
        "ss_net_profit": pa.array(rng.normal(5, 40, n_sales)
                                  .astype(np.float64)),
    })
    return {"store_sales": store_sales, "date_dim": date_dim,
            "item": item, "store": store, "customer": customer}


# --- query builders (session DataFrames) ----------------------------------

def register_frames(session, frames):
    """Expose corpus frames as SQL temp views (session catalog) — the
    SQL texts in SQL_QUERIES resolve table names through these. Bench
    harnesses that re-wrap frames (e.g. .cache()) re-register so the
    SQL path sees the same cached inputs the hand-built path does."""
    for k, df in frames.items():
        session.register_table(k, df)


def _frames(session, tables):
    """Session-memoized DataFrames for the corpus tables: repeated
    query builds share one frame per table, so bench harnesses can
    .cache() them once (device-resident inputs, matching the pandas
    baseline's in-memory tables)."""
    memo = getattr(session, "_nds_frames", None)
    if memo is not None and memo[0] is tables:
        return memo[1]
    f = {k: session.create_dataframe(t) for k, t in tables.items()}
    session._nds_frames = (tables, f)
    register_frames(session, f)
    return f


def _col(name):
    from ..expr import UnresolvedColumn
    return UnresolvedColumn(name)


def _alias(e, n):
    from ..expr.base import Alias
    return Alias(e, n)


def _lit(v):
    from ..expr.base import Literal
    from .. import datatypes as dt_
    if isinstance(v, bool):
        return Literal(v, dt_.BOOL)
    if isinstance(v, (int, np.integer)):
        return Literal(int(v), dt_.INT32)
    if isinstance(v, float):
        return Literal(v, dt_.FLOAT64)
    return Literal(v, dt_.STRING)


def _cmp(kind, name, v):
    from ..expr.predicates import (EqualTo, GreaterThan,
                                   GreaterThanOrEqual, LessThan,
                                   LessThanOrEqual)
    ops = {"==": EqualTo, ">": GreaterThan, ">=": GreaterThanOrEqual,
           "<": LessThan, "<=": LessThanOrEqual}
    return ops[kind](_col(name), _lit(v))


def q3(session, t):
    """q3 shape: brand revenue in November by year."""
    from ..expr.aggregates import Sum
    f = _frames(session, t)
    dd = f["date_dim"].filter(_cmp("==", "d_moy", 11)) \
        .select(_col("d_date_sk"), _col("d_year"))
    it = f["item"].select(_col("i_item_sk"), _col("i_brand_id"))
    df = (f["store_sales"]
          .select(_col("ss_sold_date_sk"), _col("ss_item_sk"),
                  _col("ss_ext_sales_price"))
          .join(dd, on=[("ss_sold_date_sk", "d_date_sk")], build_unique=True)
          .join(it, on=[("ss_item_sk", "i_item_sk")], build_unique=True)
          .group_by("d_year", "i_brand_id")
          .agg(_alias(Sum(_col("ss_ext_sales_price")), "sum_agg"))
          .order_by("d_year", "sum_agg", "i_brand_id",
                    ascending=[True, False, True])
          .limit(10))
    return df


def q3_pd(pd, t):
    ss, dd, it = t["store_sales"], t["date_dim"], t["item"]
    j = ss.merge(dd[dd.d_moy == 11], left_on="ss_sold_date_sk",
                 right_on="d_date_sk")
    j = j.merge(it, left_on="ss_item_sk", right_on="i_item_sk")
    g = j.groupby(["d_year", "i_brand_id"], as_index=False) \
        .agg(sum_agg=("ss_ext_sales_price", "sum"))
    return g.sort_values(["d_year", "sum_agg", "i_brand_id"],
                         ascending=[True, False, True]).head(10)


def q42(session, t):
    """q42 shape: category revenue for one month of one year."""
    from ..expr.aggregates import Sum
    from ..expr.predicates import And
    f = _frames(session, t)
    dd = f["date_dim"].filter(And(_cmp("==", "d_moy", 12),
                                  _cmp("==", "d_year", 2000)))
    df = (f["store_sales"]
          .join(dd.select(_col("d_date_sk")),
                on=[("ss_sold_date_sk", "d_date_sk")], build_unique=True)
          .join(f["item"].select(_col("i_item_sk"), _col("i_category_id")),
                on=[("ss_item_sk", "i_item_sk")], build_unique=True)
          .group_by("i_category_id")
          .agg(_alias(Sum(_col("ss_ext_sales_price")), "s"))
          .order_by("s", "i_category_id", ascending=[False, True]))
    return df


def q42_pd(pd, t):
    ss, dd, it = t["store_sales"], t["date_dim"], t["item"]
    d = dd[(dd.d_moy == 12) & (dd.d_year == 2000)]
    j = ss.merge(d, left_on="ss_sold_date_sk", right_on="d_date_sk") \
        .merge(it, left_on="ss_item_sk", right_on="i_item_sk")
    g = j.groupby("i_category_id", as_index=False) \
        .agg(s=("ss_ext_sales_price", "sum"))
    return g.sort_values(["s", "i_category_id"],
                         ascending=[False, True])


def q55(session, t):
    """q55 shape: brand revenue for a manufacturer band."""
    from ..expr.aggregates import Sum
    from ..expr.predicates import And
    f = _frames(session, t)
    it = f["item"].filter(And(_cmp(">=", "i_manufact_id", 20),
                              _cmp("<", "i_manufact_id", 40))) \
        .select(_col("i_item_sk"), _col("i_brand_id"))
    df = (f["store_sales"]
          .select(_col("ss_item_sk"), _col("ss_ext_sales_price"))
          .join(it, on=[("ss_item_sk", "i_item_sk")], build_unique=True)
          .group_by("i_brand_id")
          .agg(_alias(Sum(_col("ss_ext_sales_price")), "rev"))
          .order_by("rev", "i_brand_id", ascending=[False, True])
          .limit(20))
    return df


def q55_pd(pd, t):
    ss, it = t["store_sales"], t["item"]
    i = it[(it.i_manufact_id >= 20) & (it.i_manufact_id < 40)]
    j = ss.merge(i, left_on="ss_item_sk", right_on="i_item_sk")
    g = j.groupby("i_brand_id", as_index=False) \
        .agg(rev=("ss_ext_sales_price", "sum"))
    return g.sort_values(["rev", "i_brand_id"],
                         ascending=[False, True]).head(20)


def q7(session, t):
    """q7 shape: per-item averages across joins."""
    from ..expr.aggregates import Average
    f = _frames(session, t)
    dd = f["date_dim"].filter(_cmp("==", "d_year", 2001))
    df = (f["store_sales"]
          .join(dd.select(_col("d_date_sk")),
                on=[("ss_sold_date_sk", "d_date_sk")], build_unique=True)
          .join(f["item"].select(_col("i_item_sk"), _col("i_category_id")),
                on=[("ss_item_sk", "i_item_sk")], build_unique=True)
          .group_by("i_category_id")
          .agg(_alias(Average(_col("ss_quantity")), "avg_q"),
               _alias(Average(_col("ss_sales_price")), "avg_p"))
          .order_by("i_category_id"))
    return df


def q7_pd(pd, t):
    ss, dd, it = t["store_sales"], t["date_dim"], t["item"]
    j = ss.merge(dd[dd.d_year == 2001], left_on="ss_sold_date_sk",
                 right_on="d_date_sk") \
        .merge(it, left_on="ss_item_sk", right_on="i_item_sk")
    g = j.groupby("i_category_id", as_index=False).agg(
        avg_q=("ss_quantity", "mean"), avg_p=("ss_sales_price", "mean"))
    return g.sort_values("i_category_id")


def q96(session, t):
    """q96 shape: selective count through two dimension joins."""
    from ..expr.aggregates import Count
    from ..expr.predicates import And
    f = _frames(session, t)
    df = (f["store_sales"]
          .filter(And(_cmp(">=", "ss_quantity", 40),
                      _cmp("<=", "ss_quantity", 60)))
          .join(f["store"].select(_col("s_store_sk")),
                on=[("ss_store_sk", "s_store_sk")], build_unique=True)
          .join(f["date_dim"].filter(_cmp("==", "d_qoy", 2))
                .select(_col("d_date_sk")),
                on=[("ss_sold_date_sk", "d_date_sk")], build_unique=True)
          .group_by()
          .agg(_alias(Count(), "cnt")))
    return df


def q96_pd(pd, t):
    ss, st, dd = t["store_sales"], t["store"], t["date_dim"]
    j = ss[(ss.ss_quantity >= 40) & (ss.ss_quantity <= 60)]
    j = j.merge(st, left_on="ss_store_sk", right_on="s_store_sk")
    j = j.merge(dd[dd.d_qoy == 2], left_on="ss_sold_date_sk",
                right_on="d_date_sk")
    return pd.DataFrame({"cnt": [np.int64(len(j))]})


def q97(session, t):
    """q97 flavor: customers buying in H1, H2, both (semi/anti joins)."""
    from ..expr.aggregates import Count
    f = _frames(session, t)
    dd = f["date_dim"]
    h1 = f["store_sales"].join(dd.filter(_cmp("<=", "d_moy", 6)),
                               on=[("ss_sold_date_sk", "d_date_sk")], build_unique=True) \
        .select(_col("ss_customer_sk"))
    h2 = f["store_sales"].join(dd.filter(_cmp(">", "d_moy", 6)),
                               on=[("ss_sold_date_sk", "d_date_sk")], build_unique=True) \
        .select(_alias(_col("ss_customer_sk"), "c2"))
    both = h1.join(h2, on=[("ss_customer_sk", "c2")], how="semi") \
        .group_by().agg(_alias(Count(), "n_pairs"))
    return both


def q97_pd(pd, t):
    ss, dd = t["store_sales"], t["date_dim"]
    h1 = ss.merge(dd[dd.d_moy <= 6], left_on="ss_sold_date_sk",
                  right_on="d_date_sk")["ss_customer_sk"]
    h2 = set(ss.merge(dd[dd.d_moy > 6], left_on="ss_sold_date_sk",
                      right_on="d_date_sk")["ss_customer_sk"])
    n = int((h1.isin(h2)).sum())
    return pd.DataFrame({"n_pairs": [np.int64(n)]})


def q_like(session, t):
    """String-scan shape: LIKE over a category, revenue by state
    (exercises the device regex/LIKE path)."""
    from ..expr.aggregates import Sum
    from ..expr.strings import Like
    f = _frames(session, t)
    it = f["item"].filter(Like(_col("i_category"), "%o%s%"))
    df = (f["store_sales"]
          .join(it, on=[("ss_item_sk", "i_item_sk")], build_unique=True)
          .join(f["store"], on=[("ss_store_sk", "s_store_sk")], build_unique=True)
          .group_by("s_state")
          .agg(_alias(Sum(_col("ss_net_profit")), "profit"))
          .order_by("s_state"))
    return df


def q_like_pd(pd, t):
    ss, it, st = t["store_sales"], t["item"], t["store"]
    i = it[it.i_category.str.match(".*o.*s.*")]
    j = ss.merge(i, left_on="ss_item_sk", right_on="i_item_sk") \
        .merge(st, left_on="ss_store_sk", right_on="s_store_sk")
    g = j.groupby("s_state", as_index=False) \
        .agg(profit=("ss_net_profit", "sum"))
    return g.sort_values("s_state")


def q_percentile(session, t):
    """Quantile-report shape: price percentiles per state."""
    from ..expr.aggregates import ApproxPercentile
    f = _frames(session, t)
    df = (f["store_sales"]
          .join(f["store"], on=[("ss_store_sk", "s_store_sk")], build_unique=True)
          .group_by("s_state")
          .agg(_alias(ApproxPercentile(_col("ss_sales_price"), 0.5),
                      "p50"))
          .order_by("s_state"))
    return df


def q_percentile_pd(pd, t):
    import math
    ss, st = t["store_sales"], t["store"]
    j = ss.merge(st, left_on="ss_store_sk", right_on="s_store_sk")

    def p50(v):
        v = np.sort(v.to_numpy())
        return v[min(max(math.ceil(0.5 * len(v)) - 1, 0), len(v) - 1)]
    g = j.groupby("s_state", as_index=False) \
        .agg(p50=("ss_sales_price", p50))
    return g.sort_values("s_state")


def q_pivot(session, t):
    """Pivot-report shape: yearly revenue by quarter columns."""
    from ..expr.aggregates import Sum
    f = _frames(session, t)
    df = (f["store_sales"]
          .join(f["date_dim"], on=[("ss_sold_date_sk", "d_date_sk")], build_unique=True)
          .group_by("d_year").pivot("d_qoy", [1, 2, 3, 4])
          .agg(_alias(Sum(_col("ss_ext_sales_price")), "s"))
          .order_by("d_year"))
    return df


def q_pivot_pd(pd, t):
    ss, dd = t["store_sales"], t["date_dim"]
    j = ss.merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk")
    g = j.pivot_table(index="d_year", columns="d_qoy",
                      values="ss_ext_sales_price", aggfunc="sum")
    g = g.reindex(columns=[1, 2, 3, 4])
    g.columns = ["1", "2", "3", "4"]
    return g.reset_index().sort_values("d_year")


def q_customer_age(session, t):
    """Demographic-join shape: profit by buyer birth decade."""
    from ..expr.aggregates import Count, Sum
    from ..expr.arithmetic import IntegralDivide, Multiply
    from .. import datatypes as dt_
    from ..expr.base import Literal
    from ..expr import Cast
    f = _frames(session, t)
    decade = _alias(Multiply(
        IntegralDivide(Cast(_col("c_birth_year"), dt_.INT64),
                       Literal(10, dt_.INT64)),
        Literal(10, dt_.INT64)), "decade")
    cust = f["customer"].select(_col("c_customer_sk"), decade)
    df = (f["store_sales"]
          .join(cust, on=[("ss_customer_sk", "c_customer_sk")], build_unique=True)
          .group_by("decade")
          .agg(_alias(Sum(_col("ss_net_profit")), "profit"),
               _alias(Count(), "n"))
          .order_by("decade"))
    return df


def q_customer_age_pd(pd, t):
    ss, c = t["store_sales"], t["customer"]
    c = c.assign(decade=(c.c_birth_year.astype("int64") // 10) * 10)
    j = ss.merge(c, left_on="ss_customer_sk", right_on="c_customer_sk")
    g = j.groupby("decade", as_index=False).agg(
        profit=("ss_net_profit", "sum"), n=("ss_net_profit", "size"))
    return g.sort_values("decade")


def q_topn_profit(session, t):
    """TopN shape: most profitable items in a quarter."""
    from ..expr.aggregates import Sum
    f = _frames(session, t)
    df = (f["store_sales"]
          .join(f["date_dim"].filter(_cmp("==", "d_qoy", 4))
                .select(_col("d_date_sk")),
                on=[("ss_sold_date_sk", "d_date_sk")], build_unique=True)
          .group_by("ss_item_sk")
          .agg(_alias(Sum(_col("ss_net_profit")), "profit"))
          .order_by("profit", "ss_item_sk", ascending=[False, True])
          .limit(25))
    return df


def q_topn_profit_pd(pd, t):
    ss, dd = t["store_sales"], t["date_dim"]
    j = ss.merge(dd[dd.d_qoy == 4], left_on="ss_sold_date_sk",
                 right_on="d_date_sk")
    g = j.groupby("ss_item_sk", as_index=False) \
        .agg(profit=("ss_net_profit", "sum"))
    return g.sort_values(["profit", "ss_item_sk"],
                         ascending=[False, True]).head(25)


def q_price_band(session, t):
    """Case/filter shape: revenue by current-price band."""
    from ..expr.aggregates import Sum
    from ..expr.conditional import CaseWhen
    from ..expr.base import Literal
    from .. import datatypes as dt_
    f = _frames(session, t)
    band = _alias(CaseWhen(
        [(_cmp("<", "i_current_price", 10.0), Literal("low", dt_.STRING)),
         (_cmp("<", "i_current_price", 100.0), Literal("mid", dt_.STRING))],
        Literal("high", dt_.STRING)), "band")
    it = f["item"].select(_col("i_item_sk"), _col("i_current_price"))
    df = (f["store_sales"]
          .select(_col("ss_item_sk"), _col("ss_ext_sales_price"))
          .join(it, on=[("ss_item_sk", "i_item_sk")], build_unique=True)
          .select(_col("ss_ext_sales_price"), band)
          .group_by("band")
          .agg(_alias(Sum(_col("ss_ext_sales_price")), "rev"))
          .order_by("band"))
    return df


def q_price_band_pd(pd, t):
    ss, it = t["store_sales"], t["item"]
    band = np.where(it.i_current_price < 10.0, "low",
                    np.where(it.i_current_price < 100.0, "mid", "high"))
    i = it.assign(band=band)
    j = ss.merge(i, left_on="ss_item_sk", right_on="i_item_sk")
    g = j.groupby("band", as_index=False) \
        .agg(rev=("ss_ext_sales_price", "sum"))
    return g.sort_values("band")


def q_rank_in_category(session, t):
    """Windowed-rank shape (q67-like): top-3 brands per category by
    revenue — group-by -> RANK() OVER (PARTITION BY category ORDER BY
    revenue DESC) -> filter rank <= 3 (exercises the device window
    machine inside a corpus query)."""
    from ..exec.sort import SortOrder
    from ..exec.window import TpuWindowExec
    from ..expr import Rank, WindowExpression
    from ..expr.aggregates import Sum
    from ..expr.predicates import LessThanOrEqual
    from ..expr.base import Literal
    from ..session import DataFrame
    from .. import datatypes as dt
    f = _frames(session, t)
    base = (f["store_sales"]
            .join(f["item"], on=[("ss_item_sk", "i_item_sk")],
                  build_unique=True)
            .group_by("i_category", "i_brand_id")
            .agg(_alias(Sum(_col("ss_ext_sales_price")), "rev")))
    win = TpuWindowExec(
        [_alias(WindowExpression(
            Rank(), [_col("i_category")],
            [SortOrder(_col("rev"), ascending=False),
             SortOrder(_col("i_brand_id"))]), "rk")],
        base._node)
    return (DataFrame(win, session)
            .filter(LessThanOrEqual(_col("rk"), Literal(3, dt.INT32)))
            .order_by("i_category", "rk", "i_brand_id"))


def q_rank_in_category_pd(pd, t):
    ss, it = t["store_sales"], t["item"]
    j = ss.merge(it, left_on="ss_item_sk", right_on="i_item_sk")
    g = j.groupby(["i_category", "i_brand_id"], as_index=False) \
        .agg(rev=("ss_ext_sales_price", "sum"))
    g = g.sort_values(["i_category", "rev", "i_brand_id"],
                      ascending=[True, False, True])
    # the engine ranks over the compound (rev DESC, brand ASC) key,
    # and (category, brand) is the group key, so ranks are distinct:
    # cumcount matches exactly even under revenue ties
    g["rk"] = (g.groupby("i_category").cumcount() + 1).astype("int32")
    g = g[g["rk"] <= 3]
    return g.sort_values(["i_category", "rk", "i_brand_id"]).reset_index(
        drop=True)


def q_rolling_revenue(session, t):
    """Rolling-window shape: per-store daily revenue with a trailing
    7-day RANGE average (exercises the round-5 literal-offset range
    frames inside a corpus query)."""
    from ..exec.sort import SortOrder
    from ..exec.window import TpuWindowExec
    from ..expr import WindowExpression, WindowFrame
    from ..expr.aggregates import Average, Sum
    from ..session import DataFrame
    from ..expr import Cast
    from .. import datatypes as dt
    f = _frames(session, t)
    daily = (f["store_sales"]
             .group_by("ss_store_sk", "ss_sold_date_sk")
             .agg(_alias(Sum(_col("ss_ext_sales_price")), "rev"))
             # the device range-frame path wants a <= 32-bit order
             # lane; date surrogate keys fit int32
             .with_column("d32", Cast(_col("ss_sold_date_sk"),
                                      dt.INT32)))
    win = TpuWindowExec(
        [_alias(WindowExpression(
            Average(_col("rev")), [_col("ss_store_sk")],
            [SortOrder(_col("d32"))],
            WindowFrame("range", -6, 0)), "avg7")],
        daily._node)
    return (DataFrame(win, session)
            .select(_col("ss_store_sk"), _col("ss_sold_date_sk"),
                    _col("rev"), _col("avg7"))
            .order_by("ss_store_sk", "ss_sold_date_sk"))


def q_rolling_revenue_pd(pd, t):
    ss = t["store_sales"]
    g = ss.groupby(["ss_store_sk", "ss_sold_date_sk"],
                   as_index=False).agg(rev=("ss_ext_sales_price", "sum"))

    def roll(sub):
        sub = sub.sort_values("ss_sold_date_sk").reset_index(drop=True)
        d = sub["ss_sold_date_sk"].to_numpy()
        r = sub["rev"].to_numpy()
        out = [r[(d >= d[i] - 6) & (d <= d[i])].mean()
               for i in range(len(sub))]
        sub["avg7"] = out
        return sub
    g = g.groupby("ss_store_sk", group_keys=False)[
        ["ss_store_sk", "ss_sold_date_sk", "rev"]].apply(roll)
    return g.sort_values(["ss_store_sk", "ss_sold_date_sk"]) \
        .reset_index(drop=True)


def q52(session, t):
    """q52 shape: brand revenue for one December (q3 cousin)."""
    from ..expr.aggregates import Sum
    from ..expr.predicates import And
    f = _frames(session, t)
    dd = f["date_dim"].filter(And(_cmp("==", "d_moy", 12),
                                  _cmp("==", "d_year", 2001))) \
        .select(_col("d_date_sk"), _col("d_year"))
    it = f["item"].select(_col("i_item_sk"), _col("i_brand_id"))
    df = (f["store_sales"]
          .join(dd, on=[("ss_sold_date_sk", "d_date_sk")], build_unique=True)
          .join(it, on=[("ss_item_sk", "i_item_sk")], build_unique=True)
          .group_by("d_year", "i_brand_id")
          .agg(_alias(Sum(_col("ss_ext_sales_price")), "ext_price"))
          .order_by("d_year", "ext_price", "i_brand_id",
                    ascending=[True, False, True])
          .limit(10))
    return df


def q52_pd(pd, t):
    ss, dd, it = t["store_sales"], t["date_dim"], t["item"]
    d = dd[(dd.d_moy == 12) & (dd.d_year == 2001)]
    j = ss.merge(d, left_on="ss_sold_date_sk", right_on="d_date_sk") \
        .merge(it, left_on="ss_item_sk", right_on="i_item_sk")
    g = j.groupby(["d_year", "i_brand_id"], as_index=False) \
        .agg(ext_price=("ss_ext_sales_price", "sum"))
    return g.sort_values(["d_year", "ext_price", "i_brand_id"],
                         ascending=[True, False, True]).head(10)


def q_cte(session, t):
    """CTE shape: year-over-year revenue via a twice-referenced
    year_rev CTE (expression join key d_year = prev + 1)."""
    from .. import datatypes as dt_
    from ..expr.aggregates import Sum
    from ..expr.arithmetic import Add
    from ..expr.base import Literal
    f = _frames(session, t)
    yr = (f["store_sales"]
          .join(f["date_dim"], on=[("ss_sold_date_sk", "d_date_sk")],
                build_unique=True)
          .group_by("d_year")
          .agg(_alias(Sum(_col("ss_ext_sales_price")), "rev")))
    prev = yr.select(_alias(_col("d_year"), "py"),
                     _alias(_col("rev"), "prev_rev"))
    df = (yr.join(prev, on=[(_col("d_year"),
                             Add(_col("py"), Literal(1, dt_.INT32)))])
          .select(_col("d_year"), _col("rev"), _col("prev_rev"))
          .order_by("d_year"))
    return df


def q_cte_pd(pd, t):
    ss, dd = t["store_sales"], t["date_dim"]
    j = ss.merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk")
    g = j.groupby("d_year", as_index=False) \
        .agg(rev=("ss_ext_sales_price", "sum"))
    p = g.rename(columns={"rev": "prev_rev"}).copy()
    p["jk"] = p["d_year"] + 1
    m = g.merge(p[["jk", "prev_rev"]], left_on="d_year", right_on="jk")
    return m[["d_year", "rev", "prev_rev"]].sort_values("d_year")


def q_union(session, t):
    """UNION ALL shape: per-state profit for two quarters stacked."""
    from ..expr.aggregates import Sum
    f = _frames(session, t)

    def half(q):
        return (f["store_sales"]
                .join(f["date_dim"].filter(_cmp("==", "d_qoy", q))
                      .select(_col("d_date_sk")),
                      on=[("ss_sold_date_sk", "d_date_sk")],
                      build_unique=True)
                .join(f["store"], on=[("ss_store_sk", "s_store_sk")],
                      build_unique=True)
                .group_by("s_state")
                .agg(_alias(Sum(_col("ss_net_profit")), "profit"))
                .select(_alias(_lit(q), "qtr"), _col("s_state"),
                        _col("profit")))

    return half(1).union(half(2)).order_by("qtr", "s_state")


def q_union_pd(pd, t):
    ss, dd, st = t["store_sales"], t["date_dim"], t["store"]

    def half(q):
        j = ss.merge(dd[dd.d_qoy == q], left_on="ss_sold_date_sk",
                     right_on="d_date_sk") \
            .merge(st, left_on="ss_store_sk", right_on="s_store_sk")
        g = j.groupby("s_state", as_index=False) \
            .agg(profit=("ss_net_profit", "sum"))
        g.insert(0, "qtr", np.int32(q))
        return g

    out = pd.concat([half(1), half(2)], ignore_index=True)
    return out.sort_values(["qtr", "s_state"])


def q_having(session, t):
    """HAVING shape: busy brands only (post-aggregation filter)."""
    from .. import datatypes as dt_
    from ..expr.aggregates import Count, Sum
    from ..expr.base import Literal
    from ..expr.predicates import GreaterThan
    f = _frames(session, t)
    it = f["item"].select(_col("i_item_sk"), _col("i_brand_id"))
    df = (f["store_sales"]
          .join(it, on=[("ss_item_sk", "i_item_sk")], build_unique=True)
          .group_by("i_brand_id")
          .agg(_alias(Count(), "n"),
               _alias(Sum(_col("ss_ext_sales_price")), "rev"))
          .filter(GreaterThan(_col("n"), Literal(250, dt_.INT64)))
          .order_by("i_brand_id"))
    return df


def q_having_pd(pd, t):
    ss, it = t["store_sales"], t["item"]
    j = ss.merge(it, left_on="ss_item_sk", right_on="i_item_sk")
    g = j.groupby("i_brand_id", as_index=False).agg(
        n=("ss_ext_sales_price", "size"),
        rev=("ss_ext_sales_price", "sum"))
    g = g[g.n > 250]
    return g.sort_values("i_brand_id")


def q_in_between(session, t):
    """IN + BETWEEN shape: category revenue for a quantity band."""
    from ..expr.aggregates import Sum
    from ..expr.predicates import And, In
    f = _frames(session, t)
    it = f["item"].filter(In(_col("i_category"),
                             ("Books", "Music", "Sports")))
    df = (f["store_sales"]
          .filter(And(_cmp(">=", "ss_quantity", 20),
                      _cmp("<=", "ss_quantity", 40)))
          .join(f["date_dim"].filter(_cmp("==", "d_year", 2000))
                .select(_col("d_date_sk")),
                on=[("ss_sold_date_sk", "d_date_sk")], build_unique=True)
          .join(it, on=[("ss_item_sk", "i_item_sk")], build_unique=True)
          .group_by("i_category")
          .agg(_alias(Sum(_col("ss_ext_sales_price")), "rev"))
          .order_by("i_category"))
    return df


def q_in_between_pd(pd, t):
    ss, dd, it = t["store_sales"], t["date_dim"], t["item"]
    s = ss[(ss.ss_quantity >= 20) & (ss.ss_quantity <= 40)]
    j = s.merge(dd[dd.d_year == 2000], left_on="ss_sold_date_sk",
                right_on="d_date_sk")
    i = it[it.i_category.isin(["Books", "Music", "Sports"])]
    j = j.merge(i, left_on="ss_item_sk", right_on="i_item_sk")
    g = j.groupby("i_category", as_index=False) \
        .agg(rev=("ss_ext_sales_price", "sum"))
    return g.sort_values("i_category")


def q_agg_expr(session, t):
    """Expression-over-aggregates shape: bulk-order revenue share per
    state (sum(case)/sum)."""
    from .. import datatypes as dt_
    from ..expr.aggregates import Sum
    from ..expr.arithmetic import Divide
    from ..expr.base import Literal
    from ..expr.conditional import If
    from ..expr.predicates import GreaterThanOrEqual
    f = _frames(session, t)
    bulk = Sum(If(GreaterThanOrEqual(_col("ss_quantity"),
                                     Literal(50, dt_.INT32)),
                  _col("ss_ext_sales_price"),
                  Literal(0.0, dt_.FLOAT64)))
    df = (f["store_sales"]
          .join(f["store"], on=[("ss_store_sk", "s_store_sk")],
                build_unique=True)
          .group_by("s_state")
          .agg(_alias(bulk, "__b"),
               _alias(Sum(_col("ss_ext_sales_price")), "__t"))
          .select(_col("s_state"),
                  _alias(Divide(_col("__b"), _col("__t")), "bulk_share"))
          .order_by("s_state"))
    return df


def q_agg_expr_pd(pd, t):
    ss, st = t["store_sales"], t["store"]
    j = ss.merge(st, left_on="ss_store_sk", right_on="s_store_sk")
    j = j.assign(bulk=np.where(j.ss_quantity >= 50,
                               j.ss_ext_sales_price, 0.0))
    g = j.groupby("s_state", as_index=False).agg(
        b=("bulk", "sum"), tt=("ss_ext_sales_price", "sum"))
    g["bulk_share"] = g.b / g.tt
    return g[["s_state", "bulk_share"]].sort_values("s_state")


def q_rownum(session, t):
    """ROW_NUMBER shape: single best-selling item per category."""
    from .. import datatypes as dt_
    from ..exec.sort import SortOrder
    from ..exec.window import TpuWindowExec
    from ..expr import RowNumber, WindowExpression
    from ..expr.aggregates import Sum
    from ..expr.base import Literal
    from ..expr.predicates import EqualTo
    from ..session import DataFrame
    f = _frames(session, t)
    base = (f["store_sales"]
            .join(f["item"], on=[("ss_item_sk", "i_item_sk")],
                  build_unique=True)
            .group_by("i_category", "i_item_sk")
            .agg(_alias(Sum(_col("ss_ext_sales_price")), "rev")))
    win = TpuWindowExec(
        [_alias(WindowExpression(
            RowNumber(), [_col("i_category")],
            [SortOrder(_col("rev"), ascending=False),
             SortOrder(_col("i_item_sk"))]), "rn")],
        base._node)
    return (DataFrame(win, session)
            .filter(EqualTo(_col("rn"), Literal(1, dt_.INT32)))
            .select(_col("i_category"), _col("i_item_sk"), _col("rev"))
            .order_by("i_category"))


def q_rownum_pd(pd, t):
    ss, it = t["store_sales"], t["item"]
    j = ss.merge(it, left_on="ss_item_sk", right_on="i_item_sk")
    g = j.groupby(["i_category", "i_item_sk"], as_index=False) \
        .agg(rev=("ss_ext_sales_price", "sum"))
    g = g.sort_values(["i_category", "rev", "i_item_sk"],
                      ascending=[True, False, True])
    top = g.groupby("i_category", group_keys=False).head(1)
    return top[["i_category", "i_item_sk", "rev"]] \
        .sort_values("i_category")


def q_not_or(session, t):
    """Precedence shape: NOT/OR month exclusion + profit filter."""
    from ..expr.aggregates import Count
    from ..expr.predicates import Not, Or
    f = _frames(session, t)
    dd = f["date_dim"].filter(Not(Or(_cmp("==", "d_moy", 1),
                                     _cmp("==", "d_moy", 12)))) \
        .select(_col("d_date_sk"), _col("d_year"))
    df = (f["store_sales"]
          .filter(_cmp(">", "ss_net_profit", 0.0))
          .join(dd, on=[("ss_sold_date_sk", "d_date_sk")],
                build_unique=True)
          .group_by("d_year")
          .agg(_alias(Count(), "n"))
          .order_by("d_year"))
    return df


def q_not_or_pd(pd, t):
    ss, dd = t["store_sales"], t["date_dim"]
    d = dd[~((dd.d_moy == 1) | (dd.d_moy == 12))]
    s = ss[ss.ss_net_profit > 0.0]
    j = s.merge(d, left_on="ss_sold_date_sk", right_on="d_date_sk")
    g = j.groupby("d_year", as_index=False) \
        .agg(n=("d_date_sk", "size"))
    return g.sort_values("d_year")


QUERIES = {
    "q3": (q3, q3_pd), "q42": (q42, q42_pd), "q55": (q55, q55_pd),
    "q7": (q7, q7_pd), "q96": (q96, q96_pd), "q97": (q97, q97_pd),
    "q_like": (q_like, q_like_pd),
    "q_percentile": (q_percentile, q_percentile_pd),
    "q_pivot": (q_pivot, q_pivot_pd),
    "q_customer_age": (q_customer_age, q_customer_age_pd),
    "q_topn": (q_topn_profit, q_topn_profit_pd),
    "q_price_band": (q_price_band, q_price_band_pd),
    "q_rank": (q_rank_in_category, q_rank_in_category_pd),
    "q_rolling": (q_rolling_revenue, q_rolling_revenue_pd),
    "q52": (q52, q52_pd),
    "q_cte": (q_cte, q_cte_pd),
    "q_union": (q_union, q_union_pd),
    "q_having": (q_having, q_having_pd),
    "q_in_between": (q_in_between, q_in_between_pd),
    "q_agg_expr": (q_agg_expr, q_agg_expr_pd),
    "q_rownum": (q_rownum, q_rownum_pd),
    "q_not_or": (q_not_or, q_not_or_pd),
}


# --- SQL corpus ------------------------------------------------------------
# Every query re-expressed as REAL NDS-style SQL text (comma FROM
# lists, WHERE-clause join predicates, /*+ UNIQUE(...) */ hints where
# the hand-built plan passes build_unique=True). tests/test_sql_nds.py
# dual-runs each against its hand-built plan row-for-row; chip_smoke.py
# drives its queries from these texts.

SQL_QUERIES = {
    "q3": """
SELECT /*+ UNIQUE(dt, item) */ dt.d_year, item.i_brand_id,
       SUM(ss_ext_sales_price) AS sum_agg
FROM date_dim dt, store_sales, item
WHERE dt.d_date_sk = ss_sold_date_sk
  AND ss_item_sk = item.i_item_sk
  AND dt.d_moy = 11
GROUP BY dt.d_year, item.i_brand_id
ORDER BY dt.d_year, sum_agg DESC, i_brand_id
LIMIT 10
""",
    "q42": """
SELECT /*+ UNIQUE(dt, item) */ i_category_id,
       SUM(ss_ext_sales_price) AS s
FROM date_dim dt, store_sales, item
WHERE dt.d_date_sk = ss_sold_date_sk
  AND ss_item_sk = i_item_sk
  AND dt.d_moy = 12 AND dt.d_year = 2000
GROUP BY i_category_id
ORDER BY s DESC, i_category_id
""",
    "q55": """
SELECT /*+ UNIQUE(item) */ i_brand_id,
       SUM(ss_ext_sales_price) AS rev
FROM store_sales, item
WHERE ss_item_sk = i_item_sk
  AND i_manufact_id >= 20 AND i_manufact_id < 40
GROUP BY i_brand_id
ORDER BY rev DESC, i_brand_id
LIMIT 20
""",
    "q7": """
SELECT /*+ UNIQUE(dt, item) */ i_category_id,
       AVG(ss_quantity) AS avg_q, AVG(ss_sales_price) AS avg_p
FROM store_sales, date_dim dt, item
WHERE ss_sold_date_sk = dt.d_date_sk
  AND ss_item_sk = i_item_sk
  AND dt.d_year = 2001
GROUP BY i_category_id
ORDER BY i_category_id
""",
    "q96": """
SELECT /*+ UNIQUE(store, date_dim) */ COUNT(*) AS cnt
FROM store_sales, store, date_dim
WHERE ss_quantity BETWEEN 40 AND 60
  AND ss_store_sk = s_store_sk
  AND ss_sold_date_sk = d_date_sk
  AND d_qoy = 2
""",
    "q97": """
SELECT COUNT(*) AS n_pairs
FROM (SELECT /*+ UNIQUE(date_dim) */ ss_customer_sk
      FROM store_sales, date_dim
      WHERE ss_sold_date_sk = d_date_sk AND d_moy <= 6) h1
LEFT SEMI JOIN
     (SELECT /*+ UNIQUE(date_dim) */ ss_customer_sk AS c2
      FROM store_sales, date_dim
      WHERE ss_sold_date_sk = d_date_sk AND d_moy > 6) h2
ON h1.ss_customer_sk = h2.c2
""",
    "q_like": """
SELECT /*+ UNIQUE(item, store) */ s_state,
       SUM(ss_net_profit) AS profit
FROM store_sales, item, store
WHERE ss_item_sk = i_item_sk
  AND ss_store_sk = s_store_sk
  AND i_category LIKE '%o%s%'
GROUP BY s_state
ORDER BY s_state
""",
    "q_percentile": """
SELECT /*+ UNIQUE(store) */ s_state,
       APPROX_PERCENTILE(ss_sales_price, 0.5) AS p50
FROM store_sales, store
WHERE ss_store_sk = s_store_sk
GROUP BY s_state
ORDER BY s_state
""",
    "q_pivot": """
SELECT /*+ UNIQUE(date_dim) */ d_year,
       SUM(CASE WHEN d_qoy = 1 THEN ss_ext_sales_price END) AS "1",
       SUM(CASE WHEN d_qoy = 2 THEN ss_ext_sales_price END) AS "2",
       SUM(CASE WHEN d_qoy = 3 THEN ss_ext_sales_price END) AS "3",
       SUM(CASE WHEN d_qoy = 4 THEN ss_ext_sales_price END) AS "4"
FROM store_sales, date_dim
WHERE ss_sold_date_sk = d_date_sk
GROUP BY d_year
ORDER BY d_year
""",
    "q_customer_age": """
SELECT /*+ UNIQUE(cust) */ decade, SUM(ss_net_profit) AS profit,
       COUNT(*) AS n
FROM store_sales,
     (SELECT c_customer_sk,
             CAST(c_birth_year AS BIGINT) DIV 10 * 10 AS decade
      FROM customer) cust
WHERE ss_customer_sk = c_customer_sk
GROUP BY decade
ORDER BY decade
""",
    "q_topn": """
SELECT /*+ UNIQUE(date_dim) */ ss_item_sk,
       SUM(ss_net_profit) AS profit
FROM store_sales, date_dim
WHERE ss_sold_date_sk = d_date_sk AND d_qoy = 4
GROUP BY ss_item_sk
ORDER BY profit DESC, ss_item_sk
LIMIT 25
""",
    "q_price_band": """
SELECT /*+ UNIQUE(item) */
       CASE WHEN i_current_price < 10.0 THEN 'low'
            WHEN i_current_price < 100.0 THEN 'mid'
            ELSE 'high' END AS band,
       SUM(ss_ext_sales_price) AS rev
FROM store_sales, item
WHERE ss_item_sk = i_item_sk
GROUP BY band
ORDER BY band
""",
    "q_rank": """
SELECT i_category, i_brand_id, rev, rk
FROM (SELECT i_category, i_brand_id, rev,
             RANK() OVER (PARTITION BY i_category
                          ORDER BY rev DESC, i_brand_id) AS rk
      FROM (SELECT /*+ UNIQUE(item) */ i_category, i_brand_id,
                   SUM(ss_ext_sales_price) AS rev
            FROM store_sales, item
            WHERE ss_item_sk = i_item_sk
            GROUP BY i_category, i_brand_id) brand_rev) ranked
WHERE rk <= 3
ORDER BY i_category, rk, i_brand_id
""",
    "q_rolling": """
SELECT ss_store_sk, ss_sold_date_sk, rev,
       AVG(rev) OVER (PARTITION BY ss_store_sk ORDER BY d32
                      RANGE BETWEEN 6 PRECEDING AND CURRENT ROW)
       AS avg7
FROM (SELECT ss_store_sk, ss_sold_date_sk,
             SUM(ss_ext_sales_price) AS rev,
             CAST(ss_sold_date_sk AS INT) AS d32
      FROM store_sales
      GROUP BY ss_store_sk, ss_sold_date_sk) daily
ORDER BY ss_store_sk, ss_sold_date_sk
""",
    "q52": """
SELECT /*+ UNIQUE(dt, item) */ dt.d_year, item.i_brand_id,
       SUM(ss_ext_sales_price) AS ext_price
FROM date_dim dt, store_sales, item
WHERE dt.d_date_sk = ss_sold_date_sk
  AND ss_item_sk = item.i_item_sk
  AND dt.d_moy = 12 AND dt.d_year = 2001
GROUP BY dt.d_year, item.i_brand_id
ORDER BY dt.d_year, ext_price DESC, i_brand_id
LIMIT 10
""",
    "q_cte": """
WITH year_rev AS (
  SELECT /*+ UNIQUE(date_dim) */ d_year,
         SUM(ss_ext_sales_price) AS rev
  FROM store_sales, date_dim
  WHERE ss_sold_date_sk = d_date_sk
  GROUP BY d_year)
SELECT a.d_year, a.rev, b.rev AS prev_rev
FROM year_rev a JOIN year_rev b ON a.d_year = b.d_year + 1
ORDER BY a.d_year
""",
    "q_union": """
SELECT /*+ UNIQUE(date_dim, store) */ 1 AS qtr, s_state,
       SUM(ss_net_profit) AS profit
FROM store_sales, date_dim, store
WHERE ss_sold_date_sk = d_date_sk AND ss_store_sk = s_store_sk
  AND d_qoy = 1
GROUP BY s_state
UNION ALL
SELECT /*+ UNIQUE(date_dim, store) */ 2 AS qtr, s_state,
       SUM(ss_net_profit) AS profit
FROM store_sales, date_dim, store
WHERE ss_sold_date_sk = d_date_sk AND ss_store_sk = s_store_sk
  AND d_qoy = 2
GROUP BY s_state
ORDER BY qtr, s_state
""",
    "q_having": """
SELECT /*+ UNIQUE(item) */ i_brand_id, COUNT(*) AS n,
       SUM(ss_ext_sales_price) AS rev
FROM store_sales, item
WHERE ss_item_sk = i_item_sk
GROUP BY i_brand_id
HAVING COUNT(*) > 250
ORDER BY i_brand_id
""",
    "q_in_between": """
SELECT /*+ UNIQUE(date_dim, item) */ i_category,
       SUM(ss_ext_sales_price) AS rev
FROM store_sales, date_dim, item
WHERE ss_sold_date_sk = d_date_sk
  AND ss_item_sk = i_item_sk
  AND ss_quantity BETWEEN 20 AND 40
  AND i_category IN ('Books', 'Music', 'Sports')
  AND d_year = 2000
GROUP BY i_category
ORDER BY i_category
""",
    "q_agg_expr": """
SELECT /*+ UNIQUE(store) */ s_state,
       SUM(CASE WHEN ss_quantity >= 50 THEN ss_ext_sales_price
                ELSE 0.0 END) / SUM(ss_ext_sales_price)
       AS bulk_share
FROM store_sales, store
WHERE ss_store_sk = s_store_sk
GROUP BY s_state
ORDER BY s_state
""",
    "q_rownum": """
SELECT i_category, i_item_sk, rev
FROM (SELECT i_category, i_item_sk, rev,
             ROW_NUMBER() OVER (PARTITION BY i_category
                                ORDER BY rev DESC, i_item_sk) AS rn
      FROM (SELECT /*+ UNIQUE(item) */ i_category, i_item_sk,
                   SUM(ss_ext_sales_price) AS rev
            FROM store_sales, item
            WHERE ss_item_sk = i_item_sk
            GROUP BY i_category, i_item_sk) t) ranked
WHERE rn = 1
ORDER BY i_category
""",
    "q_not_or": """
SELECT /*+ UNIQUE(date_dim) */ d_year, COUNT(*) AS n
FROM store_sales, date_dim
WHERE ss_sold_date_sk = d_date_sk
  AND NOT (d_moy = 1 OR d_moy = 12) AND ss_net_profit > 0.0
GROUP BY d_year
ORDER BY d_year
""",
}


def build_query(name: str, session, tables):
    return QUERIES[name][0](session, tables)


def build_query_sql(name: str, session, tables):
    """The SQL-text route to the same query: registers the corpus
    frames as temp views and compiles SQL_QUERIES[name] through
    ``session.sql`` — the path chip_smoke.py drives."""
    _frames(session, tables)
    return session.sql(SQL_QUERIES[name])


def pandas_frames(tables):
    """One-time arrow->pandas conversion (callers that run several
    queries hoist this out of their loops)."""
    return {k: v.to_pandas() for k, v in tables.items()}


def pandas_oracle(name: str, tables, pdt=None):
    import pandas as pd
    if pdt is None:
        pdt = pandas_frames(tables)
    return QUERIES[name][1](pd, pdt)
