"""Session / DataFrame facade — the user-facing product surface.

TPU analog of the entry point the reference gives Spark users
(`spark.plugins=com.nvidia.spark.SQLPlugin` + the unchanged DataFrame
API — SURVEY.md §2.2-A "Plugin bootstrap"; mount empty,
capability-built): a user writes DataFrame transformations; the session
builds the exec tree, runs the override/planner pass, and executes on
TPU with per-operator CPU fallback. Until a JVM bridge exists the API
is Python-native (pyarrow in, pyarrow out), but the plan/override/
execute pipeline underneath is exactly the plugin's.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Union

import pyarrow as pa

from . import datatypes as dt
from .config import (CASE_SENSITIVE, RapidsConf, SHUFFLE_PARTITIONS)
from .exec.base import (ExecCtx, HostBatchSourceExec, OpContract,
                        TpuExec, UnaryExec)
from .expr.base import Expression, bind_expr
from .expr import UnresolvedColumn

__all__ = ["TpuSession", "DataFrame", "TpuCacheExec"]


class TpuCacheExec(UnaryExec):
    """df.cache(): the child materializes ONCE into spillable catalog
    entries and replays from them afterwards (the reference's
    GpuDataFrame cache / InMemoryTableScan analog, SURVEY.md §2.2-B
    "DataFrame cache"). Spill pressure tiers cached batches device ->
    host -> disk like any catalog entry."""

    CONTRACT = OpContract(
        schema_preserving=True,
        notes="materializes once into the spill catalog and replays")

    PRUNING_NOTE = ("requires every column of its child and keeps the "
                    "subtree below it as built: it replays what it "
                    "materialized once, whatever the next query reads")
    PRUNE_BELOW = False

    def __init__(self, child: TpuExec):
        super().__init__(child)
        self._entries = None   # List[SpillableBatch]
        self._cpu_cache = None

    def describe(self):
        state = "cached" if self._entries is not None else "lazy"
        return f"CacheExec [{state}]"

    def execute(self, ctx: ExecCtx):
        if self._entries is None:
            entries = []
            try:
                for b in self.child.execute(ctx):
                    entries.append(ctx.mm.register(b))
            except BaseException:
                # partial materialization must not leak catalog entries
                # into the process-shared manager
                for sb in entries:
                    sb.release()
                raise
            self._entries = entries
            import weakref
            for sb in entries:
                weakref.finalize(self, type(sb).release, sb)
        for sb in self._entries:
            yield sb.get()

    # CPU-side cache ceiling: the device path spills under pressure, the
    # oracle path must not hoard host memory unboundedly instead
    # (VERDICT r3 weak #9) — past this, replay re-executes the child
    _CPU_CACHE_LIMIT = 256 << 20

    def execute_cpu(self, ctx: ExecCtx):
        if self._cpu_cache is not None:
            yield from self._cpu_cache
            return
        acc: list = []
        total = 0
        for rb in self.child.execute_cpu(ctx):
            if acc is not None:
                total += rb.nbytes
                acc.append(rb)
                if total > self._CPU_CACHE_LIMIT:
                    acc = None  # too big to cache; keep streaming
            yield rb
        if acc is not None:
            self._cpu_cache = acc


def _analyze(e: Expression) -> Expression:
    """The analyzer slice the engine's type-resolved expressions expect:
    implicit numeric widening casts on binary comparisons/arithmetic
    (Catalyst's TypeCoercion analog). The exec layer stays strict; only
    the user-facing DataFrame API coerces."""
    from .expr import Cast, Divide
    from .expr.arithmetic import BinaryArithmetic
    from .expr.predicates import BinaryComparison

    def coerce(node):
        if isinstance(node, (BinaryComparison, BinaryArithmetic)) \
                and len(node.children) == 2:
            left, right = node.children
            try:
                lt, rt = left.dtype, right.dtype
            except TypeError:
                return node
            if lt == rt and not isinstance(node, Divide):
                return node
            if dt.is_numeric(lt) and dt.is_numeric(rt):
                t = dt.common_type(lt, rt)
                if isinstance(node, Divide) and dt.is_integral(t):
                    t = dt.FLOAT64  # Spark `/` is fractional
                new = []
                for c in (left, right):
                    new.append(c if c.dtype == t else Cast(c, t))
                if new[0] is not left or new[1] is not right:
                    return node.with_children(new)
        return node

    return e.transform(coerce)


def _as_expr(c) -> Expression:
    if isinstance(c, Expression):
        return c
    if isinstance(c, str):
        return UnresolvedColumn(c)
    raise TypeError(f"not a column: {c!r}")


class GroupedData:
    def __init__(self, df: "DataFrame", keys: List[Expression]):
        self._df = df
        self._keys = keys

    def agg(self, *agg_exprs) -> "DataFrame":
        """Shuffle by the grouping keys (spark.sql.shuffle.partitions
        exchanges — the plan shape CPU Spark produces) then aggregate."""
        from .exec.aggregate import TpuHashAggregateExec
        from .exec.exchange import TpuShuffleExchangeExec
        from .shuffle.partitioner import HashPartitioning
        df = self._df
        child = df._node
        if self._keys:
            n = df._session.conf.get(SHUFFLE_PARTITIONS)
            child = TpuShuffleExchangeExec(
                HashPartitioning(self._keys, n), child)
        node = TpuHashAggregateExec(self._keys, list(agg_exprs), child)
        return DataFrame(node, df._session)

    def pivot(self, pivot_col, values=None) -> "PivotedData":
        """Spark's pivot: rewritten into one conditional aggregate per
        pivot value (the Analyzer's pivot rewrite — no dedicated exec
        needed, exactly how Spark lowers it; SURVEY.md:177). With
        `values=None` the distinct pivot values are collected first
        (one extra engine query, like Spark's implicit-values mode)."""
        pe = self._df._bind(pivot_col)
        if values is None:
            from .expr.aggregates import Count
            from .expr.base import Alias
            distinct = GroupedData(self._df, [pe]).agg(
                Alias(Count(), "__n__")).collect()
            values = sorted(v for v in distinct.column(0).to_pylist()
                            if v is not None)
        return PivotedData(self._df, self._keys, pe, list(values))


class PivotedData:
    def __init__(self, df: "DataFrame", keys, pivot_expr, values):
        self._df = df
        self._keys = keys
        self._pivot = pivot_expr
        self._values = values

    def agg(self, *agg_exprs) -> "DataFrame":
        """One output column per (pivot value x aggregate): each
        aggregate's inputs are masked to the pivot value via If — the
        standard Spark rewrite. Column naming follows Spark: a single
        aggregate names columns by the value alone; multiple aggregates
        use value_aggname."""
        import copy as _copy

        from . import datatypes as dt
        from .expr.aggregates import AggregateFunction
        from .expr.base import Alias, Literal
        from .expr.conditional import If
        from .expr.predicates import EqualTo
        out = []
        multi = len(agg_exprs) > 1
        for v in self._values:
            cond = EqualTo(self._pivot, Literal(v, self._pivot.dtype))
            for e in agg_exprs:
                if isinstance(e, Alias):
                    fn, nm = e.child, e.name
                else:
                    fn, nm = e, e.pretty_name().lower()
                if not isinstance(fn, AggregateFunction):
                    raise TypeError(f"pivot agg must be an aggregate: "
                                    f"{e!r}")
                clone = _copy.copy(fn)
                if fn.children:
                    # bind against the frame first: the null literal's
                    # type comes from the (resolved) child
                    bound = [self._df._bind(c) for c in fn.children]
                    clone.children = tuple(
                        If(cond, c, Literal(None, c.dtype))
                        for c in bound)
                else:  # count(*): count rows matching the pivot value
                    clone = type(fn)(If(cond, Literal(1, dt.INT32),
                                        Literal(None, dt.INT32)))
                name = f"{v}_{nm}" if multi else str(v)
                out.append(Alias(clone, name))
        return GroupedData(self._df, self._keys).agg(*out)


class DataFrame:
    def __init__(self, node: TpuExec, session: "TpuSession"):
        self._node = node
        self._session = session

    # --- schema / plan ----------------------------------------------------
    @property
    def schema(self) -> dt.Schema:
        return self._node.output_schema

    @property
    def columns(self) -> List[str]:
        return self._node.output_schema.names

    def _bind(self, e) -> Expression:
        bound = bind_expr(_as_expr(e), self._node.output_schema,
                          case_sensitive=self._session.conf.get(
                              CASE_SENSITIVE),
                          validate=False)
        analyzed = _analyze(bound)
        analyzed.transform(lambda n: (n.validate(), n)[1])
        return analyzed

    def explain(self, mode: str = "ALL") -> str:
        from .planner import TpuOverrides
        pp = TpuOverrides(self._session.conf).apply(self._node)
        return pp.explain(mode)

    # --- transformations --------------------------------------------------
    def select(self, *cols) -> "DataFrame":
        from .exec.basic import TpuProjectExec
        return DataFrame(TpuProjectExec([self._bind(c) for c in cols],
                                        self._node), self._session)

    def with_column(self, name: str, expr) -> "DataFrame":
        from .expr import Alias
        keep = [UnresolvedColumn(n) for n in self.columns if n != name]
        return self.select(*keep, Alias(_as_expr(expr), name))

    def filter(self, cond) -> "DataFrame":
        from .exec.basic import TpuFilterExec
        return DataFrame(TpuFilterExec(self._bind(cond), self._node),
                         self._session)

    where = filter

    def group_by(self, *keys) -> GroupedData:
        return GroupedData(self, [self._bind(k) for k in keys])

    def join(self, other: "DataFrame", on=None, how: str = "inner",
             condition=None, build_unique: bool = False) -> "DataFrame":
        """Equi-join via the shuffled hash join (`on` = column name(s)
        shared by both sides, or a (left, right) expression pair list);
        condition-only joins route to the nested-loop exec like the
        reference's plan rules. ``build_unique`` declares the RIGHT
        side's keys unique (a primary-key dimension): the join then
        skips its one build-analysis readback and runs fully sync-free
        (exec/joins.py build_unique_hint — UNCHECKED, like Spark's
        broadcast hints)."""
        from .exec.joins import (TpuBroadcastNestedLoopJoinExec,
                                 TpuShuffledHashJoinExec)
        how = {"left": "left_outer", "right": "right_outer",
               "outer": "full_outer", "full": "full_outer",
               "semi": "left_semi", "anti": "left_anti"}.get(how, how)
        if on is None:
            node = TpuBroadcastNestedLoopJoinExec(
                how, self._node, other._node, condition)
            return DataFrame(node, self._session)
        if isinstance(on, str):
            on = [on]
        from .expr import Cast
        cs = self._session.conf.get(CASE_SENSITIVE)
        lkeys, rkeys = [], []
        for k in on:
            lk = _as_expr(k if not isinstance(k, tuple) else k[0])
            rk = _as_expr(k if not isinstance(k, tuple) else k[1])
            lk = bind_expr(lk, self._node.output_schema,
                           case_sensitive=cs)
            rk = bind_expr(rk, other._node.output_schema,
                           case_sensitive=cs)
            # analyzer-grade key coercion: mixed-width numeric keys
            # widen to their common type (Spark's TypeCoercion)
            if lk.dtype != rk.dtype and dt.is_numeric(lk.dtype) \
                    and dt.is_numeric(rk.dtype):
                t = dt.common_type(lk.dtype, rk.dtype)
                if lk.dtype != t:
                    lk = Cast(lk, t)
                if rk.dtype != t:
                    rk = Cast(rk, t)
            lkeys.append(lk)
            rkeys.append(rk)
        node = TpuShuffledHashJoinExec(lkeys, rkeys, how, self._node,
                                       other._node, condition,
                                       build_unique_hint=build_unique)
        return DataFrame(node, self._session)

    def order_by(self, *cols, ascending: Union[bool, Sequence[bool]] =
                 True) -> "DataFrame":
        from .exec.sort import SortOrder, TpuSortExec
        if isinstance(ascending, bool):
            ascending = [ascending] * len(cols)
        orders = [SortOrder(_as_expr(c), asc)
                  for c, asc in zip(cols, ascending)]
        return DataFrame(TpuSortExec(orders, self._node), self._session)

    def limit(self, n: int) -> "DataFrame":
        from .exec.sort import TpuGlobalLimitExec
        return DataFrame(TpuGlobalLimitExec(n, self._node), self._session)

    def union(self, other: "DataFrame") -> "DataFrame":
        from .exec.misc import TpuUnionExec
        return DataFrame(TpuUnionExec([self._node, other._node]),
                         self._session)

    def sample(self, fraction: float, seed: int = 42) -> "DataFrame":
        from .exec.misc import TpuSampleExec
        return DataFrame(TpuSampleExec(fraction, seed, self._node),
                         self._session)

    def explode(self, column, outer: bool = False,
                position: bool = False) -> "DataFrame":
        from .exec.generate import TpuGenerateExec
        return DataFrame(
            TpuGenerateExec(self._bind(column), self._node, outer=outer,
                            position=position), self._session)

    def cache(self) -> "DataFrame":
        return DataFrame(TpuCacheExec(self._node), self._session)

    # --- actions ----------------------------------------------------------
    def _plan(self):
        from .planner import TpuOverrides
        return TpuOverrides(self._session.conf).apply(self._node)

    def collect(self, qctx=None) -> pa.Table:
        """Execute and download. ``qctx`` (a lifecycle.QueryContext,
        e.g. from ``session.query_context(deadline_s=5)``) carries the
        cancellation token / deadline / tenant / memory budget; without
        one the session conf's lifecycle defaults apply."""
        return self._plan().collect(qctx=qctx)

    def count(self) -> int:
        return self.collect().num_rows

    def to_pylist(self) -> List[dict]:
        return self.collect().to_pylist()

    def write(self, path: str, fmt: str = "parquet",
              partition_by=None) -> List[str]:
        """Write via the engine's write exec; returns the part files."""
        from .io.write import TpuFileWriteExec
        node = TpuFileWriteExec(self._node, path, fmt,
                                partition_by=partition_by,
                                conf=self._session.conf)
        from .planner import TpuOverrides
        pp = TpuOverrides(self._session.conf).apply(node)
        pp.collect()
        return node.written_files

    def write_parquet(self, path: str, **kw) -> List[str]:
        return self.write(path, "parquet", **kw)


class TpuSession:
    """The SparkSession analog: conf + DataFrame builders + a temp-view
    catalog feeding the SQL frontend (``session.sql``)."""

    def __init__(self, conf: Optional[Union[RapidsConf, Dict]] = None):
        if isinstance(conf, dict):
            conf = RapidsConf(conf)
        self.conf = conf or RapidsConf()
        self._tables: Dict[str, DataFrame] = {}
        self._cluster = None  # set_cluster: EXPLAIN ANALYZE target
        from .config import SHUFFLE_MODE
        if self.conf.get(SHUFFLE_MODE) == "ICI":
            # the ONE mesh over the local devices and the ONE transport
            # of its exchanges, up before the first query (a mesh that
            # cannot be built fails here, not mid-query)
            from .shuffle.ici import local_transport
            self.ici_transport = local_transport(self.conf)

    def set_cluster(self, cluster) -> None:
        """Attach a TpuProcessCluster: ``EXPLAIN ANALYZE`` statements
        then execute across its worker processes and annotate the plan
        with cross-worker folded per-operator metrics (None detaches —
        back to in-process execution)."""
        self._cluster = cluster

    def query_context(self, **kw):
        """A lifecycle.QueryContext over this session's conf —
        deadline_s / tenant / budget_bytes / query_id overrides ride
        the kwargs. Pass it to ``DataFrame.collect(qctx=...)`` (or
        ``TpuProcessCluster.run_query``) to get a cancel handle:
        ``qctx.cancel()`` stops the query cooperatively with
        QueryCancelled(reason=user)."""
        from .lifecycle import QueryContext
        return QueryContext(self.conf, **kw)

    # --- SQL frontend -----------------------------------------------------
    def register_table(self, name: str, df: Union["DataFrame",
                                                  pa.Table, dict]):
        """Register a DataFrame (or anything create_dataframe accepts)
        as a temp view for ``sql()`` — createOrReplaceTempView analog.
        Names resolve case-insensitively; WITH-clause CTEs shadow
        catalog names."""
        if not isinstance(df, DataFrame):
            df = self.create_dataframe(df)
        self._tables[name.lower()] = df
        return df

    create_or_replace_temp_view = register_table

    def table(self, name: str) -> "DataFrame":
        df = self._tables.get(name.lower())
        if df is None:
            raise KeyError(f"table or view {name!r} is not registered")
        return df

    def _catalog_node(self, name: str):
        """SQL-compiler hook: exec node for a registered view, or
        None."""
        df = self._tables.get(name.lower())
        return df._node if df is not None else None

    def sql(self, text: str) -> Union["DataFrame", str]:
        """Compile a SQL query into a DataFrame over the same planner
        path DataFrames use. ``EXPLAIN <query>`` returns the
        placement-annotated plan text instead (``EXPLAIN FORMATTED``
        the full operator tree) without executing; ``EXPLAIN ANALYZE
        [FORMATTED] <query>`` EXECUTES the query — in process, or
        across an attached cluster's workers (``set_cluster``) — and
        returns the plan annotated with per-operator runtime metrics
        (rows, batches, time, spill, decode coverage; cross-worker
        aggregated with per-task max/skew on the cluster path).
        Parse/analysis failures raise SqlParseError / SqlAnalysisError
        and leave one event-log line (type = the error slug) when
        ``spark.rapids.eventLog.dir`` is set."""
        from .sql import SqlError, sql_to_plan
        from .tools.event_log import log_sql_error
        try:
            node, stmt = sql_to_plan(text, self)
        except SqlError as e:
            log_sql_error(self.conf, e, text)
            raise
        if stmt.explain:
            from .planner import TpuOverrides
            pp = TpuOverrides(self.conf).apply(node)
            if stmt.analyze:
                if self._cluster is not None:
                    return self._cluster.explain_analyze(
                        pp.root, formatted=stmt.formatted)
                pp.collect()
                return pp.explain_analyze(formatted=stmt.formatted)
            if stmt.formatted:
                return pp.root.tree_string()
            return pp.explain("ALL")
        return DataFrame(node, self)

    # --- builders ---------------------------------------------------------
    def create_dataframe(self, data) -> DataFrame:
        """From a pyarrow Table/RecordBatch or a {name: list} dict."""
        if isinstance(data, dict):
            data = pa.table(data)
        if isinstance(data, pa.Table):
            rbs = data.combine_chunks().to_batches()
            schema = data.schema
        elif isinstance(data, pa.RecordBatch):
            rbs = [data]
            schema = data.schema
        else:
            raise TypeError(f"cannot build a DataFrame from {type(data)}")
        from .columnar.arrow_bridge import engine_schema
        # explicit schema: a 0-row table yields no batches
        return DataFrame(HostBatchSourceExec(
            rbs, schema=engine_schema(schema)), self)

    def _read(self, paths, fmt: str, schema=None,
              columns=None) -> DataFrame:
        from .io import TpuFileScanExec
        if isinstance(paths, str):
            paths = [paths]
        scan = TpuFileScanExec(paths, fmt=fmt, schema=schema,
                               conf=self.conf)
        if columns is not None:
            missing = [c for c in columns
                       if c not in scan.output_schema.names]
            if missing:
                raise KeyError(f"columns {missing} not found in "
                               f"{scan.output_schema.names}")
            scan = scan.registered_as(columns)
        return DataFrame(scan, self)

    def read_parquet(self, paths, schema=None, columns=None) -> DataFrame:
        """``columns``: the user's own projection, kept in FILE order;
        the planner's column pruning then narrows within it."""
        return self._read(paths, "parquet", schema, columns)

    def read_csv(self, paths, schema=None) -> DataFrame:
        return self._read(paths, "csv", schema)

    def read_json(self, paths, schema=None) -> DataFrame:
        return self._read(paths, "json", schema)

    def read_orc(self, paths, schema=None) -> DataFrame:
        return self._read(paths, "orc", schema)

    def range(self, n: int) -> DataFrame:
        from .exec.basic import TpuRangeExec
        return DataFrame(TpuRangeExec(0, n), self)