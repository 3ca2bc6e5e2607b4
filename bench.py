"""Driver benchmark: TPC-H q6 at SF1 starting from REAL PARQUET FILES
through the engine's scan->filter->project->aggregate pipeline on the
chip (BASELINE config 1 — SURVEY.md §6, §3.3).

Runs on a TPU or not at all: a timing taken on another backend is not a
device metric, so ``main()`` exits non-zero when JAX finds no TPU
(`chip_smoke.py` is the quicker proof that the engine starts there).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

vs_baseline compares the SAME from-files pipeline on the host (pyarrow
parquet decode + numpy compute — the stand-in for CPU Spark until a
cluster baseline is measured, SURVEY.md §6 action note). Extra keys carry
the compute-only device number (the round-2 metric, for continuity), the
chip's HBM peak, and the achieved-bandwidth fraction so the headline is
roofline-honest (VERDICT r2 weak #1).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

from spark_rapids_tpu.compile_cache import enable_compile_cache

SF_ROWS = 6_001_215  # lineitem rows at SF1
CACHE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".bench_cache", "lineitem")

# chip HBM peak bandwidth by device_kind (public spec sheets; the v5e
# chip reports itself as "TPU v5 lite" — chip_smoke.py prints the string)
HBM_PEAK_GBS = {
    "TPU v2": 700, "TPU v3": 900, "TPU v4": 1228,
    "TPU v5 lite": 819, "TPU v5e": 819, "TPU v5": 2765, "TPU v5p": 2765,
    "TPU v6 lite": 1640, "TPU v6e": 1640,
}


def hbm_peak_gbs(device_kind: str) -> int:
    """The peak for this device; a device that is not in the table is an
    error, not a default."""
    if device_kind not in HBM_PEAK_GBS:
        raise KeyError(
            f"no HBM peak recorded for device_kind {device_kind!r}: add "
            f"it to bench.HBM_PEAK_GBS with its source "
            f"(known: {sorted(HBM_PEAK_GBS)})")
    return HBM_PEAK_GBS[device_kind]


def require_tpu(n_chips: int = 1):
    """The first ``n_chips`` devices JAX found, or exit non-zero naming
    what it found instead: no measurement path falls back to another
    backend."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n_chips:
        sys.exit(f"needs {n_chips} TPU chip(s); JAX found "
                 f"platform={devices[0].platform!r} "
                 f"device_kind={devices[0].device_kind!r} x{len(devices)}")
    return devices[:n_chips]


def gen_lineitem(n):
    """TPC-H-spec-shaped lineitem columns: l_quantity is an integer
    1..50 (spec: random value [1..50]), l_extendedprice = quantity x a
    part's retail price (~200k distinct unit prices), l_discount one of
    11 values, l_shipdate within the date range. Round 4 generated
    uniform random floats for quantity/price — artificially
    incompressible vs the actual benchmark's data, which understated
    every encoding-aware path (device page decode rides dictionary/RLE
    exactly like cuIO does on the reference)."""
    rng = np.random.default_rng(0)
    n_parts = 200_000
    retail = (90000 + (np.arange(n_parts) % 20001) * 5).astype(np.float32)
    part = rng.integers(0, n_parts, n)
    qty = rng.integers(1, 51, n).astype(np.float32)
    return {
        "l_quantity": qty,
        "l_extendedprice": (qty * retail[part] / 100.0)
        .astype(np.float32),
        "l_discount": (rng.integers(0, 11, n) / 100.0).astype(np.float32),
        "l_shipdate": rng.integers(8000, 10600, n).astype(np.int32),
    }


def ensure_parquet(cols, n, n_files=8, cache_dir=CACHE,
                   row_group_size=1 << 20):
    """Materialize lineitem as parquet part files (cached across runs;
    a caller that changes the data shape passes its own ``cache_dir``)."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    paths = [os.path.join(cache_dir, f"part-{i:02d}.parquet")
             for i in range(n_files)]
    if all(os.path.exists(p) for p in paths):
        return paths
    os.makedirs(cache_dir, exist_ok=True)
    per = (n + n_files - 1) // n_files
    for i, p in enumerate(paths):
        lo, hi = i * per, min(n, (i + 1) * per)
        rb = pa.record_batch({k: pa.array(v[lo:hi]) for k, v in cols.items()})
        # dictionary-encode the low-cardinality columns only: price has
        # ~10M distinct values, and a dict-then-fallback mixed chunk
        # carries a dead 1MB dictionary page (write-side tuning any ETL
        # pipeline would apply)
        pq.write_table(pa.Table.from_batches([rb]), p,
                       row_group_size=row_group_size, compression="snappy",
                       use_dictionary=["l_quantity", "l_discount",
                                       "l_shipdate"])
    return paths


def assert_matches_oracle(name, got, want):
    """Engine result (an Arrow table) vs the pandas oracle's frame: row
    count, then per column exact for integers/strings and rtol 1e-5 for
    floats."""
    got = got.to_pandas()
    want = want.reset_index(drop=True)
    assert len(got) == len(want), (name, len(got), len(want))
    for ci, c in enumerate(want.columns):
        w = want[c].to_numpy()
        g = got.iloc[:, ci].to_numpy()
        if np.issubdtype(w.dtype, np.floating):
            assert np.allclose(g.astype(float), w, rtol=1e-5, atol=1e-5), \
                (name, c, g[:5], w[:5])
        else:
            assert (g == w).all(), (name, c, g[:5], w[:5])


def host_q6_from_files(paths):
    """CPU baseline for the same pipeline: parquet decode + numpy q6."""
    import pyarrow.parquet as pq
    t0 = time.perf_counter()
    t = pq.read_table(paths)
    c = {name: t.column(name).to_numpy() for name in
         ("l_quantity", "l_extendedprice", "l_discount", "l_shipdate")}
    mask = ((c["l_shipdate"] >= 8766) & (c["l_shipdate"] < 9131)
            & (c["l_discount"] >= 0.05) & (c["l_discount"] <= 0.07)
            & (c["l_quantity"] < 24.0))
    revenue = float((c["l_extendedprice"][mask]
                     * c["l_discount"][mask]).sum())
    return revenue, time.perf_counter() - t0


def numpy_q6(cols):
    t0 = time.perf_counter()
    mask = ((cols["l_shipdate"] >= 8766) & (cols["l_shipdate"] < 9131)
            & (cols["l_discount"] >= 0.05) & (cols["l_discount"] <= 0.07)
            & (cols["l_quantity"] < 24.0))
    revenue = float((cols["l_extendedprice"][mask]
                     * cols["l_discount"][mask]).sum())
    return revenue, time.perf_counter() - t0


def build_q6(src):
    from spark_rapids_tpu import datatypes as dt
    from spark_rapids_tpu.exec.basic import TpuFilterExec, TpuProjectExec
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.expr import (Alias, And, GreaterThanOrEqual,
                                       LessThan, LessThanOrEqual, Literal,
                                       Multiply, UnresolvedColumn as col)
    from spark_rapids_tpu.expr.aggregates import Sum
    d = lambda v: Literal(np.float32(v), dt.FLOAT32)
    cond = And(
        And(GreaterThanOrEqual(col("l_shipdate"), Literal(8766, dt.DATE)),
            LessThan(col("l_shipdate"), Literal(9131, dt.DATE))),
        And(And(GreaterThanOrEqual(col("l_discount"), d(0.05)),
                LessThanOrEqual(col("l_discount"), d(0.07))),
            LessThan(col("l_quantity"), d(24.0))))
    filt = TpuFilterExec(cond, src)
    proj = TpuProjectExec(
        [Alias(Multiply(col("l_extendedprice"), col("l_discount")),
               "rev")], filt)
    return TpuHashAggregateExec([], [Alias(Sum(col("rev")), "revenue")],
                                proj), cond


def setup_join_groupby(n_li=1 << 23, n_ord=1 << 17):
    """q97/q72-shaped secondary bench: shuffled hash join (lineitem x
    orders on orderkey) -> group-by month -> sum(revenue), through the
    engine's join+aggregate execs.

    Round-4 shape: the build side is a primary-key dimension table, so
    the join takes the sync-free unique-build fast path
    (build_unique_hint; exec/joins.py) — ZERO host readbacks in the
    whole timed pipeline, so nothing but the final block waits on the
    device. Returns (run_fn, host_fn, finish_check_fn, n_li)."""
    import jax

    from spark_rapids_tpu import datatypes as dt
    from spark_rapids_tpu.columnar.batch import TpuBatch, bucket_rows
    from spark_rapids_tpu.columnar.column import TpuColumnVector
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.base import DeviceBatchSourceExec, ExecCtx
    from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
    from spark_rapids_tpu.expr import (Alias, Multiply, Subtract, Literal,
                                       UnresolvedColumn as col)
    from spark_rapids_tpu.expr.aggregates import Sum

    rng = np.random.default_rng(1)
    li = {
        "l_orderkey": rng.integers(0, n_ord, n_li).astype(np.int32),
        "l_extendedprice": rng.uniform(900, 105000, n_li)
        .astype(np.float32),
        "l_discount": (rng.integers(0, 11, n_li) / 100.0)
        .astype(np.float32),
    }
    orders = {
        "o_orderkey": np.arange(n_ord, dtype=np.int32),
        "o_month": rng.integers(1, 13, n_ord).astype(np.int32),
    }

    # host baseline: numpy join (direct gather on the dense key) +
    # bincount — the fastest single-core formulation of this query
    def host_run():
        t0 = time.perf_counter()
        om = orders["o_month"][li["l_orderkey"]]
        rev = (li["l_extendedprice"] * (1.0 - li["l_discount"]))
        out = np.bincount(om, weights=rev.astype(np.float64),
                          minlength=13)
        return out, time.perf_counter() - t0

    def dev_source(cols, schema, batch_rows=1 << 21):
        n = len(next(iter(cols.values())))
        batches = []
        for off in range(0, n, batch_rows):
            m = min(batch_rows, n - off)
            cap = bucket_rows(m)
            cs = [TpuColumnVector.from_numpy(f.dtype,
                                            cols[f.name][off:off + m],
                                            None, cap)
                  for f in schema.fields]
            batches.append(TpuBatch(cs, schema, m))
        return DeviceBatchSourceExec(batches, schema)

    li_schema = dt.Schema([
        dt.StructField("l_orderkey", dt.INT32, False),
        dt.StructField("l_extendedprice", dt.FLOAT32, False),
        dt.StructField("l_discount", dt.FLOAT32, False)])
    ord_schema = dt.Schema([
        dt.StructField("o_orderkey", dt.INT32, False),
        dt.StructField("o_month", dt.INT32, False)])

    join = TpuShuffledHashJoinExec(
        [col("l_orderkey")], [col("o_orderkey")], "inner",
        dev_source(li, li_schema), dev_source(orders, ord_schema),
        build_unique_hint=True)
    rev = Multiply(col("l_extendedprice"),
                   Subtract(Literal(np.float32(1.0), dt.FLOAT32),
                            col("l_discount")))
    plan = TpuHashAggregateExec([col("o_month")],
                                [Alias(Sum(rev), "revenue")], join)
    ctx = ExecCtx()

    def run():
        outs = list(plan.execute(ctx))
        jax.block_until_ready(outs)
        return outs

    def finish_check(outs, host_out):
        from spark_rapids_tpu.columnar.arrow_bridge import device_to_arrow
        got = device_to_arrow(outs[0]).to_pydict()
        want = {m: host_out[m] for m in range(1, 13)}
        for m, v in zip(got["o_month"], got["revenue"]):
            if m == 0:
                continue
            assert abs(v - want[m]) <= 2e-3 * abs(want[m]), \
                (m, v, want[m])

    return run, host_run, finish_check, n_li


def bench_nds_from_files(tmp_dir, n_sales=1 << 20, use_sql=True):
    """NDS-shaped queries with the SCAN in the timed region
    (VERDICT r4 weak #2: the cached geomean is compute-only): tables
    written as snappy parquet once, then per query the engine pipeline
    reads files -> device decode -> query, vs pandas read_parquet + the
    oracle computation on the same files. Two queries bound first-run
    compile time; both place every operator on device. Returns
    (geomean, detail, verify_fn, chunks, op_budget) — the caller runs
    verify after the timed phases. ``chunks`` carries decode coverage
    AND the whole-stage-fusion
    dispatch counters; ``op_budget`` is the per-operator from-files
    time budget mined from the query-profile history each run writes
    (the number that guided the fusion work and that BENCH rounds
    publish)."""
    import math

    import jax
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.exec.base import ExecCtx
    from spark_rapids_tpu.planner import TpuOverrides
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.tools.nds import (build_query,
                                            build_query_sql, gen_tables,
                                            pandas_oracle,
                                            register_frames)
    build = build_query_sql if use_sql else build_query
    order = ["q3", "q55"]
    tables = gen_tables(n_sales=n_sales)
    # cache keyed by the data shape: a gen_tables/n_sales change must
    # invalidate old files or the bench silently times stale data
    tmp_dir = f"{tmp_dir}_n{n_sales}"
    paths = {}
    os.makedirs(tmp_dir, exist_ok=True)
    for name, cols in tables.items():
        p = os.path.join(tmp_dir, f"{name}.parquet")
        if not os.path.exists(p):
            pq.write_table(pa.table(cols), p, row_group_size=1 << 19,
                           compression="snappy")
        paths[name] = p
    s = TpuSession(conf={"spark.sql.shuffle.partitions": "1"})
    frames = {name: s.read_parquet(p) for name, p in paths.items()}
    s._nds_frames = (tables, frames)
    register_frames(s, frames)  # SQL texts resolve the same scans
    results = {}
    ratios = []
    outs = {}
    # decode-coverage across the whole corpus: every planned column
    # chunk counts as device-decoded or host-fallback (the envelope-
    # regression tripwire — acceptance wants ZERO fallbacks here), plus
    # the dispatch-granularity counters: scan_programs = programs the
    # scans dispatched, fused_dispatches = the ones where decode+chain
    # ran as ONE spliced program (whole-stage fusion through the scan)
    chunks = {"device": 0, "fallback": 0, "scan_programs": 0,
              "fused_dispatches": 0}
    # per-operator from-files time budget rides the PR 9 profile
    # history: each query's folded metrics are committed as a profile
    # and mined back below
    # profiles land under the bench cache (not a leaked tempdir): the
    # history stays inspectable via `profiling history/compare` and
    # write_profile's retention pruning bounds it across runs
    hist_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            ".bench_cache", "nds_profiles")
    from spark_rapids_tpu.config import RapidsConf as _RC
    hist_conf = _RC({"spark.rapids.history.dir": hist_dir})
    from spark_rapids_tpu.obs.opmetrics import (build_profile, fold_ctx,
                                                read_profiles,
                                                top_op_sinks,
                                                write_profile)
    prof_inputs = []  # (name, root, ctx, dev_t): folded AFTER timing
    RUNS_FOLDED = 3   # warm-up + 2 timed runs accumulate in one ctx
    for name in order:
        df = build(name, s, tables)
        pp = TpuOverrides(s.conf).apply(df._node)
        ctx = ExecCtx(s.conf)

        def run_dev():
            bs = list(pp.root.execute(ctx))
            jax.block_until_ready(bs)
            return bs
        run_dev()  # warm-up/compile
        # tally coverage from the ONE warm-up execution (the metrics
        # accumulate per run; counting after the timed loop would
        # triple every chunk)
        for node_metrics in ctx.metrics.values():
            for mk, ck in (("deviceChunks", "device"),
                           ("fallbackChunks", "fallback"),
                           ("scanPrograms", "scan_programs"),
                           ("fusedDispatches", "fused_dispatches")):
                if mk in node_metrics:
                    chunks[ck] += node_metrics[mk].value
        times = []
        for _ in range(2):
            t0 = time.perf_counter()
            outs[name] = run_dev()
            times.append(time.perf_counter() - t0)
        dev_t = min(times)
        # profile folding is DEFERRED to finish_profiles(): fold_ctx
        # finalizes deferred row counts with a device_get, which does
        # not belong inside a timed loop
        prof_inputs.append((name, pp.root, ctx, dev_t))

        import pandas as pd

        def host_run():
            t0 = time.perf_counter()
            pdt = {n2: pq.read_table(p).to_pandas()
                   for n2, p in paths.items()}
            pandas_oracle(name, tables, pdt=pdt)
            return time.perf_counter() - t0
        host_t = min(host_run() for _ in range(2))
        results[name] = {"device_ms": round(dev_t * 1e3, 1),
                         "host_ms": round(host_t * 1e3, 1),
                         "vs_host": round(host_t / dev_t, 3)}
        ratios.append(host_t / dev_t)
    geomean = math.exp(sum(math.log(r) for r in ratios) / len(ratios))

    def verify():
        # deferred like bench_nds_subset's: a scan/decode bug must fail
        # the bench, not publish a plausible geomean over wrong rows
        from spark_rapids_tpu.columnar.arrow_bridge import (
            arrow_schema, device_to_arrow)
        pdt = {n2: pq.read_table(p).to_pandas()
               for n2, p in paths.items()}
        for name in order:
            df = build(name, s, tables)
            rbs = [device_to_arrow(b) for b in outs[name]]
            assert_matches_oracle(name, pa.Table.from_batches(
                rbs, schema=arrow_schema(df._node.output_schema)),
                pandas_oracle(name, tables, pdt=pdt))

    def finish_profiles():
        """POST-TIMING phase (the fold's deferred-row-count readback is
        only safe once every timed loop is done): commit one profile
        per query to the history dir and mine the published
        per-operator from-files time budget from them. Each ctx folded
        RUNS_FOLDED executions, so per-run budget times divide by it
        (profiles record runs_folded so `profiling compare` diffs
        like-for-like across rounds)."""
        for name, root, ctx_, dev_t in prof_inputs:
            write_profile(hist_conf, build_profile(
                root, fold_ctx(ctx_), dev_t, query=name,
                source="bench",
                extra={"bench": "nds_from_files",
                       "runs_folded": RUNS_FOLDED}))
        op_budget = {}
        for _, doc in read_profiles(hist_dir):
            runs = max(1, int(doc.get("runs_folded", 1)))
            sinks = top_op_sinks(doc.get("ops", {}), n=5)
            op_budget[doc.get("query", doc.get("profile_id", "?"))] = [
                {"op": sk["op"],
                 "time_ms": round(sk["time_s"] * 1e3 / runs, 1),
                 "rows": int(sk["rows"] / runs)} for sk in sinks]
        return op_budget
    return round(geomean, 3), results, verify, chunks, finish_profiles


def bench_nds_subset(n_sales=1 << 21, use_sql=True):
    """TPC-DS-shaped corpus (spark_rapids_tpu.tools.nds): per query,
    device wall time through the full session/planner path vs the
    pandas oracle on the same tables; returns (geomean vs host,
    per-query dict). Queries whose pipelines are sync-free (unique-dim
    hints) run first; queries with inherent size syncs run last."""
    import math

    import jax

    from spark_rapids_tpu.planner import TpuOverrides
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.tools.nds import (build_query,
                                            build_query_sql, gen_tables,
                                            pandas_frames, pandas_oracle,
                                            register_frames)
    build = build_query_sql if use_sql else build_query
    # six of the corpus queries: the full set lives in
    # tests/test_nds.py; the bench subset bounds FIRST-RUN XLA compile
    # time (the chip's compiler takes tens of seconds to minutes for a
    # fresh sort/agg program at this size — CHANGES.md, PR 21; all are
    # persistent-cached afterwards)
    order = ["q3", "q55", "q96", "q_customer_age", "q_topn",
             "q_price_band"]
    tables = gen_tables(n_sales=n_sales)
    # single-chip tuning (the reference's tuning-guide analog): one
    # shuffle partition — partition-count 16 only multiplies dispatch
    # count on one device; and CACHE the tables device-resident so the
    # comparison matches pandas' in-memory frames
    s = TpuSession(conf={"spark.sql.shuffle.partitions": "1"})
    from spark_rapids_tpu.tools import nds as _nds
    frames = _nds._frames(s, tables)
    for k in list(frames):
        frames[k] = frames[k].cache()
    s._nds_frames = (tables, frames)
    register_frames(s, frames)  # SQL texts see the same cached inputs
    from spark_rapids_tpu.exec.base import ExecCtx
    pd_frames = pandas_frames(tables)  # hoisted: matches cached device
    results = {}
    ratios = []
    outs = {}
    for name in order:
        df = build(name, s, tables)
        pp = TpuOverrides(s.conf).apply(df._node)
        # a query the planner roots on the CPU is a failure here, not a
        # timing to publish under a device metric's name
        assert pp.root_on_device, (name, pp.explain("ALL"))
        ctx = ExecCtx(s.conf)

        def run_dev():
            bs = list(pp.root.execute(ctx))
            jax.block_until_ready(bs)
            return bs
        run_dev()  # warm-up/compile
        times = []
        for _ in range(2):
            t0 = time.perf_counter()
            outs[name] = run_dev()
            times.append(time.perf_counter() - t0)
        dev_t = min(times)
        h_times = []
        for _ in range(2):
            t0 = time.perf_counter()
            want = pandas_oracle(name, tables, pdt=pd_frames)
            h_times.append(time.perf_counter() - t0)
        host_t = min(h_times)
        results[name] = {"device_ms": round(dev_t * 1e3, 1),
                         "host_ms": round(host_t * 1e3, 1),
                         "vs_host": round(host_t / dev_t, 3)}
        ratios.append(host_t / dev_t)
    geomean = math.exp(sum(math.log(r) for r in ratios) / len(ratios))

    def verify():
        # post-timing correctness: every query vs its oracle (the
        # caller runs it after the timed phases)
        import pyarrow as _pa
        from spark_rapids_tpu.columnar.arrow_bridge import (
            arrow_schema, device_to_arrow)
        for name in order:
            df = build(name, s, tables)
            rbs = [device_to_arrow(b) for b in outs[name]]
            assert_matches_oracle(name, _pa.Table.from_batches(
                rbs, schema=arrow_schema(df._node.output_schema)),
                pandas_oracle(name, tables, pdt=pd_frames))
    return round(geomean, 3), results, verify


def main():
    """One process, one chip. Every timed loop still runs before the
    first download and the correctness checks come last; chip_smoke.py
    (PR 21) found no change of dispatch regime after a readback on the
    v5e, so that order is habit, not a requirement (ROADMAP S2/D1)."""
    # before any work: a run on another backend is not a measurement
    dev_kind = require_tpu()[0].device_kind
    enable_compile_cache()
    import jax

    import spark_rapids_tpu  # noqa: F401
    from spark_rapids_tpu import datatypes as dt
    from spark_rapids_tpu.columnar.batch import TpuBatch, bucket_rows
    from spark_rapids_tpu.columnar.column import TpuColumnVector
    from spark_rapids_tpu.exec.base import DeviceBatchSourceExec, ExecCtx
    from spark_rapids_tpu.io import TpuFileScanExec

    # --- timed phase 0: NDS-shaped subset (VERDICT r3 item 7) ------------
    # FIRST, while the device is empty: the later phases' resident
    # arrays degrade allocation-heavy query dispatch (40x on the same
    # cache-warm queries in the round-4 records; not measured on the
    # chip since). Correctness downloads come at the end of the run.
    nds_geomean, nds_detail, nds_verify = bench_nds_subset()
    print(f"nds subset [device_kind={dev_kind}]: geomean "
          f"{nds_geomean}x host pandas; "
          + "; ".join(f"{k} {v['vs_host']}x" for k, v in
                      nds_detail.items()), file=sys.stderr)

    # --- timed phase 0b: NDS from FILES (scan in the timed region) -------
    nds_files_dir = os.path.join(os.path.dirname(
        os.path.abspath(__file__)), ".bench_cache", "nds_parquet")
    (nds_files_geo, nds_files_detail, nds_files_verify, nds_chunks,
     nds_profiles_fn) = bench_nds_from_files(nds_files_dir)
    print(f"nds from-files [device_kind={dev_kind}]: geomean "
          f"{nds_files_geo}x host "
          "(pandas read_parquet + compute); "
          + "; ".join(f"{k} {v['vs_host']}x" for k, v in
                      nds_files_detail.items())
          + f"; chunks device={nds_chunks['device']} "
          f"fallback={nds_chunks['fallback']}; "
          f"fused {nds_chunks['fused_dispatches']}/"
          f"{nds_chunks['scan_programs']} scan programs",
          file=sys.stderr)

    n = SF_ROWS
    cols = gen_lineitem(n)
    paths = ensure_parquet(cols, n)

    schema = dt.Schema([
        dt.StructField("l_quantity", dt.FLOAT32, False),
        dt.StructField("l_extendedprice", dt.FLOAT32, False),
        dt.StructField("l_discount", dt.FLOAT32, False),
        dt.StructField("l_shipdate", dt.DATE, False),
    ])
    ctx = ExecCtx()

    # --- timed phase 1: compute-only over device-resident batches --------
    # (round-2 continuity metric: isolates device compute from host decode;
    # upload-only, no downloads yet)
    batch_rows = 1 << 21
    batches = []
    for off in range(0, n, batch_rows):
        m = min(batch_rows, n - off)
        cap = bucket_rows(m)
        cs = [TpuColumnVector.from_numpy(t, cols[name][off:off + m], None,
                                         cap)
              for name, t in [("l_quantity", dt.FLOAT32),
                              ("l_extendedprice", dt.FLOAT32),
                              ("l_discount", dt.FLOAT32),
                              ("l_shipdate", dt.DATE)]]
        batches.append(TpuBatch(cs, schema, m))
    plan_dev, _ = build_q6(DeviceBatchSourceExec(batches, schema))

    def run_device():
        outs = list(plan_dev.execute(ctx))
        jax.block_until_ready(outs)
        return outs

    run_device()  # warm-up
    dev_times = []
    for _ in range(7):
        t0 = time.perf_counter()
        dev_outs = run_device()
        dev_times.append(time.perf_counter() - t0)
    tpu_dev_t = sorted(dev_times)[len(dev_times) // 2]

    # --- timed phase 1b: Pallas vs XLA A/B on the q6 inner loop ----------
    # (VERDICT r3 item 10: settle SURVEY.md §7.1.3 with data)
    from spark_rapids_tpu.ops.pallas_kernels import (
        masked_product_sum_pallas, masked_product_sum_xla)
    pq, pp_, pd_, ps_ = (batches[0].columns[i].data for i in range(4))
    # reuse phase-1's device-resident first batch, truncated to tiles
    pcap = (pq.shape[0] // (2048 * 128)) * (2048 * 128)
    pargs = (pq[:pcap], pp_[:pcap], pd_[:pcap], ps_[:pcap])
    xla_fn = jax.jit(masked_product_sum_xla)
    r_xla = xla_fn(*pargs)

    def _t(fn):
        ts = []
        for _ in range(7):
            t0 = time.perf_counter()
            fn(*pargs).block_until_ready()
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[3]
    t_xla = _t(xla_fn)
    # a kernel that is kept compiles on the chip or the run fails
    # (tests/test_chip_compile.py asks the chip's compiler first)
    r_pal = masked_product_sum_pallas(*pargs, False)
    jax.block_until_ready((r_xla, r_pal))
    t_pal = _t(lambda *a: masked_product_sum_pallas(*a, False))
    pallas_ab = {
        "xla_ms": round(t_xla * 1e3, 3),
        "pallas_ms": round(t_pal * 1e3, 3),
        "pallas_over_xla": round(t_xla / t_pal, 3),
    }

    import jax.numpy as jnp

    # fused filter+partial-agg A/B (ISSUE 15c): the whole-stage-fusion
    # PR moved the from-files hot loop into ONE program per batch doing
    # filter->project->partial-agg — this measures whether a hand
    # Pallas kernel beats the fused XLA chain ON THAT SHAPE (grouped
    # partial reduction, not the global sum pallas_ab measured).
    from spark_rapids_tpu.ops.pallas_kernels import (
        FUSED_AGG_GROUPS, fused_filter_agg_pallas, fused_filter_agg_xla)
    fa_key = jax.device_put(
        (np.arange(pcap) % FUSED_AGG_GROUPS).astype(np.int32))
    fa_args = (fa_key,) + pargs
    fa_xla = jax.jit(fused_filter_agg_xla)
    r_fxla = fa_xla(*fa_args)
    r_fxla.block_until_ready()

    def _tfa(fn):
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            fn(*fa_args).block_until_ready()
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[2]
    tfa_xla = _tfa(fa_xla)
    r_fpal = fused_filter_agg_pallas(*fa_args, False)
    r_fpal.block_until_ready()
    # float grouped sums: reduction ORDER differs between the tiled
    # kernel and the XLA chain, so equality is a tolerance check
    assert bool(jnp.all(jnp.abs(r_fxla - r_fpal)
                        <= 1e-3 * jnp.maximum(jnp.abs(r_fxla), 1.0))), \
        (r_fxla, r_fpal)
    tfa_pal = _tfa(lambda *a: fused_filter_agg_pallas(*a, False))
    fused_agg_ab = {"xla_ms": round(tfa_xla * 1e3, 3),
                    "pallas_ms": round(tfa_pal * 1e3, 3),
                    "pallas_over_xla": round(tfa_xla / tfa_pal, 3)}

    # --- timed phase 2: FROM FILES (scan -> filter -> proj -> agg) -------
    # one scan exec per timed run would re-plan splits; splits are cheap
    # (footers cached by OS); build the plan once and re-execute.
    scan = TpuFileScanExec(paths, schema=schema)
    plan_files, cond = build_q6(scan)
    scan.pushdown = None  # keep all groups: compare identical row volumes

    def run_files():
        outs = list(plan_files.execute(ctx))
        jax.block_until_ready(outs)
        return outs

    outs = run_files()  # warm-up compile
    file_times = []
    for _ in range(3):
        t0 = time.perf_counter()
        outs = run_files()
        file_times.append(time.perf_counter() - t0)
    tpu_file_t = sorted(file_times)[1]
    # breakdown run: which stage bounds the from-files pipeline (decode
    # is pool-overlapped, upload is the prefetch feeder; VERDICT r3 #3
    # asks the artifact to prove where the time goes)
    for m in ctx.metrics.get(scan.node_label(), {}).values():
        m.value = 0
    t0 = time.perf_counter()
    run_files()
    brk_wall = time.perf_counter() - t0
    sm = ctx.metrics.get(scan.node_label(), {})
    scan_decode_ms = round(sm["scanTime"].value * 1e3, 1) \
        if "scanTime" in sm else None
    scan_upload_ms = round(sm["uploadTime"].value * 1e3, 1) \
        if "uploadTime" in sm else None
    # the overlapped upload pipeline's split: assembleTime is host blob build,
    # uploadTime is device_put + dispatch on the feeder threads, and
    # uploadWaitTime is the only part the CONSUMER actually blocked on —
    # upload_overlap_frac is the share of uploadTime hidden behind
    # compute/pipeline
    scan_assemble_ms = round(sm["assembleTime"].value * 1e3, 1) \
        if "assembleTime" in sm else None
    scan_upload_wait_ms = round(sm["uploadWaitTime"].value * 1e3, 1) \
        if "uploadWaitTime" in sm else None
    upload_overlap_frac = None
    if scan_upload_ms and scan_upload_wait_ms is not None:
        upload_overlap_frac = round(
            max(0.0, 1.0 - sm["uploadWaitTime"].value
                / max(sm["uploadTime"].value, 1e-9)), 3)
    # device page decode (VERDICT r4 #1): encoded bytes crossing the
    # host->device link vs the decoded column bytes they expand to
    enc_b = sm["encodedBytes"].value if "encodedBytes" in sm else 0
    dec_b = sm["decodedBytes"].value if "decodedBytes" in sm else 0
    enc_ratio = round(enc_b / dec_b, 3) if dec_b else None
    # decode coverage over the q6 files (one breakdown run's counts)
    q6_dev_chunks = int(sm["deviceChunks"].value) \
        if "deviceChunks" in sm else 0
    q6_fb_chunks = int(sm["fallbackChunks"].value) \
        if "fallbackChunks" in sm else 0
    # dispatch granularity (the whole-stage-fusion claim, counter-
    # verified): scan_programs = programs dispatched by the scan this
    # run, scan_fused_dispatches = how many ran decode+filter+project+
    # partial-agg as ONE spliced program — equal counts mean every
    # coalesced batch paid exactly one dispatch
    q6_programs = int(sm["scanPrograms"].value) \
        if "scanPrograms" in sm else 0
    q6_fused = int(sm["fusedDispatches"].value) \
        if "fusedDispatches" in sm else 0

    # --- timed phase 2b: observability overhead A/B (same pipeline) ------
    # The "cheap enough to leave always-on" claim of the flight
    # recorder is audited every round: the q6 from-parquet pipeline
    # with recorder + tracing fully ON vs fully OFF (still upload-only).
    # The plan's jit caches are warm from phase 2; only the
    # ExecCtx/conf differ.
    from spark_rapids_tpu.config import RapidsConf as _RC
    import tempfile as _tempfile
    obs_trace_dir = _tempfile.mkdtemp(prefix="bench_obs_trace_")
    obs_wh_dir = _tempfile.mkdtemp(prefix="bench_obs_wh_")
    # the /status endpoint rides the ON side too: an idle daemon
    # accept() thread must cost nothing while queries run
    import socket as _socket
    _probe = _socket.socket()
    _probe.bind(("127.0.0.1", 0))
    obs_status_port = _probe.getsockname()[1]
    _probe.close()
    # opmetrics rides the A/B too: the always-on per-operator
    # accounting (rows/batches/bytes shims, obs/opmetrics.py) must fit
    # inside the same <=5% overhead envelope as the recorder + tracing
    # — and since ISSUE 17 the telemetry-warehouse writer (one counter
    # snapshot + one sealed JSON append per query) does as well
    ctx_obs_off = ExecCtx(_RC({"spark.rapids.flight.enabled": "false",
                               "spark.rapids.metrics.op.enabled":
                               "false",
                               "spark.rapids.warehouse.enabled":
                               "false"}))
    ctx_obs_on = ExecCtx(_RC({"spark.rapids.flight.enabled": "true",
                              "spark.rapids.metrics.op.enabled": "true",
                              "spark.rapids.trace.dir": obs_trace_dir,
                              "spark.rapids.warehouse.enabled": "true",
                              "spark.rapids.warehouse.dir": obs_wh_dir,
                              "spark.rapids.metrics.port":
                              str(obs_status_port)}))
    from spark_rapids_tpu.obs.metrics import maybe_start_http_server
    maybe_start_http_server(ctx_obs_on.conf)

    def _one_obs(c):
        # the flight recorder is a process-wide singleton and the LAST
        # ExecCtx construction above configured it — re-adopt THIS
        # run's conf so the off timing really runs with it off
        from spark_rapids_tpu.obs.attribution import QueryAttribution
        from spark_rapids_tpu.obs.recorder import RECORDER
        RECORDER.configure(c.conf)
        t0 = time.perf_counter()
        # warehouse bracket exactly as planner.collect runs it —
        # except folded={}: fold_ctx finalizes the opm collector with
        # a device readback, which the timed loop leaves out
        attrib = QueryAttribution.begin(c.conf)
        o = list(plan_files.execute(c))
        jax.block_until_ready(o)
        if attrib is not None:
            attrib.finish(root=plan_files, folded={}, qctx=None,
                          wall_s=time.perf_counter() - t0,
                          source="bench")
        return time.perf_counter() - t0
    # interleaved off/on pairs: a block design (3x off, then 3x on)
    # credits any monotonic host drift entirely to the ON side, which
    # on a loaded single-core host can dwarf the layer being measured
    obs_off_ts, obs_on_ts = [], []
    for _ in range(3):
        obs_off_ts.append(_one_obs(ctx_obs_off))
        obs_on_ts.append(_one_obs(ctx_obs_on))
    obs_off_t = sorted(obs_off_ts)[1]
    obs_on_t = sorted(obs_on_ts)[1]
    obs_overhead_frac = round(max(0.0, obs_on_t / obs_off_t - 1.0), 4)
    from spark_rapids_tpu.obs.warehouse import read_rows as _wh_read
    obs_wh_rows = len(_wh_read(obs_wh_dir))
    # the endpoint must serve valid JSON while enabled (read AFTER the
    # timed loops — the HTTP roundtrip is not part of the overhead)
    obs_status_ok = False
    try:
        from urllib.request import urlopen
        with urlopen(f"http://127.0.0.1:{obs_status_port}/status",
                     timeout=5) as resp:
            obs_status_ok = isinstance(json.load(resp), dict)
    except Exception:  # noqa: BLE001 — sandboxed environments
        pass
    print(f"obs overhead [device_kind={dev_kind}]: on "
          f"{obs_on_t*1e3:.1f} ms vs off "
          f"{obs_off_t*1e3:.1f} ms -> {obs_overhead_frac:.1%} "
          f"(warehouse rows {obs_wh_rows}, /status ok {obs_status_ok})",
          file=sys.stderr)
    # restore the process-wide recorder default for the rest of the run
    ExecCtx()

    # --- timed phase 2c: query-lifecycle overhead A/B (same pipeline) ----
    # The lifecycle layer (lifecycle.py) is default-on: every batch of
    # every operator runs a cooperative cancellation/deadline check,
    # and the retry scopes consult the per-query budget. Same audit
    # pattern as obs_overhead_frac: the warm q6 from-parquet pipeline
    # with a QueryContext threaded vs without one (the
    # spark.rapids.lifecycle.enabled=false path), <= 5% to stay
    # default-on.
    from spark_rapids_tpu.lifecycle import QueryContext as _QCtx
    ctx_lc_off = ExecCtx(_RC({"spark.rapids.lifecycle.enabled":
                              "false"}))
    ctx_lc_on = ExecCtx(_RC({}))
    ctx_lc_on.qctx = _QCtx(ctx_lc_on.conf)

    def _time_lc(c):
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            o = list(plan_files.execute(c))
            jax.block_until_ready(o)
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[1]
    lc_off_t = _time_lc(ctx_lc_off)
    lc_on_t = _time_lc(ctx_lc_on)
    lifecycle_overhead_frac = round(
        max(0.0, lc_on_t / lc_off_t - 1.0), 4)
    print(f"lifecycle overhead [device_kind={dev_kind}]: on "
          f"{lc_on_t*1e3:.1f} ms vs off "
          f"{lc_off_t*1e3:.1f} ms -> {lifecycle_overhead_frac:.1%}",
          file=sys.stderr)

    # --- timed phase 2d: whole-stage fusion on/off A/B (same pipeline) ---
    # The dispatch-granularity win, measured: the warm q6 from-parquet
    # pipeline with stageFusion fully ON (scan-rooted splice: ONE
    # program per coalesced batch) vs fully OFF (per-operator dispatch
    # + a full HBM materialization of the decoded batch between scan
    # and chain). Still upload-only; same warm jit caches discipline as
    # the obs/lifecycle A/Bs (the OFF path compiles its own programs on
    # its first run, which is excluded by the warm-up call).
    ctx_fu_on = ExecCtx(_RC({}))
    ctx_fu_off = ExecCtx(_RC(
        {"spark.rapids.sql.stageFusion.enabled": "false"}))

    def _time_fusion(c):
        list(plan_files.execute(c))  # warm-up (compile for this mode)
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            o = list(plan_files.execute(c))
            jax.block_until_ready(o)
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[1]
    fu_on_t = _time_fusion(ctx_fu_on)
    fu_off_t = _time_fusion(ctx_fu_off)
    fusion_ab = {"fused_ms": round(fu_on_t * 1e3, 1),
                 "unfused_ms": round(fu_off_t * 1e3, 1),
                 "fused_speedup": round(fu_off_t / fu_on_t, 3)}
    print(f"whole-stage fusion [device_kind={dev_kind}]: fused "
          f"{fu_on_t*1e3:.1f} ms vs unfused "
          f"{fu_off_t*1e3:.1f} ms -> {fusion_ab['fused_speedup']}x",
          file=sys.stderr)

    # --- timed phase 3: join+group-by (q97/q72 shape), STILL pipelined ---
    # zero host readbacks anywhere in this pipeline (unique-build fast
    # path + hint): only the final block waits on the device
    run_join, host_join, join_check, join_rows = setup_join_groupby()
    join_outs = run_join()  # warm-up compile
    join_times = []
    for _ in range(3):
        t0 = time.perf_counter()
        join_outs = run_join()
        join_times.append(time.perf_counter() - t0)
    join_dev_t = sorted(join_times)[1]

    # --- host baselines (median of 3; host-only, order-safe) -------------
    host_file_times, host_mem_times, host_join_times = [], [], []
    for _ in range(3):
        rev_host, t = host_q6_from_files(paths)
        host_file_times.append(t)
        _, tm = numpy_q6(cols)
        host_mem_times.append(tm)
        host_join_out, tj = host_join()
        host_join_times.append(tj)
    host_file_t = sorted(host_file_times)[1]
    host_mem_t = sorted(host_mem_times)[1]
    host_join_t = sorted(host_join_times)[1]

    # --- post-timing: correctness checks (first downloads happen HERE) ---
    from spark_rapids_tpu.columnar.arrow_bridge import device_to_arrow
    rev_host_mem, _ = numpy_q6(cols)
    for out_batch in (outs[0], dev_outs[0]):
        rev_tpu = device_to_arrow(out_batch).column(0)[0].as_py()
        rel_err = abs(rev_tpu - rev_host_mem) / max(1.0, abs(rev_host_mem))
        assert rel_err < 1e-2, (rev_tpu, rev_host_mem)

    # --- roofline honesty ------------------------------------------------
    bytes_touched = sum(b.device_size_bytes() for b in batches)
    achieved_gbs = bytes_touched / tpu_dev_t / 1e9
    kind = dev_kind
    peak = hbm_peak_gbs(kind)  # an unknown device is an error
    frac = round(achieved_gbs / peak, 3)
    roofline_txt = (f"achieved {achieved_gbs:.0f} GB/s of {kind} "
                    f"peak {peak} GB/s -> {frac}")

    print(f"from-files pipeline [device_kind={dev_kind}]: "
          f"{tpu_file_t*1e3:.1f} ms (host "
          f"{host_file_t*1e3:.1f} ms); compute-only {tpu_dev_t*1e3:.2f} ms "
          f"(host in-mem {host_mem_t*1e3:.2f} ms); "
          f"{roofline_txt}", file=sys.stderr)

    # --- correctness (post-timing: the downloads happen HERE) -----------
    join_check(join_outs, host_join_out)
    nds_verify()
    nds_files_verify()
    # profile fold + history write (does a readback — post-timing only)
    nds_op_budget = nds_profiles_fn()
    assert abs(float(r_xla) - float(r_pal)) <= \
        1e-3 * max(1.0, abs(float(r_xla))), \
        (float(r_xla), float(r_pal))
    join_mrows = round(join_rows / join_dev_t / 1e6, 2)
    join_vs = round(host_join_t / join_dev_t, 3)

    print(f"join+group-by [device_kind={dev_kind}]: {join_mrows} "
          f"Mrows/s ({join_vs}x host numpy)", file=sys.stderr)

    print(json.dumps({
        "metric": "tpch_q6_sf1_from_parquet_rows_per_sec",
        "value": round(n / tpu_file_t / 1e6, 2),
        "unit": "Mrows/s",
        "vs_baseline": round(host_file_t / tpu_file_t, 3),
        "compute_only_mrows_per_sec": round(n / tpu_dev_t / 1e6, 2),
        "compute_only_vs_host_mem": round(host_mem_t / tpu_dev_t, 3),
        "hbm_peak_gbs": peak,
        "hbm_achieved_gbs": round(achieved_gbs, 1),
        "hbm_achieved_frac": frac,
        # from-files breakdown: decode overlaps in the reader pool;
        # assemble+upload+dispatch run on the upload feeder threads and
        # uploadWait is the only serial remainder the consumer saw —
        # upload_overlap_frac = 1 - wait/upload is the share of transfer
        # hidden behind compute/pipeline
        "scan_decode_ms": scan_decode_ms,
        "scan_assemble_ms": scan_assemble_ms,
        "scan_upload_ms": scan_upload_ms,
        "scan_upload_wait_ms": scan_upload_wait_ms,
        "upload_overlap_frac": upload_overlap_frac,
        "scan_breakdown_wall_ms": round(brk_wall * 1e3, 1),
        # the device-page-decode mechanism: dictionary/RLE columns cross
        # the link at their ENCODED size (SURVEY.md §7.2-P5)
        "scan_encoded_mb": round(enc_b / 1e6, 1),
        "scan_decoded_mb": round(dec_b / 1e6, 1),
        "scan_encoded_over_decoded": enc_ratio,
        # decode coverage (ROADMAP item 4 tripwire): planned column
        # chunks device-decoded vs host-fallback — q6 files here, the
        # NDS corpus under nds_scan_*; regressions of the widened
        # envelope (PLAIN strings, V2 pages, DELTA_*) show up as
        # nonzero fallbacks, with per-reason counts in
        # rapids_scan_fallback_chunks_total
        "scan_device_chunks": q6_dev_chunks,
        "scan_fallback_chunks": q6_fb_chunks,
        "nds_scan_device_chunks": nds_chunks["device"],
        "nds_scan_fallback_chunks": nds_chunks["fallback"],
        # whole-stage fusion (ISSUE 15): dispatch granularity on the
        # from-files path, counter-verified — fused == programs means
        # every coalesced batch ran decode+filter+project+partial-agg
        # as ONE spliced XLA program (was >= 2 dispatches + an HBM
        # round-trip of the decoded batch). fusion_ab is the measured
        # on/off wall delta on the warm q6 pipeline.
        "scan_programs": q6_programs,
        "scan_fused_dispatches": q6_fused,
        "nds_scan_programs": nds_chunks["scan_programs"],
        "nds_scan_fused_dispatches": nds_chunks["fused_dispatches"],
        "fusion_ab": fusion_ab,
        # per-operator from-files time budget, mined from the query
        # profiles this run wrote (PR 9 profile history): where each
        # NDS from-files query actually spends its time, per operator
        "nds_from_files_op_budget": nds_op_budget,
        # observability overhead audit (flight recorder + tracing fully
        # on vs fully off, same warm q6 from-parquet pipeline): the
        # always-on claim requires this to stay <= 0.05
        "obs_overhead_frac": obs_overhead_frac,
        "obs_on_ms": round(obs_on_t * 1e3, 1),
        "obs_off_ms": round(obs_off_t * 1e3, 1),
        # the ON side of the A/B above also ran the ISSUE 17 telemetry
        # warehouse (one sealed row per timed run) and the /status
        # endpoint; rows written + endpoint liveness, audited here so a
        # silently-disabled warehouse can't fake a low overhead number
        "obs_warehouse_rows": obs_wh_rows,
        "obs_status_ok": obs_status_ok,
        # query-lifecycle overhead audit (per-batch cancellation/
        # deadline checks + budget-aware retry scopes, QueryContext
        # threaded vs lifecycle off, same warm pipeline): the
        # default-on claim requires this to stay <= 0.05
        "lifecycle_overhead_frac": lifecycle_overhead_frac,
        "lifecycle_on_ms": round(lc_on_t * 1e3, 1),
        "lifecycle_off_ms": round(lc_off_t * 1e3, 1),
        "join_agg_mrows_per_sec": join_mrows,
        "join_agg_vs_host": join_vs,
        "nds_subset_geomean_vs_host": nds_geomean,
        "nds_subset_detail": nds_detail,
        # the corpus is driven from SQL text (tools/nds.py SQL_QUERIES
        # through session.sql) — the hand-built plans remain only as
        # the dual-run oracle counterpart
        "nds_driven_from_sql": True,
        # scans in the timed region (VERDICT r4 weak #2): engine
        # files->device-decode->query vs pandas read_parquet + compute
        "nds_subset_from_files_vs_host": nds_files_geo,
        "nds_from_files_detail": nds_files_detail,
        # Pallas vs XLA (SURVEY.md §7.1.3). pallas_ab is the q6 inner
        # loop — the fused elementwise+reduce shape; pallas_fused_agg_ab
        # is the whole-stage-fusion shape itself (grouped partial
        # reduction, ISSUE 15c). Both kernels compile for the v5e
        # (tests/test_chip_compile.py); the gather and bitonic-sort
        # kernels the chip's compiler refuses were deleted in PR 21.
        "pallas_ab": pallas_ab,
        "pallas_fused_agg_ab": fused_agg_ab,
        "device_kind": kind,
    }))


if __name__ == "__main__":
    main()
