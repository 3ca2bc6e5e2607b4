"""The quickest proof that the engine still starts on the chip.

    python chip_smoke.py            # one TPU chip: types, nds
    python chip_smoke.py --chips 4  # four chips: the ICI exchange only

ONE process drives the engine's normal query path — ``TpuSession`` ->
``session.sql`` -> ``TpuOverrides`` -> ``TpuFileScanExec`` device decode
-> fused stage -> join / aggregate / sort / exchange -> Arrow download —
over shapes no cell of the benchmark runs (a types round trip, the NDS
star's queries from SQL text, the four-chip ICI exchange), checks every
result against an independent reference, and fails (non-zero exit, no
result line) when any phase fails or when JAX finds no TPU. Speed is
``python3 benchmark/run.py --workload <cell>`` and the ledger; the
instruments here (compile meter, scan counters, the HBM peak, the device
requirement) ARE the benchmark's, loaded from ``benchmark/``. Data is
generated from fixed seeds into the git-ignored ``.bench_cache/``;
nothing is read that a clean checkout does not hold. It sets no
``JAX_PLATFORMS``, no ``XLA_FLAGS`` and starts no child process.

Every one-chip query runs twice in the same process (cold, then warm
after the first result was downloaded) with the XLA compile requests,
persistent cache hits and compile seconds of each run printed beside
its wall seconds. The last line of stdout is the result:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The phases are functions of their sizes (``main`` passes the real ones)
so tests/test_chip_smoke.py can drive the same control flow on the CPU
mesh at a few thousand rows.
"""
import argparse
import collections
import json
import os
import sys
import time

import numpy as np
import pyarrow as pa

from spark_rapids_tpu.compile_cache import CHECKOUT, enable_compile_cache

BENCHMARK_DIR = os.path.join(CHECKOUT, "benchmark")
if BENCHMARK_DIR not in sys.path:  # no package: found as run.py finds them
    sys.path.insert(0, BENCHMARK_DIR)
import run as benchmark_run  # noqa: E402  (benchmark/run.py)
from compile_meter import CompileMeter  # noqa: E402

scan_counters = benchmark_run.scan_counters
DATA_DIR = os.path.join(CHECKOUT, ".bench_cache")

#: the NDS queries the chip run drives (scan + 2 joins + group-by +
#: order-by + limit; scan + sort + limit). q55, q96 and q_customer_age
#: were cut for their cold compile cost (CHANGES.md, PR 21); phase_nds
#: takes any query of tools/nds.py and the CPU test runs all five.
NDS_QUERIES = ("q3", "q_topn")


# --- measuring ---------------------------------------------------------------

_METER = None


def meter() -> CompileMeter:
    """The benchmark's compile meter, listening for the whole process."""
    global _METER
    if _METER is None:
        _METER = CompileMeter().__enter__()
    return _METER


def compile_traffic(before) -> str:
    d = meter().since(before)
    return (f"compile_requests={d['requests']} "
            f"persistent_cache_hits={d['hits']} "
            f"compile_s={d['seconds']:.2f} "
            f"cold_compile_s={d['seconds'] + d['saved']:.2f}")


def cold_warm(name, once, labels=("cold", "warm")):
    """Run ``once()`` once per label (twice by default); print wall
    seconds and compiler traffic of each run; return the results. The
    warm run starts after the cold run's result was downloaded, so a
    dispatch regime that changed with the first readback would show
    here."""
    m = meter()
    out = []
    for label in labels:
        before = m.snapshot()
        t0 = time.perf_counter()
        out.append(once())
        wall = time.perf_counter() - t0
        print(f"  {name} {label}: wall_s={wall:.3f} "
              f"{compile_traffic(before)}", flush=True)
    return out


def phase(name, fn, *args, **kw):
    """One phase; an exception ends the run (no try/except: a phase that
    fails must fail the script)."""
    print(f"== phase {name}", flush=True)
    t0 = time.perf_counter()
    before = meter().snapshot()
    out = fn(*args, **kw)
    print(f"== phase {name} ok: elapsed_s={time.perf_counter() - t0:.1f} "
          f"{compile_traffic(before)}", flush=True)
    return out


def smoke_conf(extra=None):
    """One shuffle partition (single-chip tuning) and a warehouse
    directory, so each collect leaves the telemetry row whose
    ``device_kind`` the NDS phase checks."""
    conf = {"spark.sql.shuffle.partitions": "1",
            "spark.rapids.warehouse.dir":
                os.path.join(DATA_DIR, "smoke_warehouse")}
    conf.update(extra or {})
    return conf


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


def planned(df, session):
    """The DataFrame through ``TpuOverrides``; every operator must have
    been placed on the device — a CPU island or a CPU root is a failure
    here, never a reason to run ``execute_cpu``."""
    from spark_rapids_tpu.planner import TpuOverrides
    pp = TpuOverrides(session.conf).apply(df._node)
    assert pp.root_on_device and not pp.fallback_nodes(), \
        pp.explain("ALL")
    return pp


# --- phase nds ---------------------------------------------------------------

def phase_nds(n_sales, row_group_rows, queries=NDS_QUERIES):
    """The NDS-shaped star from Parquet files, each query from SQL TEXT
    through ``session.sql``, checked against the pandas oracle; each
    ``collect()`` leaves a warehouse row naming the device it ran on."""
    import jax
    import pyarrow.parquet as pq

    from spark_rapids_tpu.obs.warehouse import read_rows
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.tools.nds import (build_query_sql, gen_tables,
                                            pandas_frames, pandas_oracle,
                                            register_frames)
    tables = gen_tables(n_sales=n_sales)
    data_dir = os.path.join(DATA_DIR,
                            f"smoke_nds_n{n_sales}_g{row_group_rows}")
    os.makedirs(data_dir, exist_ok=True)
    paths = {}
    for name, table in tables.items():
        paths[name] = os.path.join(data_dir, f"{name}.parquet")
        if not os.path.exists(paths[name]):
            pq.write_table(table, paths[name],
                           row_group_size=row_group_rows,
                           compression="snappy")
    s = TpuSession(conf=smoke_conf())
    frames = {name: s.read_parquet(p) for name, p in paths.items()}
    s._nds_frames = (tables, frames)  # build_query_sql reuses these scans
    register_frames(s, frames)
    oracle_frames = pandas_frames(tables)
    totals = collections.Counter()
    t_first = time.time()
    for name in queries:
        pp = planned(build_query_sql(name, s, tables), s)
        want = pandas_oracle(name, tables, pdt=oracle_frames)
        for got in cold_warm(name, pp.collect):
            assert_matches_oracle(name, got, want)
        c = scan_counters(pp)
        assert c["fallbackChunks"] == 0 and c["deviceChunks"] > 0, (name, c)
        totals.update(c)
        print(f"  {name} rows_out={len(want)} matches the pandas oracle "
              f"(exact ints, rtol 1e-5 floats)")
    print(f"  nds n_sales={n_sales} queries={len(queries)} "
          + " ".join(f"{k}={v}" for k, v in totals.items()))

    # the telemetry row each collect left names the device it ran on
    rows = [r for r in read_rows(s.conf.get("spark.rapids.warehouse.dir"))
            if r["ts"] >= t_first]
    kind = jax.devices()[0].device_kind
    assert len(rows) == 2 * len(queries) \
        and all(r["device_kind"] == kind for r in rows), \
        ([r["device_kind"] for r in rows], kind)
    print(f"  nds warehouse rows={len(rows)} name device_kind={kind!r}")


# --- phase types -------------------------------------------------------------

def phase_types():
    """Arrow -> device -> Arrow of one batch, then ``a + b`` and an
    ORDER BY on float64 through the engine. Everything but float64 must
    come back bit-exact; for float64 the finding is printed and the
    engine is held to 2^-44 relative (well below float32's 2^-24)."""
    import datetime

    from spark_rapids_tpu.columnar.arrow_bridge import (arrow_to_device,
                                                        device_to_arrow)
    from spark_rapids_tpu.session import TpuSession
    big = 1 << 62
    a = np.array([0.1, 1.0 / 3.0, np.pi, 1.0 + 2.0 ** -30, 1.0 + 2.0 ** -52,
                  -2.5e-7, 123456789.123456789, 1e30])
    b = np.array([0.2, 2.0 / 3.0, np.e, 2.0 ** -31, 2.0 ** -52,
                  1e-7, 0.000000001, 3e30])
    rb = pa.record_batch({
        "i": pa.array([big - 1, -big + 1, big - 3, 0, -1, None, 7,
                       -big + 5], pa.int64()),
        "a": pa.array(a, pa.float64()),
        "b": pa.array(b, pa.float64()),
        "s": pa.array(["alpha", None, "", "βeta-ütf8", "x" * 70,
                       None, "tab\there", "z"], pa.string()),
        "d": pa.array([datetime.date(1994, 1, 1), None,
                       datetime.date(1970, 1, 1),
                       datetime.date(2262, 4, 11),
                       datetime.date(1899, 12, 31),
                       datetime.date(2000, 2, 29),
                       datetime.date(1995, 1, 1), None], pa.date32()),
        "t": pa.array([True, False, None, True, False, None, True, True],
                      pa.bool_()),
    })
    back = device_to_arrow(arrow_to_device(rb))
    for name in ("i", "s", "d", "t"):
        assert back.column(name).equals(rb.column(name)), \
            (name, back.column(name), rb.column(name))
    got_a = back.column("a").to_numpy()
    exact_rt = bool((got_a.view(np.int64) == a.view(np.int64)).all())
    rel_rt = float(np.max(np.abs(got_a - a) / np.abs(a)))
    assert rel_rt <= 2.0 ** -44, (got_a, a)

    s = TpuSession(conf=smoke_conf())
    s.register_table("t", s.create_dataframe(rb))
    got_sum = planned(s.sql("SELECT a + b AS c FROM t"), s).collect() \
        .column("c").to_numpy()
    want_sum = a + b
    exact_add = bool((got_sum.view(np.int64)
                      == want_sum.view(np.int64)).all())
    rel_add = float(np.max(np.abs(got_sum - want_sum) / np.abs(want_sum)))
    assert rel_add <= 2.0 ** -44, (got_sum, want_sum)
    print(f"  types float64 round trip bit-exact: {exact_rt} "
          f"(max rel err {rel_rt:.3e})")
    print(f"  types float64 a + b kept 53 bits: {exact_add} "
          f"(max rel err {rel_add:.3e}; 2^-24 = 5.96e-08, "
          f"2^-53 = 1.11e-16)")

    # keys that differ only below float32 precision must still order
    keys = 1.0 + np.arange(1, 65, dtype=np.float64) * 2.0 ** -40
    shuffled = np.random.default_rng(3).permutation(keys)
    s.register_table("k", s.create_dataframe(
        pa.table({"x": pa.array(shuffled, pa.float64())})))
    got_keys = planned(s.sql("SELECT x FROM k ORDER BY x"), s).collect() \
        .column("x").to_numpy()
    assert (np.diff(got_keys) > 0).all() and \
        np.allclose(got_keys, keys, rtol=2.0 ** -44, atol=0), got_keys
    print("  types ORDER BY float64 keys 2^-40 apart: ascending")
    print("  types int64 near +-2^62, strings with nulls, dates, "
          "booleans: bit-exact round trip")


# --- phase ici (--chips 4) ---------------------------------------------------

def _peaks(devices):
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def phase_ici(devices, n_fact, n_dim, map_batches_per_chip=2):
    """The in-process ICI exchange over a Mesh of ``devices``: planner-
    built ShuffleExchange(IciShuffleTransport) on both sides of a
    shuffled hash join feeding a hash aggregate (a STRING lane rides the
    dimension side, so byte payloads cross the interconnect too), and
    the folded-partition group-by of ``__graft_entry__`` with its string
    key — each compared with (a) an independent oracle and (b) the same
    plan on one device with the default local transport, exactly. After
    the ICI run, the evidence that the exchange really spread: landed
    partitions sit on distinct devices, every device's peak memory rose
    where the backend reports it, and the compiled program holds an
    all-to-all."""
    import jax
    from jax.sharding import Mesh

    from __graft_entry__ import (exchange_groupby_plan,
                                 exchange_join_agg_plan, groupby_batches)
    from spark_rapids_tpu import datatypes as dt
    from spark_rapids_tpu.columnar.arrow_bridge import arrow_to_device
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.exec.base import (DeviceBatchSourceExec,
                                            collect_arrow_cpu)
    from spark_rapids_tpu.expr import (Alias, Length, StartsWith,
                                       UnresolvedColumn as col)
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.expr.base import Literal
    from spark_rapids_tpu.expr.conditional import If
    from spark_rapids_tpu.planner import TpuOverrides
    from spark_rapids_tpu.shuffle.ici import IciShuffleTransport
    n_dev = len(devices)
    mesh = Mesh(np.array(devices), ("x",))
    # AQE off: its join switch would demote this join to a broadcast
    # (the dimension side is small) and skip the fact side's exchange;
    # BOTH sides must cross the interconnect
    conf = RapidsConf(smoke_conf({"spark.sql.adaptive.enabled": "false"}))
    peaks_before = _peaks(devices)

    def collect_sorted(plan, keys):
        pp = TpuOverrides(conf).apply(plan)
        assert pp.root_on_device and not pp.fallback_nodes(), \
            pp.explain("ALL")
        return pp.collect().to_pandas().sort_values(keys) \
            .reset_index(drop=True)

    # -- exchange x exchange -> join -> aggregate, at the join phase's scale
    rng = np.random.default_rng(11)
    tags = np.array(["ash", "birch", "cedar", "oak", "sycamore"])
    amt = rng.integers(1, 1000, n_fact).astype(np.int64)
    grp = rng.integers(1, 13, n_dim).astype(np.int32)
    tag_id = rng.integers(0, len(tags), n_dim)
    n_map = map_batches_per_chip * n_dev
    per = n_fact // n_map
    assert per * n_map == n_fact, (n_fact, n_map)
    # uniform keys: partitions come out as unequal as the engine's hash
    # partitioner makes them (at 2^23 rows a destination receives
    # 2^20 +- ~900 rows an epoch, so landed batches straddle a capacity
    # bucket)
    fk = rng.integers(0, n_dim, n_fact).astype(np.int32)

    def sources():
        fact = [arrow_to_device(pa.record_batch({
            "fk": pa.array(fk[i * per:(i + 1) * per]),
            "amt": pa.array(amt[i * per:(i + 1) * per])}))
            for i in range(n_map)]
        dim = [arrow_to_device(pa.record_batch({
            "dk": pa.array(np.arange(n_dim, dtype=np.int32)),
            "grp": pa.array(grp),
            "tag": pa.array(tags[tag_id].tolist(), pa.string())}))]
        return (DeviceBatchSourceExec(fact, fact[0].schema),
                DeviceBatchSourceExec(dim, dim[0].schema))

    witness = {"landed": [], "capacities": [], "programs": []}

    class WitnessTransport(IciShuffleTransport):
        """Notes where each partition it hands over had landed, and the
        shapes the exchange program ran with."""

        def __init__(self):
            super().__init__(mesh, conf=conf)
            program = self._exchange

            def noting(*a, **k):
                witness["programs"].append((program, jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(
                        x.shape, x.dtype, sharding=x.sharding)
                    if isinstance(x, jax.Array) else x, (a, k))))
                return program(*a, **k)
            self._exchange = noting

        def read_partition(self, shuffle_id, partition_id):
            witness["landed"].append(
                self.landed_devices(shuffle_id)[partition_id])
            for b in super().read_partition(shuffle_id, partition_id):
                witness["capacities"].append(b.capacity)
                yield b

    # the dimension's STRING lane crosses the interconnect as byte
    # payloads, rides through the join, and reaches the result as two
    # exact integers per group: its characters, and its rows starting "s"
    # (string group KEYS are compared exactly in the small group-by below)
    keys = ["grp"]
    one, zero = Literal(1, dt.INT32), Literal(0, dt.INT32)
    string_aggs = [
        Alias(Sum(Length(col("tag"))), "tag_chars"),
        Alias(Sum(If(StartsWith(col("tag"), "s"), one, zero)), "s_rows")]
    ici_plan = exchange_join_agg_plan(*sources(), n_dev, keys,
                                      WitnessTransport, string_aggs)
    # once: four chips wait while the host compiles, and a repeat would
    # show nothing the one-chip phases' warm passes do not
    [ici_join] = cold_warm("ici_join_agg",
                           lambda: collect_sorted(ici_plan, keys),
                           labels=("cold",))
    want = {"t": np.zeros(13, np.int64), "n": np.zeros(13, np.int64),
            "tag_chars": np.zeros(13, np.int64),
            "s_rows": np.zeros(13, np.int64)}
    fact_grp, fact_tag = grp[fk], tags[tag_id[fk]]
    np.add.at(want["t"], fact_grp, amt)
    np.add.at(want["n"], fact_grp, 1)
    np.add.at(want["tag_chars"], fact_grp, np.char.str_len(fact_tag))
    np.add.at(want["s_rows"], fact_grp, np.char.startswith(fact_tag, "s"))
    live = np.nonzero(want["n"])[0]

    def assert_equals_numpy(got, label):
        assert (got["grp"].to_numpy() == live).all(), (label, got)
        for name, w in want.items():
            assert (got[name].to_numpy() == w[live]).all(), (label, name)

    assert_equals_numpy(ici_join, "ici")
    print(f"  ici join+agg {n_fact} x {n_dim} rows over {n_dev} devices: "
          f"{len(ici_join)} groups equal numpy exactly", flush=True)

    # -- was the work really spread?
    # one epoch for the dimension side, one per n_dev map batches for the
    # fact side
    epochs = len(witness["programs"])
    assert epochs == 1 + map_batches_per_chip, epochs
    # landing compacts: no batch handed over is as wide as the n_dev
    # blocks it landed in
    capacities = sorted(set(witness["capacities"]))
    assert capacities[-1] < n_dev * per, (capacities, per)
    landed = witness["landed"]
    owners = sorted({d for part in landed for d in part})
    print(f"  ici partitions handed to the join had landed on device ids "
          f"{landed} at capacities {capacities}")
    assert all(len(part) == 1 for part in landed) \
        and owners == sorted(d.id for d in devices), (landed, devices)
    peaks_after = _peaks(devices)
    print(f"  ici peak_bytes_in_use per device before={peaks_before} "
          f"after={peaks_after}")
    if any(peaks_after):  # the CPU backend reports no memory stats
        assert all(a > b for a, b in zip(peaks_after, peaks_before)), \
            (peaks_before, peaks_after)
    program, (args, kwargs) = witness["programs"][0]
    hlo = program.lower(*args, **kwargs).compile().as_text()
    n_a2a = hlo.count(" all-to-all(") + hlo.count(" all-to-all-start(")
    print(f"  ici compiled exchange holds {n_a2a} all-to-all ops over "
          f"{n_dev} devices", flush=True)
    assert n_a2a > 0, hlo[:2000]

    # -- the same plan, as many partitions, on ONE device with the default
    # local transport
    [local_join] = cold_warm(
        "local_join_agg", lambda: collect_sorted(exchange_join_agg_plan(
            *sources(), n_dev, keys, extra_aggs=string_aggs), keys),
        labels=("cold",))
    assert_equals_numpy(local_join, "local")
    assert ici_join.equals(local_join), (ici_join, local_join)
    print(f"  ici join+agg equals the same {n_dev}-partition plan on one "
          f"device with the local transport exactly", flush=True)

    # -- the folded-partition group-by (2 x n_dev partitions, string key)
    rbs = groupby_batches(2 * n_dev, np.random.default_rng(7))
    gkeys = ["k", "tag"]
    g_ici = collect_sorted(exchange_groupby_plan(
        rbs, 2 * n_dev, transport=IciShuffleTransport(mesh, conf=conf)),
        gkeys)
    local_plan = exchange_groupby_plan(rbs, 2 * n_dev)
    g_local = collect_sorted(local_plan, gkeys)
    g_cpu = collect_arrow_cpu(local_plan).to_pandas().sort_values(gkeys) \
        .reset_index(drop=True)
    assert g_ici.equals(g_cpu) and g_local.equals(g_cpu), \
        (g_ici, g_local, g_cpu)
    print(f"  ici group-by {2 * n_dev} folded partitions: {len(g_ici)} "
          f"groups equal the CPU oracle and the local transport exactly")


# --- main --------------------------------------------------------------------

def hbm_peak_gbs(device_kind: str) -> int:
    """The benchmark's peak for this device (``benchmark/peaks.json``); a
    device that is not in the table is a ``KeyError``, never a default."""
    return benchmark_run.load_json(BENCHMARK_DIR,
                                   "peaks.json")[device_kind]["hbm_gbs"]


def report_device(devices):
    """Device facts the records need, and the budget the engine derived
    from them (an unknown TPU kind or a missing bytes_limit is an
    error)."""
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.memory import ALLOC_FRACTION, resolve_device_budget
    d = devices[0]
    stats = d.memory_stats() or {}
    conf = RapidsConf(smoke_conf())
    budget = resolve_device_budget(conf)
    print(f"device platform={d.platform} device_kind={d.device_kind!r} "
          f"count={len(devices)} hbm_peak_gbs="
          f"{hbm_peak_gbs(d.device_kind)}")
    print(f"memory_stats keys={sorted(stats)}")
    print(f"memory bytes_limit={stats.get('bytes_limit')} "
          f"engine_budget={budget} "
          f"(allocFraction {conf.get(ALLOC_FRACTION)})")
    assert budget == int(stats["bytes_limit"] * conf.get(ALLOC_FRACTION)), \
        (budget, stats)


def single_chip_phases():
    """(name, function, arguments) in run order. The list is STATIC —
    what runs never depends on the clock or on what the compile cache
    holds — and sized so that a run with an EMPTY cache fits the 1200 s
    the driver allows, nearly all of it compilation (PERF.md section 6,
    PR 21). Q6 from files and a join + group-by are cells of the
    benchmark (``tpch-sf1.q6.files``, ``tpcds-sf1-store.q3.files``), at
    the sources' shapes and tighter limits, and are not repeated here."""
    return [
        ("types", phase_types, ()),
        ("nds", phase_nds, (1 << 21, 1 << 19)),
    ]


def result_line(devices) -> str:
    """The one JSON object the driver reads off the last line."""
    return json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    try:
        devices = benchmark_run.require_devices(args.chips, None)
    except benchmark_run.Refused as e:  # no TPU, no result line
        sys.exit(str(e))
    cache_dir = enable_compile_cache()
    import spark_rapids_tpu  # noqa: F401  (x64 on before any array)
    before = meter().snapshot()
    print(f"compile cache dir={cache_dir} "
          f"(JAX_COMPILATION_CACHE_DIR "
          f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    report_device(devices)
    phases = [("ici", phase_ici, (devices, 1 << 23, 1 << 17))] \
        if args.chips == 4 else single_chip_phases()
    for name, fn, fn_args in phases:
        phase(name, fn, *fn_args)
    print(f"total: phases={','.join(name for name, _, _ in phases)} "
          f"elapsed_s={time.perf_counter() - t_start:.1f} "
          f"{compile_traffic(before)}")
    for d in devices:
        stats = d.memory_stats() or {}
        print(f"memory device={d.id} bytes_limit={stats.get('bytes_limit')} "
              f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(result_line(devices))
    return 0


if __name__ == "__main__":
    sys.exit(main())
