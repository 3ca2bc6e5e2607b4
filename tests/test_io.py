"""File scan + write tests (reference: integration_tests parquet_test.py /
orc_test.py / csv_test.py / *_write_test.py — SURVEY.md §4.1; reader
modes + round-trip shapes from §2.2-B Scans/Writes)."""
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from asserts import assert_tpu_and_cpu_plan_equal
from data_gen import (all_basic_gens, gen_table, DateGen, DecimalGen,
                      IntegerGen, LongGen, FloatGen, StringGen)

from spark_rapids_tpu import datatypes as dt
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.exec.base import ExecCtx, collect_arrow, \
    collect_arrow_cpu
from spark_rapids_tpu.exec.basic import TpuFilterExec, TpuProjectExec
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.expr import (Alias, And, GreaterThanOrEqual, LessThan,
                                   Literal, Multiply,
                                   UnresolvedColumn as col)
from spark_rapids_tpu.expr.aggregates import Sum
from spark_rapids_tpu.io import (FileSplit, TpuFileScanExec,
                                 TpuFileWriteExec, plan_splits)
from spark_rapids_tpu.planner import overrides


def _canon(table):
    """to_pydict with NaN mapped to a comparable token (NaN != NaN)."""
    import math
    return {name: ["NaN" if isinstance(v, float) and math.isnan(v) else v
                   for v in vals]
            for name, vals in table.to_pydict().items()}


def _write_parquet(tmp_path, rb, name="data.parquet", row_group_size=None):
    p = os.path.join(str(tmp_path), name)
    pq.write_table(pa.Table.from_batches([rb]), p,
                   row_group_size=row_group_size)
    return p


def test_parquet_scan_all_basic_types(tmp_path):
    rb = gen_table(all_basic_gens, n=500)
    p = _write_parquet(tmp_path, rb)
    assert_tpu_and_cpu_plan_equal(TpuFileScanExec([p]))


def test_parquet_scan_multi_file_reader_modes(tmp_path):
    paths = []
    for i in range(6):
        rb = gen_table([IntegerGen(), StringGen(), FloatGen(dt.FLOAT64)],
                       n=200 + i, seed=100 + i)
        paths.append(_write_parquet(tmp_path, rb, f"f{i}.parquet"))
    results = {}
    for mode in ("PERFILE", "MULTITHREADED", "COALESCING"):
        conf = RapidsConf({
            "spark.rapids.sql.format.parquet.reader.type": mode})
        scan = TpuFileScanExec(paths, conf=conf)
        results[mode] = assert_tpu_and_cpu_plan_equal(scan, conf=conf)
    # all reader modes agree (same rows, same order: split-ordered)
    assert _canon(results["PERFILE"]) == _canon(results["MULTITHREADED"])
    assert sorted(map(tuple, results["PERFILE"].to_pylist()[0:0])) == []
    assert results["COALESCING"].num_rows == results["PERFILE"].num_rows


def test_parquet_row_group_splits(tmp_path):
    rb = gen_table([LongGen(null_frac=0)], n=4000)
    p = _write_parquet(tmp_path, rb, row_group_size=256)
    splits = plan_splits([p], "parquet", max_partition_bytes=8 << 10)
    assert len(splits) > 1
    covered = [g for s in splits for g in s.row_groups]
    assert covered == sorted(set(covered))  # disjoint + complete
    assert_tpu_and_cpu_plan_equal(TpuFileScanExec([p]))


def test_parquet_column_projection(tmp_path):
    rb = gen_table([IntegerGen(), StringGen(), DateGen()],
                   names=["a", "b", "c"])
    p = _write_parquet(tmp_path, rb)
    scan = TpuFileScanExec([p], columns=["c", "a"])
    assert scan.output_schema.names == ["c", "a"]
    assert_tpu_and_cpu_plan_equal(scan)


def test_parquet_predicate_pushdown_prunes_and_stays_correct(tmp_path):
    # ascending key -> row group stats are tight -> pruning provable
    n = 4096
    key = pa.array(np.arange(n, dtype=np.int64))
    val = pa.array(np.arange(n, dtype=np.float64) * 0.5)
    rb = pa.record_batch({"k": key, "v": val})
    p = _write_parquet(tmp_path, rb, row_group_size=512)
    cond = And(GreaterThanOrEqual(col("k"), Literal(1000, dt.INT64)),
               LessThan(col("k"), Literal(1500, dt.INT64)))
    scan = TpuFileScanExec([p], pushdown=cond)
    plan = TpuFilterExec(cond, scan)
    out = assert_tpu_and_cpu_plan_equal(plan)
    assert out.num_rows == 500
    # pruning really skipped groups: decode only touches 2 of 8
    from spark_rapids_tpu.io.scan import _decode_split, _simple_conjuncts
    rbs = _decode_split(FileSplit(p), "parquet", None, 1 << 20,
                        _simple_conjuncts(cond))
    assert sum(r.num_rows for r in rbs) <= 1024


def test_parquet_device_decode_matrix(tmp_path):
    """Device page decode (VERDICT r4 #1): PLAIN + dictionary/RLE
    bit-packed chunks, nullable and required, across codecs, against
    both the CPU oracle and the host-decode path; dictionary columns
    must cross the link SMALLER than decoded."""
    rng = np.random.default_rng(7)
    n = 30_000
    arrays = {
        "dict_i32": pa.array(rng.integers(0, 9, n).astype(np.int32)),
        "dict_f32": pa.array((rng.integers(0, 7, n) / 8)
                             .astype(np.float32)),
        "plain_f64": pa.array(rng.uniform(0, 1, n)),
        "i64": pa.array(rng.integers(-(1 << 40), 1 << 40, n)),
        "b": pa.array(rng.integers(0, 2, n).astype(bool)),
        "date": pa.array(rng.integers(8000, 9000, n).astype(np.int32))
        .cast(pa.date32()),
        "rle_sorted": pa.array(np.sort(rng.integers(0, 4, n))
                               .astype(np.int64)),
        "null_i32": pa.array(rng.integers(0, 50, n).astype(np.int32),
                             mask=rng.uniform(0, 1, n) < 0.25),
        "all_null": pa.array([None] * n, type=pa.int64()),
        "s": pa.array(["x" + str(i % 13) for i in range(n)]),  # host path
    }
    for codec in ("snappy", "zstd"):
        p = os.path.join(str(tmp_path), f"m_{codec}.parquet")
        pq.write_table(pa.table(arrays), p, row_group_size=8000,
                       compression=codec,
                       dictionary_pagesize_limit=32 << 10,
                       data_page_size=8 << 10)
        scan = TpuFileScanExec([p])
        ctx = ExecCtx()
        got_dev = pa.Table.from_batches(
            [b for b in map(_to_arrow, scan.execute(ctx))])
        want = pa.Table.from_batches(list(scan.execute_cpu(ExecCtx())))
        assert _canon(got_dev) == _canon(want), codec
        m = ctx.metrics[scan.node_label()]
        assert m["encodedBytes"].value > 0
        # dict/RLE savings on this data dominate the PLAIN columns
        assert m["encodedBytes"].value < m["decodedBytes"].value, codec
        # host-decode path (conf off) agrees
        off = RapidsConf({
            "spark.rapids.sql.format.parquet.deviceDecode.enabled":
                "false"})
        got_host = pa.Table.from_batches(
            [b for b in map(_to_arrow,
                            TpuFileScanExec([p]).execute(ExecCtx(off)))])
        assert _canon(got_dev) == _canon(got_host), codec


def _run_table(starts, index_base=0):
    """A run table as the planner lays it out — (row start, meta, raw,
    bit position) int64 rows — with constant-RLE runs; a merged group's
    index base rides in meta bits 16+."""
    tab = np.zeros((len(starts), 4), np.int64)
    tab[:, 0] = starts
    tab[:, 1] = 1 | (1 << 8) | (index_base << 16)
    return tab


def _run_lookup_cases():
    from spark_rapids_tpu.io.parquet_device import _pad_rows
    n_rows, cap = 1000, 1024
    two_groups = np.concatenate([
        _run_table([0, 100, 100, 400]),
        _run_table(np.array([0, 250, 499]) + 500, index_base=37)])
    cases = {
        "one-run": (_pad_rows(_run_table([0])), cap),
        "2048-runs": (_run_table(np.arange(2048) * 3), 1 << 13),
        "zero-length-runs-share-a-start":
            (_run_table([0, 7, 7, 7, 512, 512, 900, 999]), cap),
        "starts-at-and-past-n_rows":
            (_run_table([0, 300, n_rows, n_rows + 11, cap - 1, cap, cap + 5,
                         2 * cap]), cap),
        "pad_rows-padding": (_pad_rows(_run_table(np.arange(11) * 90)), cap),
        "merged-two-groups-with-index-bases": (_pad_rows(two_groups), cap),
        "capacity-off-the-prefix-block":
            (_pad_rows(_run_table([0, 5, 5, 1023, 1024, 1499, 1500])), 1500),
    }
    return [pytest.param(tab, cap, id=name)
            for name, (tab, cap) in cases.items()]


@pytest.mark.parametrize("tab, cap", _run_lookup_cases())
def test_parquet_device_run_lookup_is_the_search(tab, cap):
    """The decoder's run lookup (a prefix count of run-start flags) gives,
    element for element, the run the per-row binary search it replaced
    gave: the decode tests around this one prove nulls, delta and strings
    bit-exact, this one the lookup itself on tables they do not reach."""
    import jax.numpy as jnp
    from spark_rapids_tpu.io.parquet_device import _run_ids
    t_n = tab.shape[0]
    want = np.clip(
        np.searchsorted(tab[:, 0], np.arange(cap), "right") - 1, 0, t_n - 1)
    got = np.asarray(_run_ids(jnp.asarray(tab)[:, 0], cap, t_n))
    assert got.dtype == np.int32 and got.shape == (cap,)
    np.testing.assert_array_equal(got, want)


def _to_arrow(batch):
    from spark_rapids_tpu.columnar.arrow_bridge import device_to_arrow
    return device_to_arrow(batch)


def _run_expand_cases():
    """The run lookup's tables with fields worth expanding: a 64-bit
    literal whose high word is set, riding as its two halves; fields
    that fall from run to run; a field at both ends of int32."""
    out = []
    for param in _run_lookup_cases():
        tab, cap = param.values
        tab = tab.copy()
        r = np.arange(tab.shape[0], dtype=np.int64)
        tab[:, 2] = (0x7F3A_0000_0000_0000 >> (r % 5)) - r * 0x1_2345_6789
        tab[:, 3] = np.where(r % 2 == 0, np.iinfo(np.int32).max - r,
                             np.iinfo(np.int32).min + r)
        out.append(pytest.param(tab, cap, id=param.id))
    return out


@pytest.mark.parametrize("tab, cap", _run_expand_cases())
def test_dense_run_expand_is_the_gather_by_run(tab, cap):
    """Expanding per-run fields by scatter + prefix gives, lane for lane,
    what gathering them by the searched run id gave — zero-length runs,
    padding runs at int32.max, starts at and past the capacity, int32
    wrap-around and a 64-bit field as two 32-bit halves included."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.gather import dense_run_expand
    starts, raw = tab[:, 0], tab[:, 2]
    fields = np.stack([tab[:, 1], raw & 0xFFFFFFFF, raw >> 32, tab[:, 3]])
    fields = fields.astype(np.uint32).view(np.int32)  # low 32 bits each
    rid = np.searchsorted(starts, np.arange(cap), "right") - 1
    assert rid.min() >= 0  # every table starts at position 0
    got = np.asarray(dense_run_expand(
        jnp.asarray(starts), jnp.asarray(fields), cap))
    assert got.dtype == np.int32 and got.shape == (4, cap)
    np.testing.assert_array_equal(got, fields[:, rid])
    raw_back = (got[2].astype(np.int64) << 32) | got[1].view(np.uint32)
    np.testing.assert_array_equal(raw_back, raw[rid])


def _bits(table):
    """A table's values as comparable bits: floats by their integer
    view (nulls zeroed), so -0.0, NaN payloads and the last ulp count."""
    out = {}
    for name, col in zip(table.schema.names, table.columns):
        arr = col.combine_chunks()
        if pa.types.is_floating(arr.type):
            width = np.int64 if arr.type == pa.float64() else np.int32
            out[name] = (arr.is_null().to_pylist(), arr.fill_null(0)
                         .to_numpy(zero_copy_only=False).view(width).tolist())
        else:
            out[name] = arr.to_pylist()
    return out


@pytest.mark.parametrize("target", ["0", "1g"],
                         ids=["per-group", "coalesced"])
@pytest.mark.parametrize("written", ["required", "optional-no-nulls",
                                     "optional-with-nulls"])
def test_parquet_device_decode_null_free_chunks(tmp_path, written, target):
    """The SAME values written `nullable=False`, optional without a null
    and optional with nulls decode to pyarrow's bits; the first two run
    no definition-level pass on any chunk (`nullFreeChunks`: the chunk's
    own null count decides, not the schema), the third on every chunk.
    Dictionary, PLAIN 64-bit, boolean, DELTA and string chunks, one
    dispatch a row group and coalesced."""
    rng = np.random.default_rng(41)
    n = 20_000
    values = {
        "dict_i32": rng.integers(0, 9, n).astype(np.int32),
        "dict_f64": rng.integers(0, 11, n) / 100,
        "plain_f64": rng.uniform(-1, 1, n),
        "plain_i64": rng.integers(-(1 << 40), 1 << 40, n),
        "b": rng.integers(0, 2, n).astype(bool),
        "delta_i64": rng.integers(-1000, 1000, n).cumsum(),
        "s": np.array([f"brand #{i % 17}" for i in range(n)], object),
    }
    # at least one null in every row group of every column
    mask = {name: (np.arange(n) % 5000 == 7 * k) | (rng.uniform(0, 1, n) < .2)
            for k, name in enumerate(values)}
    nulls = written == "optional-with-nulls"
    table = pa.table(
        [pa.array(v, mask=mask[name] if nulls else None)
         for name, v in values.items()],
        schema=pa.schema([pa.field(name, pa.array(v[:1]).type,
                                   nullable=written != "required")
                          for name, v in values.items()]))
    p = os.path.join(str(tmp_path), f"{written}.parquet")
    pq.write_table(table, p, row_group_size=5000, compression="snappy",
                   data_page_size=8 << 10,
                   use_dictionary=["dict_i32", "dict_f64", "s"],
                   column_encoding={"delta_i64": "DELTA_BINARY_PACKED"})
    conf = RapidsConf({"spark.rapids.sql.scan.coalesceTargetBytes": target})
    scan = TpuFileScanExec([p], conf=conf)
    ctx = ExecCtx(conf)
    batches = [_to_arrow(b) for b in scan.execute(ctx)]
    assert len(batches) == (4 if target == "0" else 1)
    got = pa.Table.from_batches(batches)
    assert _bits(got) == _bits(pq.read_table(p))
    m = ctx.metrics[scan.node_label()]
    chunks = 4 * len(values)
    assert m["deviceChunks"].value == chunks and not m["fallbackChunks"].value
    assert m["nullFreeChunks"].value == (0 if nulls else chunks)


def test_parquet_device_decode_null_free_variants_bounded(tmp_path):
    """Row groups that differ in which of three columns hold a null must
    not compile a decode program per mixture (2^3): a column keeps the
    definition-level pass from its first chunk with a null on
    (io/scan.py), so the flags only rise over a scan — at most k + 1
    variants for k columns, the same ones on a re-scan."""
    from spark_rapids_tpu.io import parquet_device as pd_
    rng = np.random.default_rng(43)
    groups, rows, names = 8, 4000, ("a", "b", "c")
    n = groups * rows
    # row group g holds ONE null in column k iff bit k of this order's
    # g-th entry is set: all eight mixtures, the null-free one first
    order = [0, 1, 2, 4, 3, 5, 6, 7]
    arrays = {}
    for k, name in enumerate(names):
        mask = np.zeros(n, bool)
        for g, bits in enumerate(order):
            mask[g * rows + 11 + k] = (bits >> k) & 1
        arrays[name] = pa.array(rng.integers(0, 7, n).astype(np.int32),
                                mask=mask)
    p = os.path.join(str(tmp_path), "mix.parquet")
    pq.write_table(pa.table(arrays), p, row_group_size=rows)
    conf = RapidsConf({"spark.rapids.sql.scan.coalesceTargetBytes": "0"})
    pd_._JIT_CACHE.clear()
    scan = TpuFileScanExec([p], conf=conf)
    ctx = ExecCtx(conf)
    got = pa.Table.from_batches([_to_arrow(b) for b in scan.execute(ctx)])
    assert got.equals(pq.read_table(p))
    flags = {tuple(col[-1] for col in k[3])
             for k in pd_._JIT_CACHE if k[0] == "rg"}
    assert (False,) * 3 in flags and (True,) * 3 in flags
    assert len(flags) <= len(names) + 1, flags
    assert len(pd_._JIT_CACHE) <= len(names) + 1, list(pd_._JIT_CACHE)
    # the chunks before their column's first null ran without the pass:
    # one of a, two of b, three of c
    assert ctx.metrics[scan.node_label()]["nullFreeChunks"].value == 6
    before = len(pd_._JIT_CACHE)
    list(TpuFileScanExec([p], conf=conf).execute(ExecCtx(conf)))
    assert len(pd_._JIT_CACHE) == before


def test_parquet_device_decode_dict_strings(tmp_path):
    """Dictionary-encoded STRING chunks decode on device: indices cross
    the link at bit-packed width, the device gathers the strings from
    the uploaded dictionary (unicode, nulls, empties included)."""
    rng = np.random.default_rng(9)
    n = 20_000
    cats = ["alpha", "β-unicode", "", "a-much-longer-category-name",
            "x", "日本語"]
    vals = [cats[i] for i in rng.integers(0, len(cats), n)]
    arrays = {
        "s": pa.array(vals, pa.string()),
        "sn": pa.array([None if rng.uniform() < 0.3 else v
                        for v in vals], pa.string()),
        "i": pa.array(rng.integers(0, 5, n).astype(np.int32)),
    }
    p = os.path.join(str(tmp_path), "ds.parquet")
    pq.write_table(pa.table(arrays), p, row_group_size=8000,
                   compression="snappy")
    scan = TpuFileScanExec([p])
    ctx = ExecCtx()
    got = pa.Table.from_batches([_to_arrow(b) for b in scan.execute(ctx)])
    want = pa.Table.from_batches(list(scan.execute_cpu(ExecCtx())))
    assert _canon(got) == _canon(want)
    m = ctx.metrics[scan.node_label()]
    # the string chunks were device-decoded (they count toward encoded)
    assert m["encodedBytes"].value > 0
    # high-cardinality strings overflow the dictionary into PLAIN pages
    # mid-chunk — since the envelope widened, those decode on device too
    many = pa.table({"u": pa.array([f"unique-{i}" * 3
                                    for i in range(n)])})
    p2 = os.path.join(str(tmp_path), "plain.parquet")
    pq.write_table(many, p2, dictionary_pagesize_limit=1024,
                   compression="snappy")
    assert_tpu_and_cpu_plan_equal(TpuFileScanExec([p2]))
    _, dev2, fb2 = _scan_coverage(p2)
    assert fb2 == 0 and dev2 > 0, (dev2, fb2)


def test_parquet_device_decode_coalesced_bit_exact(tmp_path):
    """Quantized-arena/coalesced path vs the per-row-group path vs the
    CPU oracle, across dtype lanes, null patterns, and PLAIN/dict/RLE
    mixes (data_gen generators + crafted encoding-specific columns)."""
    from data_gen import BooleanGen, DoubleGen
    rng = np.random.default_rng(11)
    n = 16_000
    rb = gen_table([IntegerGen(null_frac=0.3), LongGen(null_frac=0),
                    DoubleGen(), BooleanGen(null_frac=0),
                    StringGen(max_len=9, null_frac=0.2), DateGen()],
                   n=n, seed=5, names=["ni", "l", "d", "b", "s", "dt"])
    arrays = {name: rb.column(i) for i, name in enumerate(rb.schema.names)}
    grp = np.arange(n) // 3000
    # heterogeneous dictionaries: each row group's value set is disjoint
    arrays["dict_i32"] = pa.array(
        (rng.integers(0, 7, n) + grp * 1000).astype(np.int32))
    arrays["rle"] = pa.array(np.sort(rng.integers(0, 5, n))
                             .astype(np.int64))
    arrays["plain_f32"] = pa.array(rng.uniform(0, 1, n)
                                   .astype(np.float32))
    p = os.path.join(str(tmp_path), "c.parquet")
    pq.write_table(pa.table(arrays), p, row_group_size=3000,
                   compression="snappy")
    want = pa.Table.from_batches(
        list(TpuFileScanExec([p]).execute_cpu(ExecCtx())))
    n_batches = {}
    for label, target in (("per_group", "0"), ("coalesced", "1g")):
        conf = RapidsConf(
            {"spark.rapids.sql.scan.coalesceTargetBytes": target})
        scan = TpuFileScanExec([p], conf=conf)
        bs = [_to_arrow(b) for b in scan.execute(ExecCtx(conf))]
        n_batches[label] = len(bs)
        assert _canon(pa.Table.from_batches(bs)) == _canon(want), label
    # the coalescer genuinely fused row groups into fewer dispatches
    assert n_batches["per_group"] == 6
    assert n_batches["coalesced"] < n_batches["per_group"]


def test_parquet_device_decode_jit_cache_quantized(tmp_path):
    """Heterogeneous row groups of one schema must NOT compile one
    fused-decode program per row group: the quantized arena collapses
    the JIT cache to a couple of variants per capacity bucket, and a
    re-scan is fully cache-hot."""
    from spark_rapids_tpu.io import parquet_device as pd_
    rng = np.random.default_rng(13)
    n = 36_000
    grp = np.arange(n) // 8000  # 4 full groups + one 4000-row tail
    arrays = {
        "a": pa.array((rng.integers(0, 6, n) + grp * 100)
                      .astype(np.int32)),
        "b": pa.array(rng.integers(0, 50, n).astype(np.int64),
                      mask=rng.uniform(0, 1, n) < 0.15),
        "c": pa.array([f"g{g}x{i % 9}" for i, g in enumerate(grp)]),
    }
    p = os.path.join(str(tmp_path), "h.parquet")
    pq.write_table(pa.table(arrays), p, row_group_size=8000,
                   compression="zstd")
    conf = RapidsConf(
        {"spark.rapids.sql.scan.coalesceTargetBytes": "0"})
    pd_._JIT_CACHE.clear()
    scan = TpuFileScanExec([p], conf=conf)
    got = pa.Table.from_batches(
        [_to_arrow(b) for b in scan.execute(ExecCtx(conf))])
    want = pa.Table.from_batches(
        list(TpuFileScanExec([p]).execute_cpu(ExecCtx())))
    assert _canon(got) == _canon(want)
    keys = [k for k in pd_._JIT_CACHE if k[0] == "rg"]
    caps = {k[1] for k in keys}
    # 5 heterogeneous row groups, 2 capacity buckets (8192 + the tail's
    # 4096): at most a couple of program variants per capacity bucket —
    # the raw-offset cache key compiled one program PER GROUP
    assert len(keys) < 5, keys
    assert len(keys) <= 2 * len(caps), keys
    # second scan: zero new compilations
    before = len(pd_._JIT_CACHE)
    list(TpuFileScanExec([p], conf=conf).execute(ExecCtx(conf)))
    assert len(pd_._JIT_CACHE) == before


def _scan_coverage(path, conf=None):
    """(arrow table via device path, deviceChunks, fallbackChunks)."""
    conf = conf or RapidsConf()
    scan = TpuFileScanExec([path], conf=conf)
    ctx = ExecCtx(conf)
    got = pa.Table.from_batches([_to_arrow(b) for b in scan.execute(ctx)])
    m = ctx.metrics[scan.node_label()]
    return got, int(m["deviceChunks"].value), int(m["fallbackChunks"].value)


def test_parquet_device_decode_fallback_encodings(tmp_path):
    """DELTA_BINARY_PACKED is now INSIDE the device envelope;
    byte-stream-split is still outside — the per-chunk fallback keeps
    results right and the coverage counters tell the two apart."""
    rng = np.random.default_rng(8)
    n = 5000
    tab = pa.table({
        "delta": pa.array(rng.integers(0, 1 << 30, n).astype(np.int64)),
        "bss": pa.array(rng.uniform(0, 1, n).astype(np.float32)),
        "ok": pa.array(rng.integers(0, 5, n).astype(np.int32)),
    })
    p = os.path.join(str(tmp_path), "enc.parquet")
    pq.write_table(tab, p, use_dictionary=False,
                   column_encoding={"delta": "DELTA_BINARY_PACKED",
                                    "bss": "BYTE_STREAM_SPLIT",
                                    "ok": "PLAIN"})
    assert_tpu_and_cpu_plan_equal(TpuFileScanExec([p]))
    got, dev, fb = _scan_coverage(p)
    assert fb == 1, (dev, fb)   # only the BYTE_STREAM_SPLIT chunk
    assert dev == 2, (dev, fb)  # delta + plain decode on device


def test_parquet_device_decode_v2_pages(tmp_path):
    """DATA_PAGE_V2 files decode ON DEVICE now (levels split from the
    data region, no length prefix, nulls from the page header):
    bit-exact vs the CPU oracle, zero fallback chunks."""
    rng = np.random.default_rng(21)
    n = 12_000
    arrays = {
        "i32": pa.array(rng.integers(0, 9, n).astype(np.int32)),
        "ni64": pa.array(rng.integers(0, 60, n).astype(np.int64),
                         mask=rng.uniform(0, 1, n) < 0.3),
        "s": pa.array([None if i % 9 == 0 else f"v{i % 13}"
                       for i in range(n)]),
        "b": pa.array(rng.integers(0, 2, n).astype(bool)),
        "all_null": pa.array([None] * n, type=pa.int32()),
    }
    p = os.path.join(str(tmp_path), "v2.parquet")
    pq.write_table(pa.table(arrays), p, data_page_version="2.0",
                   row_group_size=4000, compression="zstd",
                   data_page_size=4 << 10)
    got, dev, fb = _scan_coverage(p)
    want = pa.Table.from_batches(
        list(TpuFileScanExec([p]).execute_cpu(ExecCtx())))
    assert _canon(got) == _canon(want)
    assert fb == 0 and dev > 0, (dev, fb)


def test_parquet_device_decode_plain_strings_matrix(tmp_path):
    """PLAIN BYTE_ARRAY strings decode on device (host walks the
    length prefixes into the store, device gathers the characters):
    nulls, empty strings, unicode, v1 AND v2 pages, and the coalesced
    path, all bit-exact vs the CPU oracle with zero fallbacks."""
    rng = np.random.default_rng(23)
    n = 12_000
    cats = ["", "alpha", "β-unicode", "a-much-longer-plain-value",
            "日本語テキスト", "x"]
    arrays = {
        "ps": pa.array([None if rng.uniform() < 0.25
                        else cats[i % len(cats)] + str(i % 7)
                        for i in range(n)]),
        "pb": pa.array([None if i % 17 == 0 else b"\x00bin%d" % (i % 5)
                        for i in range(n)], pa.binary()),
        "i": pa.array(rng.integers(0, 1 << 16, n).astype(np.int32)),
    }
    for ver, codec in (("1.0", "snappy"), ("2.0", "zstd")):
        p = os.path.join(str(tmp_path), f"ps_{ver}.parquet")
        pq.write_table(pa.table(arrays), p, use_dictionary=False,
                       data_page_version=ver, compression=codec,
                       row_group_size=3000, data_page_size=8 << 10)
        want = pa.Table.from_batches(
            list(TpuFileScanExec([p]).execute_cpu(ExecCtx())))
        for target in ("0", "1g"):
            conf = RapidsConf(
                {"spark.rapids.sql.scan.coalesceTargetBytes": target})
            got, dev, fb = _scan_coverage(p, conf)
            assert _canon(got) == _canon(want), (ver, target)
            assert fb == 0 and dev > 0, (ver, target, dev, fb)


@pytest.mark.parametrize("page_version", ["1.0", "2.0"])
def test_parquet_device_decode_delta_matrix(tmp_path, page_version):
    """DELTA_BINARY_PACKED int32/int64 (negative deltas, nulls,
    multi-page chunks — the device prefix sum restarts per page) and
    DELTA_LENGTH_BYTE_ARRAY strings (nulls, empties), in v1 pages and
    in DATA_PAGE_V2 pages beside a PLAIN string column: bit-exact vs
    the CPU oracle across per-group and coalesced dispatch, zero
    fallbacks."""
    rng = np.random.default_rng(29)
    n = 16_000
    arrays = {
        "d32": pa.array((rng.integers(-100, 100, n).cumsum()
                         % 1_000_000).astype(np.int32)),
        "d64": pa.array(rng.integers(-1000, 1000, n).cumsum()
                        .astype(np.int64),
                        mask=rng.uniform(0, 1, n) < 0.2),
        "dls": pa.array([None if i % 11 == 0 else
                         ["", f"dl-{i % 53}", "長い" * (i % 4)][i % 3]
                        for i in range(n)]),
        "ps": pa.array([None if i % 13 == 0 else f"plain-{i % 97}"
                        for i in range(n)]),
    }
    p = os.path.join(str(tmp_path), "delta.parquet")
    pq.write_table(pa.table(arrays), p, use_dictionary=False,
                   compression="snappy", row_group_size=4000,
                   data_page_size=4 << 10, data_page_version=page_version,
                   column_encoding={"d32": "DELTA_BINARY_PACKED",
                                    "d64": "DELTA_BINARY_PACKED",
                                    "dls": "DELTA_LENGTH_BYTE_ARRAY",
                                    "ps": "PLAIN"})
    want = pa.Table.from_batches(
        list(TpuFileScanExec([p]).execute_cpu(ExecCtx())))
    for target in ("0", "1g"):
        conf = RapidsConf(
            {"spark.rapids.sql.scan.coalesceTargetBytes": target})
        got, dev, fb = _scan_coverage(p, conf)
        assert _canon(got) == _canon(want), target
        assert fb == 0 and dev > 0, (target, dev, fb)


def test_delta_stream_truncation_classified():
    """A truncated DELTA stream must surface as a classified
    HostFallback(reason='truncated') — never an IndexError escaping the
    per-chunk fallback net (code-review r7)."""
    from spark_rapids_tpu.io.parquet_device import (
        HostFallback, _decode_delta_ints, _plan_delta_page)
    # valid header (block 128, 4 miniblocks, 100 values, first 0) with
    # the block payload cut off
    hdr = b"\x80\x01" + b"\x04" + b"\x64" + b"\x00"
    for trunc in (hdr,                      # cut at min_delta
                  hdr + b"\x02",            # cut inside the widths
                  hdr + b"\x02" + b"\x08" * 4):  # widths, no payload
        with pytest.raises(HostFallback) as ei:
            _decode_delta_ints(trunc, 0)
        assert ei.value.reason == "truncated", trunc
        with pytest.raises(HostFallback) as ei:
            _plan_delta_page(trunc, 0, 100)
        assert ei.value.reason == "truncated", trunc


def test_parquet_device_decode_mixed_dict_plain_strings(tmp_path):
    """A chunk whose dictionary page overflows mid-write (dict pages
    then PLAIN pages in ONE column chunk) decodes on device: dict runs
    index the dictionary slice of the store, identity runs index their
    page's slice — nulls included, coalesced included."""
    rng = np.random.default_rng(31)
    n = 20_000
    tab = pa.table({
        "u": pa.array([None if i % 13 == 0
                       else f"val-{i}-{'pad' * (i % 3)}"
                       for i in range(n)]),
        "k": pa.array(rng.integers(0, 5, n).astype(np.int32)),
    })
    p = os.path.join(str(tmp_path), "mixed.parquet")
    pq.write_table(tab, p, dictionary_pagesize_limit=2048,
                   compression="zstd", row_group_size=5000,
                   data_page_size=4096)
    want = pa.Table.from_batches(
        list(TpuFileScanExec([p]).execute_cpu(ExecCtx())))
    for target in ("0", "1g"):
        conf = RapidsConf(
            {"spark.rapids.sql.scan.coalesceTargetBytes": target})
        got, dev, fb = _scan_coverage(p, conf)
        assert _canon(got) == _canon(want), target
        assert fb == 0 and dev > 0, (target, dev, fb)


def test_parquet_device_decode_string_jit_cache_quantized(tmp_path):
    """String-gather variants share the quantized JIT cache: similar
    heterogeneous PLAIN-string row groups must collapse to a couple of
    fused-program variants per capacity bucket, and a re-scan compiles
    nothing new."""
    from spark_rapids_tpu.io import parquet_device as pd_
    rng = np.random.default_rng(37)
    n = 24_000
    grp = np.arange(n) // 8000
    tab = pa.table({
        "s": pa.array([f"g{g}-{'x' * int(rng.integers(3, 9))}-{i % 11}"
                       for i, g in enumerate(grp)]),
        "i": pa.array((rng.integers(0, 50, n) + grp * 100)
                      .astype(np.int64)),
    })
    p = os.path.join(str(tmp_path), "sq.parquet")
    pq.write_table(tab, p, use_dictionary=False, compression="snappy",
                   row_group_size=8000)
    conf = RapidsConf(
        {"spark.rapids.sql.scan.coalesceTargetBytes": "0"})
    pd_._JIT_CACHE.clear()
    got, dev, fb = _scan_coverage(p, conf)
    assert fb == 0, (dev, fb)
    want = pa.Table.from_batches(
        list(TpuFileScanExec([p]).execute_cpu(ExecCtx())))
    assert _canon(got) == _canon(want)
    keys = [k for k in pd_._JIT_CACHE if k[0] == "rg"]
    caps = {k[1] for k in keys}
    assert len(keys) <= 2 * len(caps), keys
    before = len(pd_._JIT_CACHE)
    list(TpuFileScanExec([p], conf=conf).execute(ExecCtx(conf)))
    assert len(pd_._JIT_CACHE) == before


def test_csv_scan(tmp_path):
    rb = gen_table([IntegerGen(), FloatGen(dt.FLOAT64),
                    StringGen(ascii_only=True,
                              charset="abcdefgh123")], n=300)
    import pyarrow.csv as pcsv
    p = os.path.join(str(tmp_path), "data.csv")
    pcsv.write_csv(pa.Table.from_batches([rb]), p)
    assert_tpu_and_cpu_plan_equal(TpuFileScanExec([p], fmt="csv"))


def test_json_scan(tmp_path):
    rb = gen_table([IntegerGen(), LongGen(), StringGen(ascii_only=True)],
                   n=200)
    p = os.path.join(str(tmp_path), "data.json")
    with open(p, "w") as f:
        for row in pa.Table.from_batches([rb]).to_pylist():
            import json
            f.write(json.dumps(row) + "\n")
    assert_tpu_and_cpu_plan_equal(TpuFileScanExec([p], fmt="json"))


def test_orc_scan(tmp_path):
    from pyarrow import orc
    rb = gen_table([IntegerGen(), LongGen(), FloatGen(dt.FLOAT64),
                    StringGen()], n=300)
    p = os.path.join(str(tmp_path), "data.orc")
    orc.write_table(pa.Table.from_batches([rb]), p)
    assert_tpu_and_cpu_plan_equal(TpuFileScanExec([p], fmt="orc"))


@pytest.mark.parametrize("fmt", ["parquet", "orc", "csv"])
def test_write_round_trip(tmp_path, fmt):
    """BASELINE config-5 shape: write via device path and CPU path, read
    both back, results equal (write dual-run)."""
    gens = [IntegerGen(), LongGen(), FloatGen(dt.FLOAT64)]
    if fmt != "csv":
        gens += [StringGen(), DateGen()]
    rb = gen_table(gens, n=700)
    from spark_rapids_tpu.exec.base import HostBatchSourceExec
    src = HostBatchSourceExec([rb])
    dev_dir = os.path.join(str(tmp_path), "dev")
    cpu_dir = os.path.join(str(tmp_path), "cpu")

    w = TpuFileWriteExec(src, dev_dir, fmt=fmt)
    list(w.execute(ExecCtx()))
    assert w.written_files
    w2 = TpuFileWriteExec(src, cpu_dir, fmt=fmt)
    list(w2.execute_cpu(ExecCtx()))

    back_dev = collect_arrow_cpu(TpuFileScanExec(w.written_files, fmt=fmt))
    back_cpu = collect_arrow_cpu(TpuFileScanExec(w2.written_files, fmt=fmt))
    assert _canon(back_dev) == _canon(back_cpu)
    # and the device-read of what the device wrote matches the source
    again = collect_arrow(TpuFileScanExec(w.written_files, fmt=fmt))
    assert again.num_rows == rb.num_rows


def test_partitioned_write(tmp_path):
    rb = gen_table([IntegerGen(min_val=0, max_val=3, null_frac=0),
                    LongGen(), StringGen()], names=["part", "v", "s"])
    from spark_rapids_tpu.exec.base import HostBatchSourceExec
    src = HostBatchSourceExec([rb])
    out = os.path.join(str(tmp_path), "out")
    w = TpuFileWriteExec(src, out, fmt="parquet", partition_by=["part"])
    list(w.execute(ExecCtx()))
    assert any("part=" in f for f in w.written_files)
    import pyarrow.dataset as pads
    back = pads.dataset(out, format="parquet",
                        partitioning="hive").to_table()
    assert back.num_rows == rb.num_rows
    assert sorted(back.column("v").to_pylist(), key=lambda x: (x is None, x)) \
        == sorted(rb.column(1).to_pylist(), key=lambda x: (x is None, x))


def test_hive_partition_inference_strict(tmp_path):
    """Directory values like 'nan'/'inf'/'1_0' type as STRING, not
    float64/int64 (Python float()/int() accept them; Spark does not —
    ADVICE r4)."""
    from spark_rapids_tpu.io.scan import _hive_partition_values
    base = str(tmp_path)
    paths = [f"{base}/k={v}/f.parquet" for v in ("nan", "inf", "1_0")]
    typed, schema = _hive_partition_values(paths)
    assert schema.fields[0].dtype == dt.STRING
    assert typed[paths[0]]["k"] == "nan"
    # plain ints still infer int64
    paths = [f"{base}/k={v}/f.parquet" for v in ("1", "-2", "+3")]
    typed, schema = _hive_partition_values(paths)
    assert schema.fields[0].dtype == dt.INT64
    assert typed[paths[1]]["k"] == -2
    # decimals/exponents infer float64
    paths = [f"{base}/k={v}/f.parquet" for v in ("1.5", "2e3", ".25")]
    _, schema = _hive_partition_values(paths)
    assert schema.fields[0].dtype == dt.FLOAT64


def test_scan_q6_pipeline_through_planner(tmp_path):
    """Scan -> filter -> project -> agg, planned via TpuOverrides: the full
    BASELINE config-1 pipeline starting at real files."""
    n = 5000
    rng = np.random.default_rng(3)
    rb = pa.record_batch({
        "l_quantity": pa.array(rng.uniform(1, 50, n).astype(np.float32)),
        "l_extendedprice": pa.array(
            rng.uniform(900, 105000, n).astype(np.float32)),
        "l_discount": pa.array(
            (rng.integers(0, 11, n) / 100).astype(np.float32)),
        "l_shipdate": pa.array(
            rng.integers(8000, 10600, n).astype(np.int32)),
    })
    p = _write_parquet(tmp_path, rb)
    d = lambda v: Literal(np.float32(v), dt.FLOAT32)
    cond = And(And(GreaterThanOrEqual(col("l_shipdate"),
                                      Literal(8766, dt.INT32)),
                   LessThan(col("l_shipdate"), Literal(9131, dt.INT32))),
               LessThan(col("l_quantity"), d(24.0)))
    scan = TpuFileScanExec([p], pushdown=cond)
    filt = TpuFilterExec(cond, scan)
    proj = TpuProjectExec([Alias(Multiply(col("l_extendedprice"),
                                          col("l_discount")), "rev")], filt)
    agg = TpuHashAggregateExec([], [Alias(Sum(col("rev")), "revenue")], proj)
    pp = overrides(agg)
    assert pp.fallback_nodes() == []
    got = pp.collect()
    exp = collect_arrow_cpu(agg)
    assert abs(got.column(0)[0].as_py() - exp.column(0)[0].as_py()) \
        <= 1e-6 * abs(exp.column(0)[0].as_py())


def test_scan_falls_back_when_format_disabled(tmp_path):
    rb = gen_table([IntegerGen()], n=50)
    p = _write_parquet(tmp_path, rb)
    conf = RapidsConf({"spark.rapids.sql.exec.FileScanExec": "false"})
    pp = overrides(TpuFileScanExec([p]), conf)
    assert "FileScanExec" in pp.fallback_nodes()
    got = pp.collect()
    assert got.num_rows == 50


def test_hive_text_round_trip(tmp_path):
    """Hive LazySimpleSerDe text (B13): \\x01 delimiters, \\N nulls,
    serde escapes — write + read round-trip incl. hostile strings."""
    import datetime as dtm
    from spark_rapids_tpu.io.write import TpuFileWriteExec
    from spark_rapids_tpu.exec.base import HostBatchSourceExec
    rb = pa.record_batch({
        "i": pa.array([1, None, -3, 400], pa.int64()),
        "f": pa.array([0.5, 2.25, None, -1.0]),
        "b": pa.array([True, False, None, True]),
        "d": pa.array([dtm.date(2021, 3, 5), None,
                       dtm.date(1999, 12, 31), dtm.date(2000, 1, 1)]),
        "s": pa.array(["plain", "with\x01delim", "multi\nline",
                       "back\\slash"]),
    })
    src = HostBatchSourceExec([rb])
    out_dir = os.path.join(str(tmp_path), "ht")
    w = TpuFileWriteExec(src, out_dir, fmt="hivetext")
    list(w.execute(ExecCtx()))
    assert w.written_files
    from spark_rapids_tpu.columnar.arrow_bridge import engine_schema
    scan = TpuFileScanExec(w.written_files, fmt="hivetext",
                           schema=engine_schema(rb.schema))
    back = assert_tpu_and_cpu_plan_equal(scan)
    assert _canon(back) == _canon(
        pa.Table.from_batches([rb]))


def test_hive_text_binary_base64(tmp_path):
    """BINARY columns ride Hive text as Base64 (the serde's encoding) —
    round-trip exact, including delimiter-colliding bytes."""
    from spark_rapids_tpu.io.write import TpuFileWriteExec
    from spark_rapids_tpu.exec.base import HostBatchSourceExec
    from spark_rapids_tpu.columnar.arrow_bridge import engine_schema
    rb = pa.record_batch({
        "k": pa.array([1, 2, 3], pa.int64()),
        "bin": pa.array([b"ab\x01c", None, b"\\x\nraw"], pa.binary()),
    })
    out_dir = os.path.join(str(tmp_path), "htb")
    w = TpuFileWriteExec(HostBatchSourceExec([rb]), out_dir,
                         fmt="hivetext")
    list(w.execute(ExecCtx()))
    scan = TpuFileScanExec(w.written_files, fmt="hivetext",
                           schema=engine_schema(rb.schema))
    back = assert_tpu_and_cpu_plan_equal(scan)
    assert back.column("bin").to_pylist() == [b"ab\x01c", None,
                                              b"\\x\nraw"]


def test_hive_text_cr_decimal_timestamp(tmp_path):
    """\\r in strings must not split rows, and decimal/timestamp
    columns round-trip via their text forms (code-review r5)."""
    import datetime as dtm
    import decimal
    from spark_rapids_tpu.io.write import TpuFileWriteExec
    from spark_rapids_tpu.exec.base import HostBatchSourceExec
    from spark_rapids_tpu.columnar.arrow_bridge import engine_schema
    utc = dtm.timezone.utc
    rb = pa.record_batch({
        "i": pa.array([1, 2], pa.int64()),
        "s": pa.array(["a\rb", "win\r\nline"]),
        "dec": pa.array([decimal.Decimal("1.50"), None],
                        pa.decimal128(10, 2)),
        "ts": pa.array([dtm.datetime(2021, 3, 5, 12, 0, 1, 250000,
                                     tzinfo=utc), None],
                       pa.timestamp("us", tz="UTC")),
    })
    out_dir = os.path.join(str(tmp_path), "htc")
    w = TpuFileWriteExec(HostBatchSourceExec([rb]), out_dir,
                         fmt="hivetext")
    list(w.execute(ExecCtx()))
    scan = TpuFileScanExec(w.written_files, fmt="hivetext",
                           schema=engine_schema(rb.schema))
    back = assert_tpu_and_cpu_plan_equal(scan)
    assert back.num_rows == 2
    assert back.column("s").to_pylist() == ["a\rb", "win\r\nline"]
    assert back.column("dec").to_pylist() == [decimal.Decimal("1.50"),
                                              None]


def test_hive_text_crlf_external_file(tmp_path):
    """CRLF-terminated files (external writers) parse without trailing
    \\r leaking into the last field (code-review r5)."""
    from spark_rapids_tpu.columnar.arrow_bridge import engine_schema
    p = os.path.join(str(tmp_path), "crlf.txt")
    with open(p, "wb") as f:
        f.write(b"1\x01alpha\r\n2\x01beta\r\n\\N\x01\\N\r\n")
    schema = engine_schema(pa.schema([("i", pa.int64()),
                                      ("s", pa.string())]))
    scan = TpuFileScanExec([p], fmt="hivetext", schema=schema)
    back = assert_tpu_and_cpu_plan_equal(scan)
    assert back.column("i").to_pylist() == [1, 2, None]
    assert back.column("s").to_pylist() == ["alpha", "beta", None]
