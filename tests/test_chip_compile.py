"""Chip-compiler guards: the main path's programs compiled for a described
(not attached) TPU v5e, at real shapes, by the TPU compiler this sandbox
has installed.

Nothing runs and nothing is timed; a case passes when the chip's compiler
accepts the program (or, for the two documented refusals, still refuses
it in the documented words). All cases live in THIS file and describe the
topology inside a module-scoped fixture: only one process may load the
TPU library, so the call must not happen at import, in a ``skipif``, in
``parametrize`` or in ``conftest.py`` (on-chip-measurement guide, §2).
The persistent compile cache is off around them — a compile for a
described chip can be written to it but never read back.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from spark_rapids_tpu import datatypes as dt
from spark_rapids_tpu.columnar.batch import TpuBatch
from spark_rapids_tpu.columnar.column import TpuColumnVector

ROWS = 1 << 21          # the engine's default batch capacity at bench sizes


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=False)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture
def chip(one_chip, no_persistent_cache):
    """``chip(shape, dtype)`` -> a ShapeDtypeStruct on the described chip."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return sds


def _col(chip, dtype, n=ROWS):
    return TpuColumnVector(dtype, data=chip((n,), dtype.np_dtype),
                           validity=chip((n,), jnp.bool_))


def _batch(chip, fields, n=ROWS):
    schema = dt.Schema([dt.StructField(name, t, True) for name, t in fields])
    return TpuBatch([_col(chip, t, n) for _, t in fields], schema,
                    chip((), jnp.int32))


# --- the q6 stage: decode, then filter -> project -> partial aggregate --------

def _q6_stage(src):
    """Q6's filter -> project -> ungrouped sum over float32 columns."""
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.basic import TpuFilterExec, TpuProjectExec
    from spark_rapids_tpu.expr import (Alias, And, GreaterThanOrEqual,
                                       LessThan, LessThanOrEqual, Literal,
                                       Multiply, UnresolvedColumn as col)
    from spark_rapids_tpu.expr.aggregates import Sum
    f32 = lambda v: Literal(np.float32(v), dt.FLOAT32)  # noqa: E731
    cond = And(
        And(GreaterThanOrEqual(col("l_shipdate"), Literal(8766, dt.DATE)),
            LessThan(col("l_shipdate"), Literal(9131, dt.DATE))),
        And(And(GreaterThanOrEqual(col("l_discount"), f32(0.05)),
                LessThanOrEqual(col("l_discount"), f32(0.07))),
            LessThan(col("l_quantity"), f32(24.0))))
    proj = TpuProjectExec(
        [Alias(Multiply(col("l_extendedprice"), col("l_discount")), "rev")],
        TpuFilterExec(cond, src))
    return TpuHashAggregateExec([], [Alias(Sum(col("rev")), "revenue")], proj)


def test_q6_filter_project_partial_agg_chain_compiles(chip):
    """The epilogue the fused scan program splices after decode, composed
    exactly as ``exec.base.fused_batches`` composes it, at a 2^20-row
    capacity."""
    from spark_rapids_tpu.exec.base import (DeviceBatchSourceExec, ExecCtx,
                                            UnaryExec)
    fields = [("l_quantity", dt.FLOAT32), ("l_extendedprice", dt.FLOAT32),
              ("l_discount", dt.FLOAT32), ("l_shipdate", dt.DATE)]
    batch = _batch(chip, fields, 1 << 20)
    agg = _q6_stage(DeviceBatchSourceExec([], batch.schema))
    fns, node = [], agg.children[0]
    while isinstance(node, UnaryExec) and node.device_fn() is not None:
        fns.insert(0, node.device_fn())
        node = node.children[0]
    assert len(fns) == 2  # filter, project
    fns.append(agg._partial)

    def composed(b, ectx):
        for f in fns:
            b = f(b, ectx)
        return b
    jax.jit(composed, static_argnums=1).lower(
        batch, ExecCtx().eval_ctx).compile()


@pytest.mark.parametrize("has_nulls, gathers", [
    pytest.param(False, 3, id="null-free"),
    pytest.param(True, 6, id="nullable")])
def test_parquet_dictionary_rle_chunk_decode_compiles(chip, has_nulls,
                                                      gathers):
    """One dictionary/RLE float32 column chunk of a 2^20-row group through
    the device decoder (run table -> funnel-shift unpack -> dictionary
    gather -> null scatter), built from shapes alone, holding the CAUSES
    of its device and compiler seconds in the chip compiler's HLO:
    - no `while`: the run lookup is a prefix count of run-start flags,
      not a per-row binary search (2^20 lanes x log2(runs) rounds; 7.1 s
      of q6's 13.0 device-busy seconds, ledger, PR 26);
    - per row only the packed words and the dictionary are gathered
      (9-24 ms a gather over 2^20 lanes on the v5e whatever it reads):
      two word gathers (a third only for an 8-byte lane, whose PLAIN
      values are 64 bits wide) and the dictionary's for a chunk without
      nulls, which runs no definition-level pass; a nullable chunk adds
      the two word gathers of its levels and the dense-to-row gather.
      The run table's fields ride ``dense_run_expand``'s ONE scatter an
      expansion. This sandbox's compiler read 20 gathers a column
      before PR 32 (3 and 6 now);
    - no 1-D prefix over the 2^20 lanes (`didx` was one: the cold
      cell's 50 s `reduce-window`, which the compiler tiles to
      [8192,128]; alone it compiles in 30 s where the 1024-blocked
      prefix takes 0.4 s, builder, PR 27): the program handed to the
      compiler holds no `reduce_window` whose window is `cap` lanes."""
    from spark_rapids_tpu.io.parquet_device import _decode_device
    cap = 1 << 20
    lowered = jax.jit(_decode_device, static_argnums=(6, 7, 8)).lower(
        chip((cap // 2,), jnp.uint32),      # bit-packed index words
        chip((2048, 4), jnp.int64),         # run table (q6's value tables)
        chip((4096,), jnp.float32),         # dictionary page
        chip((cap // 32 + 2,), jnp.uint32),  # definition-level words
        chip((8, 4), jnp.int64),            # definition-level runs
        chip((), jnp.int64), cap, False, has_nulls)
    assert f"window_dimensions = array<i64: {cap}>" not in lowered.as_text()
    hlo = lowered.compile().as_text()
    assert " while(" not in hlo
    assert hlo.count(" gather(") <= gathers, hlo.count(" gather(")
    assert hlo.count(" scatter(") <= (2 if has_nulls else 1)


# --- the scan chains of the pruned plans (column pruning, exec/pruning.py) ------

def _shape_batch(chip, schema, cap):
    """A batch of ``schema`` on the described chip, from shapes alone
    (a string column: offsets and 32 characters a row)."""
    cols = []
    for f in schema.fields:
        if isinstance(f.dtype, dt.StringType):
            cols.append(TpuColumnVector(
                f.dtype, validity=chip((cap,), jnp.bool_),
                offsets=chip((cap + 1,), jnp.int32),
                chars=chip((cap * 32,), jnp.uint8)))
        else:
            cols.append(TpuColumnVector(
                f.dtype, data=chip((cap,), f.dtype.np_dtype),
                validity=chip((cap,), jnp.bool_)))
    return TpuBatch(cols, schema, chip((), jnp.int32))


@pytest.mark.parametrize("config,query,widths", [
    ("tpch-sf1", "tpch/q6", {"lineitem": (4, 1 << 20)}),
    ("tpcds-sf1-store", "tpcds/q3", {"date_dim": (3, 1 << 17),
                                     "item": (4, 1 << 15),
                                     "store_sales": (3, 1 << 19)}),
])
def test_pruned_scan_chains_compile(chip, tmp_path, config, query, widths):
    """The benchmark's two query texts planned over tables registered at
    full width (16, and 23 + 28 + 22 columns): every scan comes out of
    the planner cut to the columns the text names, and the chain above
    it (filter, the narrowing projection, Q6's partial aggregate), bound
    against that NARROWED schema, compiles for the chip at the capacity
    of the cell's row groups — the epilogue ``fused_scan_execute``
    splices into ``scan_decode_chain``."""
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmark")
    sys.path.insert(0, bench)
    try:
        import datagen
        import run
    finally:
        sys.path.remove(bench)
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.base import ExecCtx, UnaryExec
    from spark_rapids_tpu.io import TpuFileScanExec
    from spark_rapids_tpu.session import TpuSession
    config_file = os.path.join(bench, "configs", config + ".json")
    paths, _, _ = datagen.make_tables(config_file, str(tmp_path), 5, 256)
    session = TpuSession(dict(run.load_json(config_file)["session_conf"]))
    for table, files in paths.items():
        session.register_table(table, session.read_parquet(files))
    pp = run.plan_of(session, run.read_query(query))

    found = {}

    def visit(node, above):
        if isinstance(node, TpuFileScanExec):
            table = next(t for t, fs in paths.items()
                         if fs[0] in node.paths)
            fns = []
            for up in above:  # nearest parent first
                if isinstance(up, UnaryExec) and up.device_fn() is not None:
                    fns.append(up.device_fn())
                    continue
                if isinstance(up, TpuHashAggregateExec):
                    fns.append(up._partial)
                break
            found[table] = (node, fns)
        for c in node.children:
            visit(c, [node] + above)

    visit(pp.root, [])
    assert {t: len(n.output_schema.fields) for t, (n, _) in found.items()} \
        == {t: w for t, (w, _) in widths.items()}
    ectx = ExecCtx().eval_ctx
    for table, (scan, fns) in found.items():
        if not fns:
            continue  # store_sales: the bare decode, no chain

        def composed(b, e, fns=tuple(fns)):
            for f in fns:
                b = f(b, e)
            return b
        jax.jit(composed, static_argnums=1).lower(
            _shape_batch(chip, scan.output_schema, widths[table][1]),
            ectx).compile()


# --- sorts and scans at the engine's batch size ---------------------------------

def test_compaction_sort_compiles(chip):
    """``compaction_indices``: the (int8 keep-rank, int32 index) sort
    every filter compaction and exchange split pays."""
    from spark_rapids_tpu.ops.gather import compaction_indices
    jax.jit(compaction_indices).lower(chip((ROWS,), jnp.bool_)).compile()


@pytest.mark.slow  # ~30-70 s each on the sandbox's cores (CHANGES.md, PR 21)
@pytest.mark.parametrize("key", ["int32", "int64", "float64-off-cpu"])
def test_engine_sort_permutation_compiles(chip, key, monkeypatch):
    """``sort_permutation`` with the lanes exec/sort.py and the sort-based
    group-by really hand to ``lax.sort``: (int8 live rank, int8 null rank,
    value lane, int32 row index). int64 keys ride as a RAW 64-bit lane;
    float64 keys off the CPU ride the packed (hi, lo) int64 lane."""
    from spark_rapids_tpu.ops.sort_keys import SortSpec, sort_permutation
    if key == "float64-off-cpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        t = dt.FLOAT64
    else:
        t = dt.INT32 if key == "int32" else dt.INT64
    jax.jit(lambda c, live: sort_permutation([c], [SortSpec()], live)) \
        .lower(_col(chip, t), chip((ROWS,), jnp.bool_)).compile()


def test_inclusive_int_cumsum_compiles_as_int32(chip):
    """int32 by design: the int64 cumsum costs 3x the compile (PR 21), so
    no caller may widen it."""
    from spark_rapids_tpu.ops.gather import inclusive_int_cumsum
    for dtype in (jnp.bool_, jnp.int32, jnp.int64):
        out = jax.eval_shape(inclusive_int_cumsum,
                             jax.ShapeDtypeStruct((8,), dtype))
        assert out.dtype == jnp.int32
    jax.jit(inclusive_int_cumsum).lower(chip((ROWS,), jnp.int32)).compile()


def test_dense_run_counts_compiles_as_int32_without_a_loop(chip):
    """The decoder's run lookup at a 2^20-row capacity: int64 run starts
    in, int32 flags and an int32 prefix (1024-blocked: half a second of
    compilation where the 1-D cumsum takes 30 s), and no `while`."""
    from spark_rapids_tpu.ops.gather import dense_run_counts
    cap = 1 << 20
    compiled = jax.jit(dense_run_counts, static_argnums=1).lower(
        chip((2048,), jnp.int64), cap).compile()
    text = compiled.as_text()
    assert compiled.out_info.dtype == jnp.int32
    assert " while(" not in text and "s64[%d]" % cap not in text


def test_gather_strings_compiles_without_a_loop(chip):
    """The variable-length gather at the shape q3's second join traces it
    with (the item side's 2^15 rows of `i_brand` gathered into 2^18 stream
    rows over a 2^22-lane char buffer; the aggregate's and the sort's
    calls are 2^18 x 2^22 on both sides): the owner-row lookup is a
    scatter of row-end flags and a blocked int32 prefix — no `while` of
    per-character gathers (six were 3.4 s of q3's 7.1 busy seconds;
    ledger, PR 29) and no int64 lane of the char capacity."""
    from spark_rapids_tpu.ops.strings import gather_strings
    src_rows, src_chars, rows, cap = 1 << 15, 1 << 18, 1 << 18, 1 << 22
    col = TpuColumnVector(dt.STRING, validity=chip((src_rows,), jnp.bool_),
                          offsets=chip((src_rows + 1,), jnp.int32),
                          chars=chip((src_chars,), jnp.uint8))
    compiled = jax.jit(
        lambda c, idx, live: gather_strings(c, idx, cap, out_live=live)) \
        .lower(col, chip((rows,), jnp.int32), chip((rows,), jnp.bool_)) \
        .compile()
    text = compiled.as_text()
    assert " while(" not in text and "s64[%d]" % cap not in text
    assert text.count(" scatter(") == 1


# --- float64 on the chip ----------------------------------------------------------

def test_f64_to_s64_bitcast_is_refused_and_s64_to_f64_is_not(chip):
    """What ops/sort_keys.py rests on: XLA's X64 rewriter for the TPU
    cannot bitcast float64 -> int64 (float64 is a pair of float32 there),
    while int64 -> float64 — the Parquet DOUBLE decode's direction —
    compiles."""
    def to_bits(x):
        return jax.lax.bitcast_convert_type(x, jnp.int64)

    def from_bits(x):
        return jax.lax.bitcast_convert_type(x, jnp.float64)
    with pytest.raises(jax.errors.JaxRuntimeError, match="UNIMPLEMENTED"):
        jax.jit(to_bits).lower(chip((ROWS,), jnp.float64)).compile()
    jax.jit(from_bits).lower(chip((ROWS,), jnp.int64)).compile()


def test_f64_order_key_round_trip_compiles_off_cpu(chip, monkeypatch):
    """The off-CPU float64 ordering key and its inverse (min/max reduce)
    use no refused bitcast."""
    from spark_rapids_tpu.ops.sort_keys import (orderable_int,
                                                orderable_int_to_float)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def there_and_back(c):
        return orderable_int_to_float(orderable_int(c), jnp.float64)
    jax.jit(there_and_back).lower(_col(chip, dt.FLOAT64)).compile()


# --- four chips: the ICI exchange ---------------------------------------------------

def test_ici_all_to_all_compiles_on_four_chips(topo, no_persistent_cache):
    """``make_ici_all_to_all`` over a Mesh of the four described devices:
    one int32 lane, one int64 lane and one string payload at 2^20 rows
    per device; the compiled program must hold the collective."""
    from spark_rapids_tpu.shuffle.ici import make_ici_all_to_all
    mesh = Mesh(np.array(topo.devices), ("x",))
    ndev, cap, char_cap, pair_bytes = 4, 1 << 20, 1 << 23, 1 << 21

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(
            (ndev,) + shape, dtype,
            sharding=NamedSharding(mesh, P("x", *([None] * len(shape)))))
    datas = (sds((cap,), jnp.int32), sds((cap,), jnp.int64),
             sds((cap, 0), jnp.int8), sds((cap,), jnp.int32))
    valids = tuple(sds((cap,), jnp.bool_) for _ in datas)
    compiled = make_ici_all_to_all(mesh).lower(
        datas, valids, sds((cap,), jnp.int32), sds((cap,), jnp.bool_),
        char_offs=(sds((cap + 1,), jnp.int32),),
        char_bytes=(sds((char_cap,), jnp.uint8),),
        char_caps=(pair_bytes,)).compile()
    assert "all-to-all" in compiled.as_text()
    per_device = compiled.memory_analysis()
    assert per_device.temp_size_in_bytes < (12 << 30), per_device
