import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import datatypes as dt
from spark_rapids_tpu.columnar import (arrow_to_device, device_to_arrow,
                                       bucket_rows)


def roundtrip(rb: pa.RecordBatch) -> pa.RecordBatch:
    return device_to_arrow(arrow_to_device(rb))


def test_bucket_rows():
    assert bucket_rows(0) == 128
    assert bucket_rows(128) == 128
    assert bucket_rows(129) == 256
    assert bucket_rows(1000) == 1024


@pytest.mark.parametrize("atype,values", [
    (pa.int32(), [1, 2, None, -7, 2**31 - 1]),
    (pa.int64(), [None, 0, -(2**63), 2**63 - 1]),
    (pa.int8(), [1, None, -128, 127]),
    (pa.int16(), [300, None, -32768]),
    (pa.float32(), [1.5, None, float("nan"), float("inf")]),
    (pa.float64(), [None, -0.0, 1e300, float("-inf")]),
    (pa.bool_(), [True, None, False, True]),
])
def test_fixed_width_roundtrip(atype, values):
    rb = pa.record_batch({"a": pa.array(values, type=atype)})
    out = roundtrip(rb)
    assert out.column(0).equals(rb.column(0)) or (
        # NaN != NaN under Arrow equals; compare via numpy
        np.array_equal(out.column(0).to_numpy(zero_copy_only=False),
                       rb.column(0).to_numpy(zero_copy_only=False),
                       equal_nan=True))


def test_string_roundtrip():
    vals = ["hello", "", None, "wörld", "a" * 1000, None, "x"]
    rb = pa.record_batch({"s": pa.array(vals, type=pa.string())})
    out = roundtrip(rb)
    assert out.column(0).to_pylist() == vals


def test_binary_roundtrip():
    vals = [b"\x00\x01", None, b"", b"abc"]
    rb = pa.record_batch({"b": pa.array(vals, type=pa.binary())})
    assert roundtrip(rb).column(0).to_pylist() == vals


def test_date_timestamp_roundtrip():
    import datetime
    d = [datetime.date(2020, 1, 1), None, datetime.date(1969, 12, 31)]
    ts = [datetime.datetime(2021, 6, 1, 12, 30, 15, 123456), None, None]
    rb = pa.record_batch({
        "d": pa.array(d, type=pa.date32()),
        "t": pa.array(ts, type=pa.timestamp("us", tz="UTC")),
    })
    out = roundtrip(rb)
    assert out.column(0).to_pylist() == d
    got = out.column(1).to_pylist()
    assert got[1] is None and got[2] is None
    assert got[0].replace(tzinfo=None) == ts[0]


def test_decimal_roundtrip():
    import decimal
    vals = [decimal.Decimal("123.45"), None, decimal.Decimal("-0.01"),
            decimal.Decimal("99999999999999.99")]
    rb = pa.record_batch({"d": pa.array(vals, type=pa.decimal128(16, 2))})
    assert roundtrip(rb).column(0).to_pylist() == vals


def test_sliced_input():
    arr = pa.array(["aa", "bb", "cc", "dd", None, "ff"]).slice(2, 3)
    rb = pa.record_batch({"s": arr})
    assert roundtrip(rb).column(0).to_pylist() == ["cc", "dd", None]


def test_schema_mapping():
    rb = pa.record_batch({"i": pa.array([1], pa.int32()),
                          "s": pa.array(["x"])})
    b = arrow_to_device(rb)
    assert b.schema.names == ["i", "s"]
    assert b.schema.types == [dt.INT32, dt.STRING]
    assert b.num_rows == 1
    assert b.capacity == 128


# --- the variable-length gather: gather_strings and its twin gather_list ----

def _varlen_case(lens, indices, live=None, valid=None, cap=None):
    """Source row lengths, the gather's indices, the output's live mask
    (any mask, not only a prefix), the source rows' validity and the
    payload capacity (None: exactly the source's total)."""
    return dict(lens=lens, indices=indices,
                live=[True] * len(indices) if live is None else live,
                valid=[True] * len(lens) if valid is None else valid,
                cap=sum(lens) if cap is None else cap)


_VARLEN_CASES = {
    "empty_rows_share_an_offset": _varlen_case(
        [3, 0, 0, 5, 0, 2], [0, 1, 2, 3, 4, 5]),
    "dead_rows_between_live_ones": _varlen_case(
        [4, 7, 1, 6, 2, 9], [5, 0, 3, 1, 4, 2],
        live=[True, False, False, True, False, True], cap=40),
    "repeated_indices": _varlen_case(
        [2, 5, 3], [1, 1, 0, 2, 1, 0], cap=32),
    "total_equals_capacity": _varlen_case(
        [6, 0, 10, 4], [2, 3, 0, 1]),
    "total_under_capacity": _varlen_case(
        [6, 0, 10, 4], [2, 0], cap=64),
    "total_past_capacity": _varlen_case(
        [6, 3, 10, 4], [2, 2, 0, 2, 1, 3]),
    "capacity_off_the_1024_block": _varlen_case(
        [700, 0, 423, 301, 5], [3, 0, 1, 4, 2, 4],
        live=[True, True, True, False, True, True], cap=1500),
    "capacity_of_zero": _varlen_case([0, 0, 0], [2, 0, 1]),
    "null_rows": _varlen_case(
        [3, 0, 4, 0, 2], [4, 3, 2, 1, 0],
        valid=[True, False, True, False, True], cap=16),
    "all_rows_dead": _varlen_case(
        [3, 8, 4], [2, 1, 0, 1], live=[False] * 4, cap=16),
}


def _varlen_reference(offsets, payload, indices, live, cap):
    """Plain Python: the live rows' slices end to end, cut at `cap`."""
    rows = [payload[offsets[i]:offsets[i + 1]] if keep else payload[:0]
            for i, keep in zip(indices, live)]
    new_offsets = np.concatenate(
        [[0], np.cumsum([len(r) for r in rows])]).astype(np.int32)
    return new_offsets, np.concatenate(rows + [payload[:0]])[:cap]


@pytest.mark.parametrize("kind", ["strings", "list"])
@pytest.mark.parametrize("name", list(_VARLEN_CASES))
def test_variable_length_gather_matches_plain_reference(name, kind):
    """Offsets, payload up to the total, and validity, exactly: the
    owner-row lookup is a prefix count of row ends, and every row's end
    counts (dead and zero-length rows share one with their predecessor)."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.column import TpuColumnVector
    from spark_rapids_tpu.ops.gather import gather_list
    from spark_rapids_tpu.ops.strings import gather_strings
    case = _VARLEN_CASES[name]
    rng = np.random.RandomState(len(name))
    lens, cap = case["lens"], case["cap"]
    offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    total = int(offsets[-1])
    valid = np.array(case["valid"], np.bool_)
    indices = np.array(case["indices"], np.int32)
    live = np.array(case["live"], np.bool_)

    if kind == "strings":
        payload = rng.randint(1, 256, total).astype(np.uint8)
        col = TpuColumnVector.from_string_parts(
            dt.STRING, offsets, payload, valid, len(lens), total)
        out = gather_strings(col, jnp.asarray(indices), cap,
                             out_live=jnp.asarray(live))
        got, want_valid = np.asarray(out.chars), valid[indices]
    else:
        assert cap >= total, "a list gathers into its child's capacity"
        payload = rng.randint(-2**31, 2**31 - 1, total).astype(np.int32)
        elem_valid = rng.rand(total) < 0.7
        child = TpuColumnVector.from_numpy(dt.INT32, payload, elem_valid,
                                           cap)
        col = TpuColumnVector(dt.ArrayType(dt.INT32),
                              validity=jnp.asarray(valid),
                              offsets=jnp.asarray(offsets),
                              children=[child])
        out = gather_list(col, jnp.asarray(indices), jnp.asarray(live))
        got, want_valid = np.asarray(out.children[0].data), \
            valid[indices] & live
    want_offsets, want = _varlen_reference(offsets, payload, indices, live,
                                           cap)
    assert np.asarray(out.offsets).dtype == np.int32
    assert got.shape == (cap,)
    np.testing.assert_array_equal(np.asarray(out.offsets), want_offsets)
    np.testing.assert_array_equal(got[:len(want)], want)
    np.testing.assert_array_equal(np.asarray(out.validity), want_valid)
    if kind == "strings":
        assert not got[len(want):].any()      # zero past the total
    else:
        _, want_elem_valid = _varlen_reference(offsets, elem_valid, indices,
                                               live, cap)
        got_elem_valid = np.asarray(out.children[0].validity)
        np.testing.assert_array_equal(got_elem_valid[:len(want)],
                                      want_elem_valid)
        assert not got_elem_valid[len(want):].any()


def test_string_gather_into_no_capacity_keeps_the_offsets():
    """A char capacity of 0 under live characters: every byte is cut, the
    offsets still say where each row would have ended."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.column import TpuColumnVector
    from spark_rapids_tpu.ops.strings import gather_strings
    col = TpuColumnVector.from_string_parts(
        dt.STRING, np.array([0, 3, 3, 5], np.int32),
        np.frombuffer(b"abcde", np.uint8), None, 3, 5)
    out = gather_strings(col, jnp.asarray([2, 0, 1], jnp.int32), 0)
    assert out.chars.shape == (0,)
    assert np.asarray(out.offsets).tolist() == [0, 2, 5, 5]
