"""Host <-> device columnar interchange over Arrow.

TPU analog of the reference's cudf Java/JNI boundary (`ai.rapids.cudf.Table`,
`HostMemoryBuffer` — SURVEY.md §2.2-E; reference mount empty): pyarrow
RecordBatches are the host currency (what the JVM side would hand across the
Arrow C Data Interface), jax.Arrays the device currency. Conversions are
zero-copy on the host side wherever Arrow buffer layout allows.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from .. import datatypes as dt
from .batch import TpuBatch, bucket_rows, bucket_bytes
from .column import TpuColumnVector

__all__ = ["arrow_to_device", "device_to_arrow", "arrow_schema",
           "engine_schema"]


def engine_schema(arrow_schema: pa.Schema) -> dt.Schema:
    return dt.Schema([dt.StructField(f.name, dt.from_arrow(f.type),
                                     f.nullable) for f in arrow_schema])


def arrow_schema(schema: dt.Schema) -> pa.Schema:
    return pa.schema([pa.field(f.name, dt.to_arrow(f.dtype), f.nullable)
                      for f in schema])


def _valid_mask(arr: pa.Array) -> Optional[np.ndarray]:
    if arr.null_count == 0:
        return None
    return pc.is_valid(arr).to_numpy(zero_copy_only=False)


def _fixed_values(arr: pa.Array, t: dt.DataType) -> np.ndarray:
    """Dense host values (nulls zero-filled) in the device lane dtype."""
    atype = arr.type
    if pa.types.is_boolean(atype):
        return pc.fill_null(arr, False).to_numpy(zero_copy_only=False)
    if pa.types.is_date32(atype):
        arr = arr.view(pa.int32())
    elif pa.types.is_timestamp(atype):
        if atype.unit != "us":  # ns (pandas default) / ms / s inputs
            arr = arr.cast(pa.timestamp("us", tz=atype.tz))
        arr = arr.view(pa.int64())
    elif pa.types.is_decimal(atype):
        if not pa.types.is_decimal128(atype):
            arr = arr.cast(pa.decimal128(atype.precision, atype.scale))
            atype = arr.type
        # decimal128 little-endian: low 8 bytes == value when it fits int64
        assert atype.precision <= dt.DecimalType.MAX_INT64_PRECISION, \
            "decimal128 > 18 digits not yet on device"
        if arr.null_count:
            arr = pc.fill_null(arr, pa.scalar(0, type=atype))
        buf = arr.buffers()[1]
        vals = np.frombuffer(buf, np.int64)
        vals = vals.reshape(-1, 2)[arr.offset: arr.offset + len(arr), 0]
        return np.ascontiguousarray(vals)
    if arr.null_count:
        zero = pa.scalar(0, type=arr.type) if not pa.types.is_boolean(arr.type) \
            else pa.scalar(False)
        arr = pc.fill_null(arr, zero)
    return arr.to_numpy(zero_copy_only=False).astype(t.np_dtype, copy=False)


def _string_parts(arr: pa.Array) -> Tuple[np.ndarray, np.ndarray]:
    """(offsets[int32 n+1], chars[uint8]) with offsets rebased to 0."""
    if arr.null_count:
        fill = pa.scalar("", type=arr.type) if pa.types.is_string(arr.type) \
            else pa.scalar(b"", type=arr.type)
        arr = pc.fill_null(arr, fill)
    if pa.types.is_large_string(arr.type):
        arr = arr.cast(pa.string())
    elif pa.types.is_large_binary(arr.type):
        arr = arr.cast(pa.binary())
    bufs = arr.buffers()
    offsets = np.frombuffer(bufs[1], np.int32)[
        arr.offset: arr.offset + len(arr) + 1]
    data = np.frombuffer(bufs[2], np.uint8) if bufs[2] is not None else \
        np.zeros(0, np.uint8)
    chars = data[offsets[0]: offsets[-1]]
    if offsets[0] != 0:
        offsets = offsets - offsets[0]
    return offsets, chars


def _pad_validity(valid: Optional[np.ndarray], n: int, capacity: int):
    import jax.numpy as jnp
    out = np.zeros(capacity, np.bool_)
    out[:n] = True if valid is None else valid
    return jnp.asarray(out)


def _list_parts(arr: pa.Array):
    """(offsets[int32 n+1] rebased to 0, element window (start, end))."""
    at = arr.type
    if pa.types.is_large_list(at):
        arr = arr.cast(pa.list_(at.value_type))
    bufs = arr.buffers()
    offsets = np.frombuffer(bufs[1], np.int32)[
        arr.offset: arr.offset + len(arr) + 1]
    start, end = int(offsets[0]), int(offsets[-1])
    if start != 0:
        offsets = offsets - start
    return offsets, start, end


def arrow_column_to_device(arr, t: dt.DataType, capacity: int) \
        -> TpuColumnVector:
    import jax.numpy as jnp
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    n = len(arr)
    if isinstance(t, dt.NullType):
        return TpuColumnVector.nulls(t, capacity)
    if isinstance(t, dt.StructType):
        valid = _valid_mask(arr)
        children = [arrow_column_to_device(arr.field(i), f.dtype, capacity)
                    for i, f in enumerate(t.fields)]
        return TpuColumnVector(t, validity=_pad_validity(valid, n, capacity),
                               children=children)
    if isinstance(t, (dt.ArrayType, dt.MapType)):
        valid = _valid_mask(arr)
        offsets, start, end = _list_parts(arr)
        obuf = np.zeros(capacity + 1, np.int32)
        obuf[:n + 1] = offsets
        obuf[n + 1:] = offsets[-1] if n else 0
        ecap = bucket_rows(end - start)
        if isinstance(t, dt.MapType):
            children = [
                arrow_column_to_device(
                    arr.keys.slice(start, end - start), t.key_type, ecap),
                arrow_column_to_device(
                    arr.items.slice(start, end - start), t.value_type,
                    ecap)]
        else:
            children = [arrow_column_to_device(
                arr.values.slice(start, end - start), t.element_type,
                ecap)]
        return TpuColumnVector(t, validity=_pad_validity(valid, n, capacity),
                               offsets=jnp.asarray(obuf), children=children)
    if t.is_variable_width:
        if isinstance(t, dt.DecimalType):
            raise NotImplementedError(
                f"wide decimal (precision > 18) not yet on device: {t}")
        if not isinstance(t, (dt.StringType, dt.BinaryType)):
            raise NotImplementedError(f"nested type on device: {t}")
        valid = _valid_mask(arr)
        offsets, chars = _string_parts(arr)
        char_cap = bucket_bytes(len(chars))
        return TpuColumnVector.from_string_parts(
            t, offsets, chars, valid, capacity, char_cap)
    valid = _valid_mask(arr)
    values = _fixed_values(arr, t)
    return TpuColumnVector.from_numpy(t, values, valid, capacity)


def arrow_to_device(rb: pa.RecordBatch,
                    schema: Optional[dt.Schema] = None,
                    capacity: Optional[int] = None) -> TpuBatch:
    """Upload a host RecordBatch into a padded device TpuBatch."""
    if schema is None:
        schema = engine_schema(rb.schema)
    n = rb.num_rows
    cap = capacity or bucket_rows(n)
    cols = [arrow_column_to_device(rb.column(i), schema[i].dtype, cap)
            for i in range(rb.num_columns)]
    return TpuBatch(cols, schema, n)


def _null_buffer(valid: np.ndarray):
    """Arrow validity bitmap buffer from a bool validity array."""
    return pa.array(valid).buffers()[1]


def _host_column_to_arrow(col: TpuColumnVector, host, n: int,
                          row_start: int = 0) -> pa.Array:
    """Build an Arrow array from prefetched host buffers. `host` maps the
    column's device arrays (by position in col.arrays(), pre-order
    through nested children) to numpy. `row_start` selects a child
    window for nested recursion (array elements)."""
    t = col.dtype
    atype = dt.to_arrow(t)
    bufs = list(host)
    data = bufs.pop(0) if col.data is not None else None
    valid = np.asarray(bufs.pop(0))[row_start: row_start + n]
    offsets_h = np.asarray(bufs.pop(0)) if col.offsets is not None else None
    chars_h = np.asarray(bufs.pop(0)) if col.chars is not None else None
    mask = None if bool(valid.all()) else ~valid
    if isinstance(t, dt.StructType):
        null_buf = None if mask is None else _null_buffer(valid)
        children = []
        for ch in col.children:
            k = len(ch.arrays())
            children.append(_host_column_to_arrow(ch, bufs[:k], n,
                                                  row_start))
            bufs = bufs[k:]
        return pa.Array.from_buffers(atype, n, [null_buf],
                                     children=children)
    if isinstance(t, (dt.ArrayType, dt.MapType)):
        offsets = offsets_h[row_start: row_start + n + 1].astype(
            np.int32, copy=True)
        start = int(offsets[0]) if n else 0
        end = int(offsets[-1]) if n else 0
        if start != 0:
            offsets = offsets - start
        null_buf = None if mask is None else _null_buffer(valid)
        children = []
        for ch in col.children:
            k = len(ch.arrays())
            children.append(_host_column_to_arrow(ch, bufs[:k],
                                                  end - start, start))
            bufs = bufs[k:]
        if isinstance(t, dt.MapType):
            entries = pa.StructArray.from_arrays(
                children, fields=[atype.key_field, atype.item_field])
            return pa.Array.from_buffers(
                atype, n,
                [null_buf, pa.py_buffer(np.ascontiguousarray(offsets))],
                children=[entries])
        return pa.Array.from_buffers(
            atype, n,
            [null_buf, pa.py_buffer(np.ascontiguousarray(offsets))],
            children=children)
    if isinstance(t, dt.NullType):
        return pa.nulls(n)
    if col.is_string_like:
        offsets = offsets_h[row_start: row_start + n + 1]
        chars = chars_h
        start = int(offsets[0]) if n else 0
        end = int(offsets[-1]) if n else 0
        # Rebuild via Arrow buffers (zero-copy from the host numpy views).
        # Offsets may be absolute into a shared chars buffer (split
        # batches): rebase them AND slice chars from the same start.
        if start != 0:
            offsets = offsets - start
        null_buf = None if mask is None else _null_buffer(valid)
        arr = pa.Array.from_buffers(
            pa.string() if isinstance(t, dt.StringType) else pa.binary(), n,
            [null_buf, pa.py_buffer(np.ascontiguousarray(offsets)),
             pa.py_buffer(np.ascontiguousarray(chars[start:end]))],
            null_count=-1)
        return arr
    values = np.asarray(data)[row_start: row_start + n]
    if isinstance(t, dt.DecimalType):
        lo = values.astype(np.int64)
        hi = (lo >> 63).astype(np.int64)  # sign extension
        pairs = np.empty((n, 2), np.int64)
        pairs[:, 0] = lo
        pairs[:, 1] = hi
        null_buf = None if mask is None else _null_buffer(valid)
        return pa.Array.from_buffers(
            atype, n, [null_buf, pa.py_buffer(np.ascontiguousarray(pairs))],
            null_count=-1)
    if isinstance(t, dt.DateType):
        return pa.array(values, pa.int32(), mask=mask).view(pa.date32())
    if isinstance(t, dt.TimestampType):
        return pa.array(values, pa.int64(), mask=mask).view(atype)
    return pa.array(values, atype, mask=mask)


def device_column_to_arrow(col: TpuColumnVector, n: int) -> pa.Array:
    """Download one device column (first n rows) as an Arrow array."""
    import jax
    return _host_column_to_arrow(col, jax.device_get(col.arrays()), n)


def device_to_arrow(batch: TpuBatch) -> pa.RecordBatch:
    """Download a batch in ONE device->host transfer: every buffer (plus
    the row count) rides a single device_get, paying the per-transfer
    latency once at the price of the padding bytes (device->host ran at
    0.68 GB/s for 128 MiB on the v5e, chip run of PR 21)."""
    import jax
    from ..ops.gather import ensure_compacted
    batch = ensure_compacted(batch)  # arrow slices the live prefix
    leaves = [batch.row_count]
    spans = []
    for c in batch.columns:
        arrs = c.arrays()
        spans.append(len(arrs))
        leaves.extend(arrs)
    host = jax.device_get(leaves)
    n = int(host[0])
    batch._num_rows_cache = n
    arrays = []
    off = 1
    for c, k in zip(batch.columns, spans):
        arrays.append(_host_column_to_arrow(c, host[off:off + k], n))
        off += k
    return pa.RecordBatch.from_arrays(arrays,
                                      schema=arrow_schema(batch.schema))
