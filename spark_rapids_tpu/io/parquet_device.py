"""Device-side Parquet decode: ship ENCODED pages, decode in HBM.

TPU analog of the reference's cuIO path — its north star is literally
"GpuParquetScan decodes directly into TPU HBM" (BASELINE.json north_star;
SURVEY.md:162 cuIO, :198, §7.2-P5 "Pallas page-decode experiments
PLAIN/dictionary/RLE"; reference mount empty). The round-4 scan decoded
on host pyarrow and uploaded fully-decoded columns; for dictionary/RLE
encoded columns that multiplies the bytes crossing the host→device link
by the compression ratio. This module uploads the column chunk's own
encoded representation instead:

  host side (cheap, IO-shaped):
    - read the chunk's raw bytes (one pread via the footer offsets),
    - parse page headers (minimal Thrift compact-protocol reader),
    - codec-decompress page payloads (snappy/zstd/gzip — memcpy-rate),
    - walk the RLE/bit-packed run HEADERS (varints only — the payload
      bytes stay opaque) into a run table,
  device side (one XLA program per shape bucket):
    - expand runs: value v_i = two uint32 gathers + funnel shift + mask
      (bit-packed), or the run's literal (RLE); what a run holds is
      expanded over its positions by a scatter + prefix sum, never
      gathered per row,
    - dictionary gather for dict-encoded pages, bitcast for PLAIN,
    - for a chunk that holds a null: definition-level expansion (same
      run machinery at width 1) + dense→row gather via a prefix sum; a
      chunk without nulls runs neither.

PLAIN-only non-null chunks skip the kernel entirely (the bytes ARE the
column). The envelope covers v1 AND v2 data pages of flat columns in
PLAIN / PLAIN_DICTIONARY / RLE_DICTIONARY / DELTA_BINARY_PACKED /
DELTA_LENGTH_BYTE_ARRAY encodings, including BYTE_ARRAY strings:

- PLAIN strings: the host walks the 4-byte length prefixes once into
  int32 offsets; the page's character bytes ride the fused-decode
  arena and the device gathers them exactly like a dictionary whose
  index stream is the identity (so dictionary-then-PLAIN mixed chunks
  share one mechanism and one JIT cache key shape);
- DATA_PAGE_V2: split rep/def/data regions, levels RLE-decoded into
  the existing null-mask run tables (levels are uncompressed and
  carry no length prefix in v2);
- DELTA_BINARY_PACKED: the host unpacks miniblock headers into
  bit-packed delta runs (min_delta rides the run table), the device
  reconstructs values with a prefix sum that restarts at each page's
  first-value run;
- DELTA_LENGTH_BYTE_ARRAY: lengths host-decoded (they gate where the
  character bytes start), characters gathered on device through the
  same identity-index string path.

Anything still outside the envelope (nested, FIXED_LEN_BYTE_ARRAY,
DELTA_BYTE_ARRAY prefix compression, BYTE_STREAM_SPLIT, LZ4,
repetition levels, delta miniblocks wider than 32 bits) falls back to
the host pyarrow decode per column chunk — the same per-format
kill-switch philosophy as the reference's readers. Every
``HostFallback`` carries a bounded ``reason`` slug so the scan can
export a per-reason fallback histogram (envelope regressions show up
in BENCH rounds, not in silence).
"""
from __future__ import annotations

import struct
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

from .. import datatypes as dt
from ..columnar.batch import (bucket_bytes, bucket_fine,
                              bucket_fine_even, bucket_rows)
from ..columnar.column import TpuColumnVector
from ..obs.tracer import StageClock
from ..programs import module_name, named_jit

__all__ = ["plan_chunk", "chunk_envelope", "chunk_byte_range",
           "decode_chunk_device",
           "decode_row_group_device", "merge_chunk_plans", "ChunkPlan",
           "HostFallback", "encoded_nbytes"]

# string-expansion device cap shared by plan_chunk's per-chunk guard and
# the coalescer's merge precheck (io/scan.py)
STR_EXPANSION_CAP = 1 << 26


#: Bounded label set for the per-reason fallback histogram (obs metric
#: labels must not explode; free-form messages stay on the exception).
FALLBACK_REASONS = ("phys-type", "nested", "def-depth", "codec",
                    "encoding", "dict-width", "delta-width", "page",
                    "truncated", "size-guard", "string-cap", "other")


class HostFallback(Exception):
    """This column chunk is outside the device-decode envelope; the scan
    decodes it with pyarrow instead (per-chunk granularity). ``reason``
    is one of :data:`FALLBACK_REASONS` — the bounded slug the scan's
    fallback histogram is labeled with."""

    def __init__(self, msg: str, reason: str = "other"):
        super().__init__(msg)
        self.reason = reason if reason in FALLBACK_REASONS else "other"


# --- Thrift compact protocol (just enough for PageHeader) ------------------

_CT_STOP, _CT_TRUE, _CT_FALSE, _CT_BYTE, _CT_I16, _CT_I32, _CT_I64, \
    _CT_DOUBLE, _CT_BINARY, _CT_LIST, _CT_SET, _CT_MAP, _CT_STRUCT = \
    range(13)


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    result = 0
    shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7


def _zigzag(buf: bytes, pos: int) -> Tuple[int, int]:
    v, pos = _varint(buf, pos)
    return (v >> 1) ^ -(v & 1), pos


def _skip(buf: bytes, pos: int, ctype: int) -> int:
    if ctype in (_CT_TRUE, _CT_FALSE):
        return pos
    if ctype == _CT_BYTE:
        return pos + 1
    if ctype in (_CT_I16, _CT_I32, _CT_I64):
        return _varint(buf, pos)[1]
    if ctype == _CT_DOUBLE:
        return pos + 8
    if ctype == _CT_BINARY:
        n, pos = _varint(buf, pos)
        return pos + n
    if ctype in (_CT_LIST, _CT_SET):
        head = buf[pos]
        pos += 1
        size = head >> 4
        if size == 15:
            size, pos = _varint(buf, pos)
        for _ in range(size):
            pos = _skip(buf, pos, head & 0x0F)
        return pos
    if ctype == _CT_MAP:
        size, pos = _varint(buf, pos)
        if size == 0:
            return pos
        kv = buf[pos]
        pos += 1
        for _ in range(size):
            pos = _skip(buf, pos, kv >> 4)
            pos = _skip(buf, pos, kv & 0x0F)
        return pos
    if ctype == _CT_STRUCT:
        fid = 0
        while True:
            head = buf[pos]
            pos += 1
            if head == 0:
                return pos
            delta = head >> 4
            if delta == 0:
                fid, pos = _zigzag(buf, pos)
            else:
                fid += delta
            pos = _skip(buf, pos, head & 0x0F)
    raise HostFallback(f"unknown thrift type {ctype}", "page")


def _read_struct(buf: bytes, pos: int) -> Tuple[Dict[int, object], int]:
    """Field-id → value for i32/i64/bool fields; nested structs recurse;
    everything else (statistics blobs etc.) is skipped."""
    out: Dict[int, object] = {}
    fid = 0
    while True:
        head = buf[pos]
        pos += 1
        if head == 0:
            return out, pos
        delta = head >> 4
        if delta == 0:
            fid, pos = _zigzag(buf, pos)
        else:
            fid += delta
        ctype = head & 0x0F
        if ctype in (_CT_TRUE, _CT_FALSE):
            out[fid] = ctype == _CT_TRUE
        elif ctype in (_CT_I16, _CT_I32, _CT_I64):
            out[fid], pos = _zigzag(buf, pos)
        elif ctype == _CT_STRUCT:
            out[fid], pos = _read_struct(buf, pos)
        else:
            pos = _skip(buf, pos, ctype)


# PageType / Encoding enum values from parquet.thrift (public format spec)
_PAGE_DATA, _PAGE_INDEX, _PAGE_DICT, _PAGE_DATA_V2 = 0, 1, 2, 3
_ENC_PLAIN, _ENC_PLAIN_DICT, _ENC_RLE, _ENC_RLE_DICT = 0, 2, 3, 8
_ENC_DELTA_BINARY_PACKED, _ENC_DELTA_LENGTH_BA, _ENC_DELTA_BA = 5, 6, 7

# Run-table meta bits (column 1 of the int64[n_runs, 4] run table).
# Bits 0-7 hold the bit-packed width; bits 16+ hold the merged-group
# index base merge_chunk_plans adds for dictionary/string runs.
_META_RLE = 1 << 8      # constant run: value rides in column 2
_META_DICT = 1 << 9     # expanded value is a dictionary index
_META_IDENT = 1 << 10   # value_i = col2 + (i - row_start): the identity
                        # index stream PLAIN / DELTA_LENGTH strings use
_META_DELTA = 1 << 11   # bit-packed DELTA miniblock: col2 = min_delta,
                        # the device prefix-sums the expanded deltas


def parse_page_header(buf: bytes, pos: int):
    """(dict with keys: type, uncompressed, compressed, data_hdr|dict_hdr,
    header_len)."""
    fields, end = _read_struct(buf, pos)
    return {
        "type": fields.get(1),
        "uncompressed": fields.get(2),
        "compressed": fields.get(3),
        "data_hdr": fields.get(5),
        "dict_hdr": fields.get(7),
        "v2_hdr": fields.get(8),
        "header_len": end - pos,
    }


# --- RLE / bit-packed hybrid run parsing (headers only) --------------------

def _parse_runs(data: bytes, start: int, end: int, width: int,
                total: int, packed_base_bits: int):
    """Walk the RLE/bit-packed hybrid stream's run headers. Returns
    (runs, stream_end): runs = list of (value_row_start, is_rle, value,
    bit_start) where bit_start is relative to `packed_base_bits` +
    (offset within data[start:end])*8 — i.e. positions in the packed
    buffer the caller appends data[start:end] to. Payload bytes are
    never touched here."""
    runs = []
    count = 0
    pos = start
    byte_w = (width + 7) // 8
    while count < total:
        if pos >= end:
            raise HostFallback("RLE stream truncated", "truncated")
        header, pos = _varint(data, pos)
        if header & 1:  # bit-packed: groups of 8 values
            groups = header >> 1
            runs.append((count, False, 0,
                         packed_base_bits + (pos - start) * 8))
            pos += groups * width
            count += groups * 8
        else:
            repeat = header >> 1
            if repeat == 0:
                raise HostFallback("zero-length RLE run", "truncated")
            value = int.from_bytes(data[pos:pos + byte_w], "little")
            pos += byte_w
            runs.append((count, True, value, 0))
            count += repeat
    return runs, pos


def _popcount_valid(def_runs, packed: bytes, base_bits: int,
                    n_rows: int) -> int:
    """Number of set definition-level bits (width 1) among the first
    n_rows — host-side, numpy unpackbits over the tiny level buffer."""
    total = 0
    for i, (row0, is_rle, value, bit_start) in enumerate(def_runs):
        row1 = def_runs[i + 1][0] if i + 1 < len(def_runs) else n_rows
        row1 = min(row1, n_rows)
        if row1 <= row0:
            continue
        n = row1 - row0
        if is_rle:
            total += n * (value & 1)
        else:
            b0 = (bit_start - base_bits) // 8
            nbytes = (n + 7) // 8
            bits = np.unpackbits(
                np.frombuffer(packed, np.uint8, count=nbytes, offset=b0),
                bitorder="little")[:n]
            total += int(bits.sum())
    return total


# --- chunk planning --------------------------------------------------------

_PHYS_LANE = {"INT32": np.dtype(np.int32), "INT64": np.dtype(np.int64),
              "FLOAT": np.dtype(np.float32), "DOUBLE": np.dtype(np.float64),
              "BOOLEAN": np.dtype(np.bool_)}
_SUPPORTED_CODECS = {"UNCOMPRESSED", "SNAPPY", "ZSTD", "GZIP", "BROTLI"}
_MAX_DICT_WIDTH = 24  # funnel-shift window bound: shift(<=31) + width <= 55
# the decoder's bit positions are int32 lanes: a chunk's packed stream
# stays under 2^31 bits (256 MiB), guard words included
_MAX_PACKED_WORDS = 1 << 26


class ChunkPlan:
    """Host-side product of planning one column chunk for device decode:
    numpy arrays ready for upload + the static facts the kernel needs.
    For STRING chunks (BYTE_ARRAY), `lane` is int32 (the index stream),
    `dictionary` is None and `str_dict` holds the host-side string
    store (offsets int32[n+1], chars uint8[...]) — dictionary-page
    entries first, then any PLAIN / DELTA_LENGTH page values in page
    order; dictionary runs index the dict slice, identity runs index
    their page's slice, and the device gathers the characters in HBM
    either way. `is_delta` marks DELTA_BINARY_PACKED numeric chunks
    whose values the device reconstructs by prefix sum; `str_bound` is
    the chunk's worst-case decoded character count (the string output
    buffer currency — merge sums it). `has_nulls` says whether the
    device runs the definition-level pass: the chunk holds a null
    (`n_valid < n_rows`, whatever the schema's nullability says), or
    the scan met one in an earlier row group of the column and keeps
    the column on one program (`io/scan.py`); `chunks` counts the column
    chunks a merged plan was made from."""

    __slots__ = ("n_rows", "lane", "dictionary", "packed", "runs",
                 "def_packed", "def_runs", "n_valid", "has_nulls",
                 "encoded_bytes", "str_dict", "str_char_cap",
                 "str_max_len", "is_delta", "str_bound", "chunks")

    def __init__(self, n_rows, lane, dictionary, packed, runs, def_packed,
                 def_runs, n_valid, encoded_bytes, str_dict=None,
                 str_char_cap=0, str_max_len=0, is_delta=False,
                 str_bound=0, chunks=1):
        self.n_rows = n_rows
        self.lane = lane
        self.dictionary = dictionary
        self.packed = packed
        self.runs = runs              # int64[n_runs, 4]: row, flags, val, bit
        self.def_packed = def_packed
        self.def_runs = def_runs
        self.n_valid = n_valid
        self.has_nulls = n_valid < n_rows
        self.encoded_bytes = encoded_bytes
        self.str_dict = str_dict      # (offsets, chars) or None
        self.str_char_cap = str_char_cap
        self.str_max_len = str_max_len  # longest store string
        self.is_delta = is_delta
        self.str_bound = str_bound
        self.chunks = chunks


def _decompress(codec: str, payload: bytes, uncompressed: int) -> bytes:
    if codec == "UNCOMPRESSED":
        return payload
    return pa.Codec(codec.lower()).decompress(
        payload, decompressed_size=uncompressed).to_pybytes()


def _align8(parts: List[bytes]) -> int:
    """Pad the packed accumulator to an 8-byte boundary (keeps PLAIN
    32/64-bit regions word-aligned for the 2-gather extraction) and
    return the new base offset in bytes."""
    total = sum(len(p) for p in parts)
    pad = (-total) % 8
    if pad:
        parts.append(b"\x00" * pad)
    return total + pad


# --- host-side helpers for the widened envelope ----------------------------

def _walk_plain_byte_array(data: bytes, off: int, count: int):
    """PLAIN BYTE_ARRAY page body -> (lengths int64[count], contiguous
    character bytes). The 4-byte little-endian length prefixes chain
    sequentially, so the host walks them once — ONE int read + list
    append per value, the only inherently serial work; everything else
    (start positions, the ragged character gather) derives vectorized."""
    lens_list = []
    pos = off
    end = len(data)
    for _ in range(count):
        if pos + 4 > end:
            raise HostFallback("PLAIN byte-array page truncated",
                               "truncated")
        ln = int.from_bytes(data[pos:pos + 4], "little")
        lens_list.append(ln)
        pos += 4 + ln
    if pos > end:
        raise HostFallback("PLAIN byte-array page truncated", "truncated")
    lens = np.asarray(lens_list, np.int64) if lens_list \
        else np.zeros(0, np.int64)
    total = int(lens.sum())
    if total == 0:
        return lens, b""
    # value i's data starts after i+1 length prefixes and the i
    # preceding values' characters
    arr = np.frombuffer(data, np.uint8)
    starts = off + 4 * np.arange(1, count + 1, dtype=np.int64)
    starts[1:] += np.cumsum(lens[:-1])
    out_off = np.concatenate([np.zeros(1, np.int64), np.cumsum(lens)])
    idx = np.repeat(starts - out_off[:-1], lens) \
        + np.arange(total, dtype=np.int64)
    return lens, arr[idx].tobytes()


def _delta_header(data: bytes, pos: int):
    """<block_size><miniblocks/block><total_count><first_value> — the
    DELTA_BINARY_PACKED stream preamble."""
    block_size, pos = _varint(data, pos)
    mb_per_block, pos = _varint(data, pos)
    total, pos = _varint(data, pos)
    first, pos = _zigzag(data, pos)
    if block_size <= 0 or mb_per_block <= 0 \
            or block_size % mb_per_block \
            or (block_size // mb_per_block) % 32:
        # the spec fixes values-per-miniblock at a multiple of 32; a
        # header violating it would make `cpm * w // 8` floor and
        # desynchronize every subsequent miniblock read into silently
        # wrong values
        raise HostFallback(
            f"malformed delta header ({block_size}/{mb_per_block})",
            "truncated")
    return block_size, mb_per_block, total, first, pos


def _delta_miniblocks(data: bytes, pos: int, mb: int, cpm: int,
                      total: int):
    """The ONE miniblock walk both delta consumers share: yields
    (min_delta, width, payload_byte_pos, take) per USED miniblock of a
    DELTA_BINARY_PACKED stream and returns them with the end position.
    All truncation / width-bound classification lives here so the
    numeric-chunk planner and the DELTA_LENGTH lengths decoder can
    never drift apart."""
    out = []
    remaining = total - 1
    while remaining > 0:
        if pos >= len(data):
            raise HostFallback("delta stream truncated", "truncated")
        min_d, pos = _zigzag(data, pos)
        if pos + mb > len(data):
            raise HostFallback("delta stream truncated", "truncated")
        widths = data[pos:pos + mb]
        pos += mb
        for w in widths:
            if remaining <= 0:
                break
            if w > 32:
                # funnel-shift window bound: shift(<=31) + width <= 63
                raise HostFallback(f"delta miniblock width {w}",
                                   "delta-width")
            nbytes = cpm * w // 8
            if pos + nbytes > len(data):
                raise HostFallback("delta stream truncated", "truncated")
            take = min(cpm, remaining)
            out.append((min_d, w, pos, take))
            pos += nbytes
            remaining -= take
    return out, pos


def _plan_delta_page(data: bytes, off: int, total_expected: int):
    """Walk one DELTA_BINARY_PACKED page's miniblock headers WITHOUT
    touching the packed delta payload: returns (first_value,
    [(value_start, width, min_delta, bit_off)], end_pos) where bit_off
    is relative to ``off`` — the caller appends data[off:end] to the
    packed accumulator and shifts. The device expands each miniblock
    like any bit-packed run, adds its min_delta, and prefix-sums."""
    bs, mb, total, first, pos = _delta_header(data, off)
    if total != total_expected:
        raise HostFallback(
            f"delta page count {total} != page values {total_expected}",
            "truncated")
    cpm = bs // mb  # values per miniblock (spec: multiple of 32)
    blocks, pos = _delta_miniblocks(data, pos, mb, cpm, total)
    mbs = []
    vstart = 1
    for min_d, w, bpos, take in blocks:
        mbs.append((vstart, w, min_d, (bpos - off) * 8))
        vstart += take
    return first, mbs, pos


def _decode_delta_ints(data: bytes, off: int):
    """Fully host-decode a DELTA_BINARY_PACKED int stream (the lengths
    preamble of DELTA_LENGTH_BYTE_ARRAY — the lengths gate where the
    character bytes start, so the host needs the actual values):
    returns (int64 values, end_pos). numpy unpackbits per miniblock —
    no per-value python loop."""
    bs, mb, total, first, pos = _delta_header(data, off)
    out = np.zeros(max(total, 1), np.int64)
    out[0] = first
    cpm = bs // mb
    blocks, pos = _delta_miniblocks(data, pos, mb, cpm, total)
    filled = 1
    for min_d, w, bpos, take in blocks:
        if w:
            bits = np.unpackbits(
                np.frombuffer(data, np.uint8, count=cpm * w // 8,
                              offset=bpos),
                bitorder="little")
            vals = bits.reshape(cpm, w).astype(np.int64)
            vals = (vals << np.arange(w, dtype=np.int64)).sum(1)
        else:
            vals = np.zeros(cpm, np.int64)
        out[filled:filled + take] = vals[:take] + min_d
        filled += take
    np.cumsum(out[:total], out=out[:total])
    return out[:total], pos


def chunk_envelope(col_md, descriptor, engine_dtype: dt.DataType,
                   arrow_field_type):
    """What the footer alone says of one column chunk: ``(phys,
    is_string, lane, max_def, codec)``, or HostFallback where its type,
    nesting or codec is outside the envelope. Reads no byte of the
    chunk: the scan's fetch asks it which chunks to fetch at all, and
    ``plan_chunk`` starts from it."""
    phys = col_md.physical_type
    is_string = phys == "BYTE_ARRAY" \
        and isinstance(engine_dtype, (dt.StringType, dt.BinaryType))
    lane = np.dtype(np.int32) if is_string else _PHYS_LANE.get(phys)
    if lane is None:
        raise HostFallback(f"physical type {phys}", "phys-type")
    if descriptor.max_repetition_level != 0:
        raise HostFallback("repetition levels (nested)", "nested")
    max_def = descriptor.max_definition_level
    if max_def > 1:
        raise HostFallback("definition depth > 1", "def-depth")
    codec = col_md.compression
    if codec not in _SUPPORTED_CODECS:
        raise HostFallback(f"codec {codec}", "codec")
    # bit-identity gate: the file's arrow type must equal the engine
    # dtype's arrow type, be an integer widening the device can astype
    # exactly (int8/int16 ride INT32 physically), or be the same bits
    # under a reinterpreting cast (date32 <-> int32, timestamp[us] <->
    # int64 — what the host path's _align view-casts anyway)
    def _bits_class(t):
        if pa.types.is_date32(t):
            return "i32"
        if pa.types.is_timestamp(t) and t.unit == "us" and t.tz is None:
            return "i64"
        if t == pa.int32():
            return "i32"
        if t == pa.int64():
            return "i64"
        return str(t)
    eng_arrow = dt.to_arrow(engine_dtype)
    if not is_string and arrow_field_type != eng_arrow \
            and _bits_class(arrow_field_type) != _bits_class(eng_arrow):
        both_int = pa.types.is_integer(arrow_field_type) \
            and pa.types.is_integer(eng_arrow)
        if not both_int:
            raise HostFallback(
                f"file type {arrow_field_type} vs engine {eng_arrow}",
                "phys-type")
    return phys, is_string, lane, max_def, codec


def chunk_byte_range(col_md) -> Tuple[int, int]:
    """``(start, size)`` of one column chunk's pages in its file."""
    start = col_md.data_page_offset
    if col_md.dictionary_page_offset is not None:
        start = min(start, col_md.dictionary_page_offset)
    return start, col_md.total_compressed_size


def plan_chunk(f, col_md, descriptor, engine_dtype: dt.DataType,
               arrow_field_type) -> ChunkPlan:
    """Plan one column chunk (one row group × one column) for device
    decode. `f` is an open seekable file object (or the scan's view
    over the chunk's fetched bytes); raises HostFallback anywhere
    outside the envelope."""
    phys, is_string, lane, max_def, codec = chunk_envelope(
        col_md, descriptor, engine_dtype, arrow_field_type)
    n_rows = col_md.num_values
    start, size = chunk_byte_range(col_md)
    f.seek(start)
    buf = f.read(size)

    dictionary: Optional[np.ndarray] = None
    # string store: dictionary-page values first, then PLAIN /
    # DELTA_LENGTH page values in page order (identity runs index the
    # page's own slice)
    sd_lens: List[np.ndarray] = []
    sd_chars: List[bytes] = []
    sd_count = 0
    n_dict = 0                      # store entries from the dict page
    dict_rows = 0                   # rows decoded via dictionary runs
    ident_chars = 0                 # chars reachable via identity runs
    packed_parts: List[bytes] = []
    runs: List[tuple] = []          # (value_row, meta, value, bit)
    def_packed_parts: List[bytes] = []
    def_runs: List[tuple] = []
    values_seen = 0                 # dense (non-null) value-stream rows
    rows_seen = 0
    has_delta = has_nondelta = False
    pos = 0
    while rows_seen < n_rows:
        if pos >= len(buf):
            raise HostFallback("page walk ran past chunk bytes",
                               "truncated")
        hdr = parse_page_header(buf, pos)
        payload_start = pos + hdr["header_len"]
        payload = buf[payload_start: payload_start + hdr["compressed"]]
        pos = payload_start + hdr["compressed"]
        if hdr["type"] == _PAGE_DICT:
            dh = hdr["dict_hdr"] or {}
            if dh.get(2, _ENC_PLAIN) not in (_ENC_PLAIN, _ENC_PLAIN_DICT):
                raise HostFallback("non-PLAIN dictionary page",
                                   "encoding")
            data = _decompress(codec, payload, hdr["uncompressed"])
            if phys == "BOOLEAN":
                raise HostFallback("boolean dictionary", "encoding")
            if is_string:
                if sd_count:
                    raise HostFallback("dictionary page after values",
                                       "page")
                d_lens, d_chars = _parse_byte_array_dict(data,
                                                         dh.get(1, 0))
                sd_lens.append(d_lens)
                sd_chars.append(d_chars)
                sd_count = n_dict = d_lens.shape[0]
            else:
                dictionary = np.frombuffer(data, lane, count=dh.get(1, 0))
            continue
        if hdr["type"] == _PAGE_INDEX:
            continue
        if hdr["type"] == _PAGE_DATA:
            dph = hdr["data_hdr"] or {}
            num_values = dph.get(1, 0)
            enc = dph.get(2)
            data = _decompress(codec, payload, hdr["uncompressed"])
            off = 0
            page_valid = num_values
            if max_def > 0:
                if dph.get(3) != _ENC_RLE:
                    raise HostFallback("non-RLE definition levels",
                                       "encoding")
                (dl,) = struct.unpack_from("<i", data, 0)
                base_bits = _align8(def_packed_parts) * 8
                page_def, _ = _parse_runs(data, 4, 4 + dl, 1, num_values,
                                          base_bits)
                page_def = [(r + rows_seen, k, v, b)
                            for r, k, v, b in page_def]
                def_packed_parts.append(data[4:4 + dl])
                page_valid = _popcount_valid(
                    [(r - rows_seen, k, v, b - base_bits)
                     for r, k, v, b in page_def],
                    data[4:4 + dl], 0, num_values)
                def_runs.extend(page_def)
                off = 4 + dl
        elif hdr["type"] == _PAGE_DATA_V2:
            # v2 pages: rep/def level regions ride UNCOMPRESSED before
            # the (optionally compressed) data region, levels carry no
            # 4-byte length prefix, and the null count is in the header
            h2 = hdr["v2_hdr"] or {}
            num_values = h2.get(1, 0)
            num_nulls = h2.get(2, 0)
            enc = h2.get(4)
            def_len = h2.get(5, 0)
            rep_len = h2.get(6, 0)
            if rep_len:
                raise HostFallback("v2 repetition levels (nested)",
                                   "nested")
            body = payload[def_len:]
            if h2.get(7, True) and codec != "UNCOMPRESSED":
                body = _decompress(codec, body,
                                   hdr["uncompressed"] - def_len)
            page_valid = num_values - num_nulls
            if max_def > 0 and def_len:
                def_bytes = bytes(payload[:def_len])
                base_bits = _align8(def_packed_parts) * 8
                page_def, _ = _parse_runs(def_bytes, 0, def_len, 1,
                                          num_values, base_bits)
                def_runs.extend((r + rows_seen, k, v, b)
                                for r, k, v, b in page_def)
                def_packed_parts.append(def_bytes)
            elif num_nulls:
                raise HostFallback("v2 nulls without definition levels",
                                   "page")
            elif max_def > 0:
                # level region elided for an all-valid page: a previous
                # page's trailing run must not govern these rows
                def_runs.append((rows_seen, True, 1, 0))
            data = bytes(body)
            off = 0
        else:
            raise HostFallback("unknown page type", "page")

        # --- shared per-encoding dispatch (v1 and v2 pages) ------------
        if enc in (_ENC_RLE_DICT, _ENC_PLAIN_DICT) \
                and (dictionary is not None or n_dict):
            has_nondelta = True
            width = data[off]
            if width > _MAX_DICT_WIDTH:
                raise HostFallback(f"dict index width {width}",
                                   "dict-width")
            # string chunks: the INDEX stream is the decoded value
            # (no _META_DICT -> the kernel returns raw indices; the
            # device gathers strings from the uploaded store)
            dmeta = 0 if is_string else _META_DICT
            dict_rows += page_valid
            base_bits = _align8(packed_parts) * 8
            if width == 0:
                # every value is dictionary[0]
                runs.append((values_seen, 1 | _META_RLE | dmeta, 0, 0))
            else:
                pruns, stream_end = _parse_runs(data, off + 1, len(data),
                                                width, page_valid,
                                                base_bits)
                packed_parts.append(data[off + 1: stream_end])
                runs.extend(
                    (r + values_seen,
                     (width | _META_RLE | dmeta) if k
                     else (width | dmeta), v, b)
                    for r, k, v, b in pruns)
        elif enc == _ENC_PLAIN and is_string:
            # host walks the length prefixes once into the store; the
            # device gathers the characters via an identity index run
            has_nondelta = True
            lens, chars = _walk_plain_byte_array(data, off, page_valid)
            runs.append((values_seen, _META_IDENT, sd_count, 0))
            sd_lens.append(lens)
            sd_chars.append(chars)
            sd_count += page_valid
            ident_chars += len(chars)
        elif enc == _ENC_PLAIN:
            has_nondelta = True
            base = _align8(packed_parts)
            if phys == "BOOLEAN":
                nbytes = (page_valid + 7) // 8
                packed_parts.append(data[off: off + nbytes])
                runs.append((values_seen, 1, 0, base * 8))
            else:
                w = lane.itemsize * 8
                packed_parts.append(
                    data[off: off + page_valid * lane.itemsize])
                runs.append((values_seen, w, 0, base * 8))
        elif enc == _ENC_RLE and phys == "BOOLEAN":
            # v2 boolean values: RLE/bit-packed hybrid with an i32
            # byte-length prefix (same stream shape as def levels)
            has_nondelta = True
            (bl,) = struct.unpack_from("<i", data, off)
            base_bits = _align8(packed_parts) * 8
            pruns, _ = _parse_runs(data, off + 4, off + 4 + bl, 1,
                                   page_valid, base_bits)
            packed_parts.append(data[off + 4: off + 4 + bl])
            runs.extend((r + values_seen,
                         (1 | _META_RLE) if k else 1, v, b)
                        for r, k, v, b in pruns)
        elif enc == _ENC_DELTA_BINARY_PACKED \
                and phys in ("INT32", "INT64"):
            # miniblock headers -> bit-packed delta runs; the device
            # prefix-sums from each page's first-value run
            has_delta = True
            first, mbs, _ = _plan_delta_page(data, off, page_valid)
            if page_valid:  # a 0-value page must not emit a phantom
                base_bits = _align8(packed_parts) * 8  # first-value run
                runs.append((values_seen, _META_RLE, first, 0))
                runs.extend((values_seen + vs, w | _META_DELTA, md,
                             base_bits + bo)
                            for vs, w, md, bo in mbs)
                packed_parts.append(data[off:])
        elif enc == _ENC_DELTA_LENGTH_BA and is_string:
            # lengths are a host-decoded delta stream (they gate where
            # the character bytes start); characters ride the store
            has_nondelta = True
            lens, cpos = _decode_delta_ints(data, off)
            if lens.shape[0] != page_valid:
                raise HostFallback(
                    f"delta-length count {lens.shape[0]} != "
                    f"{page_valid}", "truncated")
            total = int(lens.sum()) if lens.size else 0
            if cpos + total > len(data):
                # a short slice would silently gather padding as string
                # content — classify, never truncate quietly
                raise HostFallback("delta-length characters truncated",
                                   "truncated")
            runs.append((values_seen, _META_IDENT, sd_count, 0))
            sd_lens.append(lens)
            sd_chars.append(bytes(data[cpos:cpos + total]))
            sd_count += page_valid
            ident_chars += total
        else:
            raise HostFallback(f"encoding {enc}", "encoding")
        values_seen += page_valid
        rows_seen += num_values

    if has_delta and has_nondelta:
        # the prefix-sum reconstruction treats every RLE run as a page
        # restart; a chunk mixing delta pages with other encodings
        # cannot ride it
        raise HostFallback("mixed DELTA/non-DELTA data pages",
                           "encoding")

    packed = b"".join(packed_parts)
    def_packed = b"".join(def_packed_parts)
    words = _as_words(packed)
    if not packed_words_fit(words.shape[0]):
        raise HostFallback(f"packed stream of {len(packed)}B: bit "
                           "positions past int32", "size-guard")
    run_tab = np.zeros((max(len(runs), 1), 4), np.int64)
    for i, r in enumerate(runs):
        run_tab[i] = r
    if not runs:
        run_tab[0] = (0, 1 | _META_RLE, 0, 0)
    def_tab = np.zeros((max(len(def_runs), 1), 4), np.int64)
    for i, (row, is_rle, value, bit) in enumerate(def_runs):
        def_tab[i] = (row, 1 | (int(is_rle) << 8), value, bit)
    if not def_runs:
        def_tab[0] = (0, 1 | _META_RLE, 1, 0)  # all-valid constant run
    encoded = (len(packed) + len(def_packed) + run_tab.nbytes
               + def_tab.nbytes
               + (dictionary.nbytes if dictionary is not None else 0))
    str_dict = None
    str_char_cap = 0
    str_max_len = 0
    str_bound = 0
    if is_string:
        if not sd_lens and values_seen:
            raise HostFallback("string chunk without dictionary",
                               "encoding")
        lens = np.concatenate(sd_lens) if sd_lens \
            else np.zeros(0, np.int64)
        offs = np.zeros(lens.shape[0] + 1, np.int64)
        np.cumsum(lens, out=offs[1:])
        if offs[-1] > np.iinfo(np.int32).max:
            raise HostFallback("string store over int32 offsets",
                               "string-cap")
        chars = np.frombuffer(b"".join(sd_chars) + b"\x00" * 8, np.uint8)
        str_dict = (offs.astype(np.int32), chars)
        str_max_len = int(lens.max()) if lens.size else 0
        d_max = int(lens[:n_dict].max()) if n_dict else 0
        # worst-case decoded characters: dictionary runs can repeat the
        # longest dictionary entry per row; identity runs emit each
        # page value at most once
        str_bound = dict_rows * max(d_max, 1) + ident_chars
        str_bound = max(str_bound, 16)
        if str_bound > STR_EXPANSION_CAP:
            raise HostFallback(
                f"string expansion bound {str_bound}B over the device "
                "cap", "string-cap")
        encoded += offs.nbytes // 2 + chars.nbytes  # int32 on device
        str_char_cap = bucket_bytes(str_bound)
    else:
        # no-win guard: the host-decode path uploads bucket_rows(n)×lane
        # data + a bool validity lane — but it ALSO pays the pyarrow
        # host decode and rides the per-column arrow upload instead of
        # the fused blob, so parity-sized encoded forms still win on
        # device; only a substantially bigger encoded form (pathological
        # dictionaries: near-unique values dict-encoded) is a real loss
        host_upload = bucket_rows(n_rows) * (lane.itemsize + 1)
        if encoded * 2 > host_upload * 3:
            raise HostFallback(
                f"encoded {encoded}B > 1.5x host upload {host_upload}B",
                "size-guard")
    return ChunkPlan(n_rows, lane,
                     dictionary if dictionary is not None
                     else np.zeros(1, lane),
                     words, run_tab,
                     _as_words(def_packed), def_tab, values_seen, encoded,
                     str_dict=str_dict, str_char_cap=str_char_cap,
                     str_max_len=str_max_len, is_delta=has_delta,
                     str_bound=str_bound)


def _parse_byte_array_dict(data: bytes, count: int):
    """PLAIN BYTE_ARRAY dictionary page -> (lengths int64[count],
    contiguous character bytes) — the string-store shape plan_chunk
    accumulates page values into."""
    return _walk_plain_byte_array(data, 0, count)


def _as_words(b: bytes) -> np.ndarray:
    """uint32 word view of the byte stream, padded so widx+1 is always
    in bounds for the funnel-shift gather."""
    pad = (-len(b)) % 4
    arr = np.frombuffer(b + b"\x00" * (pad + 8), np.uint32)
    return arr


def encoded_nbytes(plan: ChunkPlan) -> int:
    return plan.encoded_bytes


def null_free_chunks(plans) -> int:
    """Column chunks among these (possibly merged) ChunkPlans that
    decode without a definition-level pass: the ``nullFreeChunks``
    counter and the ``null_free`` argument of ``scan.dispatch``."""
    return sum(plan.chunks for plan in plans if not plan.has_nulls)


def packed_words_fit(n_words: int) -> bool:
    """May a packed stream of this many words ride the device decode?
    Its arena slice (guard words, bucketed) must stay under the int32
    bit positions of ``_expand``; plan_chunk sends a larger chunk to
    the host (`size-guard`) and the coalescer (io/scan.py) does not
    merge row groups past it."""
    return _seg_bucket(n_words + 2) < _MAX_PACKED_WORDS


def merge_chunk_plans(plans: Sequence[ChunkPlan]) -> ChunkPlan:
    """Concatenate consecutive row groups' plans for ONE column into a
    single plan, so small row groups coalesce into one fused-decode
    dispatch instead of one program + transfer each.

    Streams concatenate 8-byte aligned; run tables shift their dense
    row starts and absolute bit offsets; dictionaries concatenate, and
    every dictionary-index run (numeric ``is_dict`` runs, every value
    run of a string chunk) records its group's index base in meta bits
    16+ so indices keep pointing at their OWN row group's slice of the
    merged dictionary — heterogeneous dictionaries merge without
    re-encoding any payload bytes."""
    if len(plans) == 1:
        return plans[0]
    p0 = plans[0]
    lane = p0.lane
    is_string = p0.str_dict is not None
    is_delta = p0.is_delta
    words_parts: List[np.ndarray] = []
    def_parts: List[np.ndarray] = []
    run_tabs: List[np.ndarray] = []
    def_tabs: List[np.ndarray] = []
    dict_parts: List[np.ndarray] = []
    offs_parts: List[np.ndarray] = []
    chars_parts: List[bytes] = []
    w_words = dw_words = 0
    dense_base = row_base = dict_base = char_base = 0
    n_rows = n_valid = encoded = chunks = 0
    str_max_len = 0
    str_bound = 0
    for p in plans:
        if p.lane != lane or (p.str_dict is None) != (not is_string) \
                or p.is_delta != is_delta:
            raise ValueError("merge_chunk_plans: incompatible plans")
        if w_words % 2:  # keep every stream 8-byte aligned (PLAIN w=64)
            words_parts.append(np.zeros(1, np.uint32))
            w_words += 1
        if dw_words % 2:
            def_parts.append(np.zeros(1, np.uint32))
            dw_words += 1
        rt = p.runs.copy()
        rt[:, 0] += dense_base
        rt[:, 3] += w_words * 32
        if dict_base:
            if is_string:
                idx_runs = np.ones(rt.shape[0], bool)
            else:
                idx_runs = ((rt[:, 1] >> 9) & 1) == 1
            rt[idx_runs, 1] += np.int64(dict_base) << 16
        run_tabs.append(rt)
        dtab = p.def_runs.copy()
        dtab[:, 0] += row_base
        dtab[:, 3] += dw_words * 32
        def_tabs.append(dtab)
        words_parts.append(p.packed)
        w_words += p.packed.shape[0]
        def_parts.append(p.def_packed)
        dw_words += p.def_packed.shape[0]
        if is_string:
            offs, chars = p.str_dict
            nd = offs.shape[0] - 1
            o64 = offs.astype(np.int64) + char_base
            offs_parts.append(o64 if not offs_parts else o64[1:])
            real = int(offs[-1]) if offs.size else 0
            chars_parts.append(chars[:real].tobytes())
            char_base += real
            dict_base += nd
        else:
            dict_parts.append(p.dictionary)
            dict_base += p.dictionary.shape[0]
        dense_base += p.n_valid
        row_base += p.n_rows
        n_rows += p.n_rows
        n_valid += p.n_valid
        encoded += p.encoded_bytes
        chunks += p.chunks
        str_max_len = max(str_max_len, p.str_max_len)
        str_bound += p.str_bound
    str_dict = None
    str_char_cap = 0
    if is_string:
        # each group's rows only reach its own slice of the merged
        # store, so the merged worst case is the SUM of per-group
        # bounds — tight for identity (PLAIN/DELTA_LENGTH) groups too
        if str_bound > STR_EXPANSION_CAP:  # the coalescer prechecks this
            raise HostFallback(
                f"merged string expansion bound {str_bound}B over the "
                "cap", "string-cap")
        if char_base > np.iinfo(np.int32).max:  # coalescer-prechecked
            raise HostFallback(
                "merged string store over int32 offsets", "string-cap")
        offs64 = np.concatenate(offs_parts)
        str_dict = (offs64.astype(np.int32),
                    np.frombuffer(b"".join(chars_parts) + b"\x00" * 8,
                                  np.uint8))
        str_char_cap = bucket_bytes(max(str_bound, 16))
        dictionary = np.zeros(1, lane)
    else:
        dictionary = np.concatenate(dict_parts)
    merged = ChunkPlan(n_rows, lane, dictionary,
                       np.concatenate(words_parts),
                       np.concatenate(run_tabs),
                       np.concatenate(def_parts),
                       np.concatenate(def_tabs),
                       n_valid, encoded, str_dict=str_dict,
                       str_char_cap=str_char_cap, str_max_len=str_max_len,
                       is_delta=is_delta, str_bound=str_bound,
                       chunks=chunks)
    # a part the scan holds to the definition-level pass holds the whole
    merged.has_nulls = any(p.has_nulls for p in plans)
    return merged


# --- device kernel ---------------------------------------------------------

def _run_ids(starts, cap: int, t_n: int):
    """The run of a sorted run table that covers each dense position
    0..cap-1: a prefix count of run-start flags, not a search per row
    (``ops.gather.dense_run_counts``)."""
    import jax.numpy as jnp
    from ..ops.gather import dense_run_counts
    return jnp.clip(dense_run_counts(starts, cap) - 1, 0, t_n - 1)


def _expand(words, tab, cap: int, delta: bool = False, wide: bool = True):
    """Expand the run table at the dense positions 0..cap-1: uint64 raw
    bits + the is_dict lane for the caller's interpretation. Only the
    packed words are gathered per row: what a run holds (width, flags,
    where its bits start, its literal) is constant over the run's
    positions, so it is folded into four int32 per-run constants and
    EXPANDED by the scatter + prefix that finds the run
    (``ops.gather.dense_run_expand``), never gathered by a run id.
    ``wide`` (static) says a run may be 64 bits wide — PLAIN values of
    an 8-byte lane, the only ones that need a third word.
    With ``delta`` (static), the expanded lanes are per-value DELTA
    contributions (bit-packed delta + the run's min_delta; a page's
    first value rides an RLE run) and the return value is the
    prefix-sum reconstruction, restarted at every RLE run — each page
    is its own delta stream."""
    import jax.numpy as jnp
    from jax import lax
    from ..ops.gather import dense_run_expand
    if words.shape[0] >= _MAX_PACKED_WORDS:
        # a bit position is an int32 lane (plan_chunk and the coalescer
        # send larger chunks to the host before they get here)
        raise ValueError(f"packed stream of {words.shape[0]} words")
    idx = jnp.arange(cap, dtype=jnp.int32)
    starts, meta, raw = tab[:, 0], tab[:, 1], tab[:, 2]
    run_rle = (meta >> 8) & 1
    run_ident = (meta >> 10) & 1
    run_delta = (meta >> 11) & 1
    # bit position of dense position i: bit0 + i * width
    bit0 = tab[:, 3] - starts * (meta & 0xFF)
    # what a position adds to its unpacked bits: the run's literal
    # (RLE), its min_delta (DELTA miniblocks), or for identity runs
    # (PLAIN / DELTA_LENGTH strings: value_i = literal + i - start) the
    # literal less the run's start; and the merged row group's index
    # base (meta bits 16+: 0 for PLAIN runs and unmerged plans), so a
    # dictionary index points into its own group's slice of the
    # concatenated dictionary/store. int64 wraparound ==
    # two's-complement addition, as the uint64 lanes below
    addend = jnp.where((run_rle | run_ident | run_delta) == 1, raw, 0) \
        - jnp.where(run_ident == 1, starts, 0) + (meta >> 16)
    fields = [meta & 0xFFF, bit0, addend, addend >> 32]
    if delta:
        # the dense position of each run's page start (its last RLE run)
        fields.append(lax.cummax(
            jnp.where(run_rle == 1, starts, jnp.int64(-1))))
    lanes = dense_run_expand(
        starts, jnp.stack([_low32(f) for f in fields]), cap)
    meta, bit0 = lanes[0], lanes[1]
    addend = (_u64(lanes[3]) << jnp.uint64(32)) | _u64(lanes[2])
    literal = ((meta >> 8) | (meta >> 10)) & 1   # RLE or identity run
    is_dict = (meta >> 9) & 1
    is_ident = (meta >> 10) & 1
    bitpos = bit0 + idx * (meta & 0xFF)
    width = (meta & 0xFF).astype(jnp.uint64)
    widx = jnp.clip(bitpos >> 5, 0, words.shape[0] - 2)
    lo = words[widx].astype(jnp.uint64)
    hi = words[widx + 1].astype(jnp.uint64)
    sh = (bitpos & 31).astype(jnp.uint64)
    window = (hi << jnp.uint64(32)) | lo
    mask = jnp.where(width >= 64, jnp.uint64(0xFFFFFFFFFFFFFFFF),
                     (jnp.uint64(1) << width) - jnp.uint64(1))
    bits = (window >> sh) & mask
    if wide:
        # w == 64 PLAIN regions are 8-byte aligned (sh is 0 mod 32): the
        # 64-bit window IS the value, but sh==32 can occur when the
        # region starts on an odd word — handle by re-gathering the next
        # word pair
        hi2 = words[jnp.clip(widx + 2, 0, words.shape[0] - 1)] \
            .astype(jnp.uint64)
        full64 = jnp.where(sh == 0, window, (hi2 << jnp.uint64(32)) | hi)
        bits = jnp.where(width >= 64, full64, bits)
    bits = jnp.where(literal == 1, jnp.uint64(0), bits) \
        + jnp.where(is_ident == 1, idx, 0).astype(jnp.uint64) + addend
    if delta:
        # value_i = page_first + Σ deltas: inclusive prefix sum minus
        # the sum just before the page's first-value (RLE) run
        page_start = lanes[4]
        csum = jnp.cumsum(bits)
        before = csum[jnp.clip(page_start - 1, 0, idx.shape[0] - 1)]
        bits = csum - jnp.where(page_start > 0, before, jnp.uint64(0))
    return bits, is_dict


def _low32(x):
    """The low 32 bits of an int64 lane as int32."""
    import jax.numpy as jnp
    from jax import lax
    return lax.bitcast_convert_type(
        (x & 0xFFFFFFFF).astype(jnp.uint32), jnp.int32)


def _u64(x):
    """An int32 lane's 32 bits as the low half of a uint64."""
    import jax.numpy as jnp
    from jax import lax
    return lax.bitcast_convert_type(x, jnp.uint32).astype(jnp.uint64)


def _decode_device(words, tab, dict_arr, def_words, def_tab, n_rows,
                   cap: int, delta: bool = False, has_nulls: bool = True):
    """The whole chunk decode as one jittable program: returns
    (values[cap] in the DICTIONARY/lane dtype, validity[cap]). A chunk
    without nulls (``has_nulls`` false, static: ``ChunkPlan.has_nulls``)
    runs no definition-level pass: every row below ``n_rows`` is valid
    and the value stream is already dense over the rows."""
    import jax.numpy as jnp
    from jax import lax
    from ..ops.gather import blocked_int_cumsum
    i = jnp.arange(cap, dtype=jnp.int64)
    valid = i < n_rows
    if has_nulls:
        def_bits, _ = _expand(def_words, def_tab, cap, wide=False)
        valid = valid & ((def_bits & jnp.uint64(1)) != 0)
    lane = dict_arr.dtype
    bits, is_dict = _expand(words, tab, cap, delta=delta,
                            wide=lane.itemsize == 8)
    if lane == jnp.bool_:
        vals = (bits & jnp.uint64(1)) != 0
    elif lane.itemsize == 8:
        vals = lax.bitcast_convert_type(bits, lane)
    else:
        vals = lax.bitcast_convert_type(bits.astype(jnp.uint32), lane)
    dgot = dict_arr[jnp.clip(bits.astype(jnp.int32), 0,
                             dict_arr.shape[0] - 1)]
    vals = jnp.where(is_dict == 1, dgot, vals)
    if has_nulls:
        # values are dense over valid rows — gather back to rows by the
        # dense index of each valid row into the value stream
        didx = blocked_int_cumsum(valid) - 1
        vals = vals[jnp.clip(didx, 0, cap - 1)]
    out = jnp.where(valid, vals, jnp.zeros((), lane))
    return out, valid


_JIT_CACHE: Dict[tuple, object] = {}
_JIT_LOCK = threading.Lock()
_STAGING = threading.local()


def _await_staging_arena(clock: StageClock) -> None:
    """Before this thread's staging arena is written again, wait for
    the PREVIOUS decode dispatched from this thread — its outputs being
    ready proves the program (and therefore the async host->device copy
    feeding it) consumed the buffer; blocking only on the ``device_put``
    result is NOT enough on backends that defer the copy into the
    consuming computation. The wait is a stage of its own
    (``arena_wait``): it is the device being busy, not a transfer."""
    import jax
    pending = getattr(_STAGING, "pending", None)
    if pending is not None:
        with clock.stage("arena_wait"):
            jax.block_until_ready(pending)
        _STAGING.pending = None


def _staging_arena(n_words: int) -> np.ndarray:
    """Pooled per-thread host staging arena for the fused-decode blob:
    segments are written in place instead of a fresh ``np.concatenate``
    per row group (``_await_staging_arena`` first)."""
    buf = getattr(_STAGING, "buf", None)
    if buf is None or buf.shape[0] < n_words:
        buf = np.zeros(max(n_words, 1 << 12), np.uint32)
        _STAGING.buf = buf
    return buf


def _seg_bucket(n: int) -> int:
    """Bucketed (and even, for 8-byte alignment) arena segment length:
    the quantization that makes blob offsets — and therefore the fused
    program's JIT cache key — collapse across heterogeneous row
    groups (columnar.batch.bucket_fine_even — shared so every arena
    user quantizes identically)."""
    return bucket_fine_even(n)


def decode_chunk_device(plan: ChunkPlan, engine_dtype: dt.DataType,
                        capacity: int) -> TpuColumnVector:
    """Single-chunk decode (test/utility entry): delegates to the fused
    row-group path with one column."""
    out = decode_row_group_device({"c": (plan, engine_dtype)}, capacity)
    return out["c"]


def _lane_of(name: str):
    return np.dtype(name)


def decode_row_group_device(plans: Dict[str, Tuple[ChunkPlan, dt.DataType]],
                            capacity: int,
                            clock: Optional[StageClock] = None,
                            mm=None, chain=None, chain_key=None,
                            schema: Optional[dt.Schema] = None,
                            extra_cols=None, row_count=None,
                            ectx=None, donate: bool = False):
    """Decode every device-eligible chunk of a row group with ONE
    host->device transfer and ONE program dispatch: all encoded segments
    (packed streams, run tables, dictionaries, def levels) concatenate
    into a single uint32 blob; the fused program slices it statically
    per column. The per-transfer and per-dispatch cost (about 0.2 ms
    of host time per dispatch on the v5e, chip run of PR 21) is paid
    once per row group instead of ~5x per column.

    The arena layout is QUANTIZED: every segment lands at a bucketed
    offset with a bucketed length (``_seg_bucket``) and the per-group
    row count rides as a traced scalar, so the JIT cache key collapses
    across heterogeneous row groups of one schema instead of compiling
    a fresh program (44 s for q6's at SF1 on the v5e's compiler, PR 21)
    per distinct raw offset tuple. Segments are written into a pooled per-thread host
    staging arena rather than np.concatenate'd fresh per group.

    ``clock`` (``obs.tracer.StageClock``) times the stages where they
    happen, one span and one clock pair each: ``assemble`` (segments
    and spec, then the arena fill), ``arena_wait`` between them,
    ``upload`` (the blob's ``device_put``; ``bytes`` is its ``nbytes``)
    and ``dispatch`` (the call of the jitted program, which on a cold
    run holds its compilation). The scan folds ``clock.seconds`` into
    its counters. ``mm`` (optional
    DeviceMemoryManager) takes a transient ledger reservation for the
    encoded blob while the upload + dispatch are in flight, so the
    staging bytes the widened envelope ships (string stores, delta
    streams) are visible to eviction pressure and the HBM timeline.

    **Composable epilogue (scan-rooted whole-stage fusion).** With
    ``chain`` (a tuple of pure ``(TpuBatch, EvalCtx) -> pytree``
    callables — the downstream filter/project/partial-agg device_fn
    chain plus the consumer's tail), the fused program additionally
    assembles the decoded columns — together with ``extra_cols``
    (already-device-resident host-fallback / partition / null columns)
    — into a ``TpuBatch`` over ``schema`` with traced ``row_count``,
    and applies the chain INSIDE the same XLA program: decode ->
    filter -> project -> partial-agg is ONE dispatch with no
    full-batch HBM materialization in between, and the return value is
    the chain's output pytree instead of the column dict. The JIT
    cache is keyed on the quantized arena key x ``chain_key`` (the
    chain's content key from ``exec.base.fn_content_key``), so
    heterogeneous row groups of one schema x one chain stay at a
    handful of compiled variants. ``donate`` donates the staged blob
    (and the chain's extra columns) into the program — XLA reuses
    their HBM for outputs instead of holding both live (skip on the
    CPU backend, where donation is unimplemented)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    clock = clock or StageClock()
    segs: List[Tuple[np.ndarray, int]] = []  # (u32 array, word offset)
    off = 0

    def add(arr_u32: np.ndarray, guard: int = 0) -> Tuple[int, int]:
        nonlocal off
        start = off
        blen = _seg_bucket(arr_u32.shape[0] + guard)
        segs.append((arr_u32, start))
        off += blen
        return start, blen

    with clock.stage("assemble"):  # segments and the program's spec
        spec = []
        names = []
        nrs = []
        for name, (plan, eng_dtype) in plans.items():
            lane = plan.lane
            # +2 guard words inside the bucketed slice: the funnel-shift
            # gather reads widx+1 (and +2 for w=64 at sh==32)
            w_off, w_len = add(plan.packed, guard=2)
            t = _pad_rows(plan.runs)
            t_off, _ = add(np.ascontiguousarray(t).view(np.uint32)
                           .reshape(-1))
            dw_off, dw_len = add(plan.def_packed, guard=2)
            dtab = _pad_rows(plan.def_runs)
            dt_off, _ = add(np.ascontiguousarray(dtab).view(np.uint32)
                            .reshape(-1))
            d = _pad_pow2(plan.dictionary)
            d_u32 = np.ascontiguousarray(d).view(np.uint32).reshape(-1) \
                if d.dtype != np.bool_ else np.zeros(2, np.uint32)
            dict_off, _ = add(d_u32)
            if plan.str_dict is not None:
                s_offs, s_chars = plan.str_dict
                so = _pad_pow2(s_offs)
                so_off, _ = add(np.ascontiguousarray(so).view(np.uint32))
                sc_off, _ = add(_as_words(s_chars.tobytes()))
                str_info = (so_off, so.shape[0], sc_off, plan.str_char_cap)
            else:
                str_info = None
            names.append(name)
            nrs.append(plan.n_rows)
            spec.append((str(lane), str(np.dtype(eng_dtype.np_dtype))
                         if eng_dtype.np_dtype is not None else "str",
                         w_off, w_len, t_off, t.shape[0],
                         dw_off, dw_len, dt_off, dtab.shape[0],
                         dict_off, d.shape[0], str_info, plan.is_delta,
                         bool(plan.has_nulls)))
        total = _seg_bucket(off + 4)  # trailing slice-overrun guard
    _await_staging_arena(clock)
    with clock.stage("assemble", rows=max(nrs, default=0),
                     bytes=total * 4):
        buf = _staging_arena(total)
        for arr, start in segs:
            buf[start:start + arr.shape[0]] = arr
        view = buf[:total]
    cap = capacity
    eng_dtypes = [plans[n][1] for n in names]
    if chain is not None:
        schema_sig = tuple((f.name, f.dtype.simple_string(), f.nullable)
                           for f in schema.fields)
        extra_names = tuple(extra_cols) if extra_cols else ()
        key = ("rgc", cap, total, tuple(spec), chain_key, extra_names,
               schema_sig, bool(donate))
    else:
        key = ("rg", cap, total, tuple(spec), bool(donate))
    program = "scan_decode_chain" if chain is not None else "scan_decode"
    with _JIT_LOCK:  # one compile per key even across feeder threads
        fn = _JIT_CACHE.get(key)
        if fn is None:
            def decode_cols(b, nr):
                outs = []
                for j, (lane_s, eng_s, w_off, w_len, t_off, t_n, dw_off,
                        dw_len, dt_off, dt_n, d_off, d_n,
                        str_info, is_delta, has_nulls) in enumerate(spec):
                    lane = np.dtype(lane_s)
                    words = b[w_off: w_off + w_len]
                    tab = lax.bitcast_convert_type(
                        b[t_off: t_off + t_n * 8].reshape(t_n, 4, 2),
                        jnp.int64)
                    def_words = b[dw_off: dw_off + dw_len]
                    def_tab = lax.bitcast_convert_type(
                        b[dt_off: dt_off + dt_n * 8].reshape(dt_n, 4, 2),
                        jnp.int64)
                    if lane == np.bool_:
                        dict_arr = jnp.zeros(1, jnp.bool_)
                    elif lane.itemsize == 8:
                        dict_arr = lax.bitcast_convert_type(
                            b[d_off: d_off + d_n * 2].reshape(d_n, 2),
                            jnp.dtype(lane))
                    else:
                        dict_arr = lax.bitcast_convert_type(
                            b[d_off: d_off + d_n], jnp.dtype(lane))
                    vals, valid = _decode_device(
                        words, tab, dict_arr, def_words, def_tab,
                        nr[j], cap, delta=is_delta, has_nulls=has_nulls)
                    if str_info is not None:
                        so_off, so_n, sc_off, char_cap = str_info
                        d_offs = lax.bitcast_convert_type(
                            b[so_off: so_off + so_n], jnp.int32)
                        idx = jnp.clip(vals.astype(jnp.int32), 0,
                                       max(so_n - 2, 0))
                        lens = d_offs[idx + 1] - d_offs[idx]
                        ll = jnp.where(valid, lens, 0)
                        offsets = jnp.concatenate(
                            [jnp.zeros((1,), jnp.int32),
                             jnp.cumsum(ll).astype(jnp.int32)])
                        k = jnp.arange(char_cap, dtype=jnp.int32)
                        row = _run_ids(offsets, char_cap, cap)
                        src = d_offs[idx[row]] + (k - offsets[:-1][row])
                        word = b[jnp.clip(sc_off + (src >> 2), 0,
                                          b.shape[0] - 1)]
                        byte = ((word >> ((src & 3) * 8))
                                & jnp.uint32(0xFF)).astype(jnp.uint8)
                        chars = jnp.where(k < offsets[-1], byte,
                                          jnp.uint8(0))
                        outs.append((offsets, chars, valid))
                        continue
                    if vals.dtype != np.dtype(eng_s):
                        vals = vals.astype(np.dtype(eng_s))
                    outs.append((vals, valid))
                return tuple(outs)

            def decoded_vectors(b, nr):
                """Decoded columns as TpuColumnVectors, by name."""
                cols = {}
                for name_, eng_dtype, out in zip(
                        names, eng_dtypes, decode_cols(b, nr)):
                    if len(out) == 3:
                        offsets, chars, valid = out
                        cols[name_] = TpuColumnVector(
                            eng_dtype, validity=valid, offsets=offsets,
                            chars=chars)
                    else:
                        vals, valid = out
                        cols[name_] = TpuColumnVector(
                            eng_dtype, data=vals, validity=valid)
                return cols

            if chain is not None:
                chain_fns = tuple(chain)
                out_schema = schema
                enames = tuple(extra_cols) if extra_cols else ()

                def build(b, nr, rc, extra, e):
                    from ..columnar.batch import TpuBatch
                    cols = decoded_vectors(b, nr)
                    cols.update(zip(enames, extra))
                    batch = TpuBatch(
                        [cols[f.name] for f in out_schema.fields],
                        out_schema, rc)
                    for f in chain_fns:
                        batch = f(batch, e)
                    return batch
                fn = named_jit(program, build, static_argnums=4,
                               donate_argnums=(0, 3) if donate else ())
            else:
                def build(b, nr):
                    return tuple(decode_cols(b, nr))
                fn = named_jit(program, build,
                               donate_argnums=(0,) if donate else ())
            _JIT_CACHE[key] = fn
    import contextlib
    charge = mm.transient_reservation(view.nbytes) if mm is not None \
        and hasattr(mm, "transient_reservation") else contextlib.nullcontext()
    with charge:
        with clock.stage("upload", bytes=view.nbytes):
            blob = jax.device_put(view)
            nr_dev = jnp.asarray(np.asarray(nrs, np.int64))
        with clock.stage("dispatch", program=module_name(program),
                         fused=chain is not None,
                         null_free=null_free_chunks(
                             plan for plan, _ in plans.values())):
            if chain is not None:
                extras = tuple((extra_cols or {}).values())
                outs = fn(blob, nr_dev, np.int32(row_count), extras, ectx)
            else:
                outs = fn(blob, nr_dev)
    _STAGING.pending = outs  # arena reusable once the decode ran
    if chain is not None:
        return outs  # the chain's output pytree (ONE dispatch, fused)
    result = {}
    for name, (plan, eng_dtype), out in zip(
            names, [plans[n] for n in names], outs):
        if plan.str_dict is not None:
            offsets, chars, valid = out
            result[name] = TpuColumnVector(eng_dtype, validity=valid,
                                           offsets=offsets, chars=chars)
        else:
            vals, valid = out
            result[name] = TpuColumnVector(eng_dtype, data=vals,
                                           validity=valid)
    return result


def _pad_pow2(arr: np.ndarray) -> np.ndarray:
    """Pad 1-D upload arrays to (finely) bucketed lengths so the jit
    cache is bounded (bucket_fine lives in columnar.batch — these
    arrays are the bytes crossing the host->device link, so padding
    directly taxes the mechanism)."""
    n = arr.shape[0]
    cap = bucket_fine(n)
    if cap == n:
        return arr
    out = np.zeros(cap, arr.dtype)
    out[:n] = arr
    return out


def _pad_rows(tab: np.ndarray) -> np.ndarray:
    n = tab.shape[0]
    cap = max(8, bucket_rows(n))
    if cap == n:
        return tab
    out = np.zeros((cap, tab.shape[1]), tab.dtype)
    out[:n] = tab
    # padding runs: row start beyond any real row so searchsorted never
    # selects them; constant RLE zero
    out[n:, 0] = np.iinfo(np.int32).max
    out[n:, 1] = 1 | (1 << 8)
    return out
