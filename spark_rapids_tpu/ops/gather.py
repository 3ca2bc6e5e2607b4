"""Row gather / stream-compaction kernels.

TPU replacement for libcudf's stream compaction (apply_boolean_mask,
gather/scatter — SURVEY.md §2.2-E; reference mount empty). Filter output
size is data-dependent, which XLA can't express as a shape — so compaction
is prefix-sum + scatter into the SAME static capacity, with the live count
threaded alongside (SURVEY.md §7.3.1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..columnar.batch import TpuBatch, row_mask
from ..columnar.column import TpuColumnVector
from ..programs import named_jit
from .strings import gather_strings

__all__ = ["compaction_indices", "dense_run_counts", "dense_run_expand",
           "blocked_int_cumsum", "exclusive_cumsum",
           "varlen_gather_plan",
           "invert_permutation", "gather_column", "gather_batch",
           "gather_columns", "compact_batch", "ensure_compacted",
           "shrink_batch"]


def inclusive_int_cumsum(x: jax.Array) -> jax.Array:
    """Inclusive int32 prefix sum via the native cumulative-sum HLO.
    The v5e's compiler (asked without a chip, 2^21 elements, PR 21)
    takes 9.6 s for this and 366 s for the `lax.associative_scan`
    network it replaced, so the choice stands on the real compiler. It
    is int32 BY DESIGN — the int64 cumsum costs 3x the compile (33.5 s)
    and tests/test_chip_compile.py holds every caller to it. Exact to
    2^31; a float64 prefix (a pair of float32 on the TPU, ~49 bits)
    would be exact to 2^49 but takes 331 s to compile."""
    return jnp.cumsum(x.astype(jnp.int32))


_PREFIX_BLOCK = 1024


def _blocked_int_cumsum(x: jax.Array) -> jax.Array:
    """Inclusive int32 prefix sum along the last axis, whose length is a
    multiple of 1024: within rows of 1024 lanes, plus the prefix of the
    row totals. On the v5e (PR 27, 2^20 lanes) it compiles in 0.4 s
    where the 1-D ``jnp.cumsum`` takes 30 s and runs as fast alone (0.9
    against 0.8 ms). Sums wrap modulo 2^32 like every int32 add."""
    rows = x.shape[-1] // _PREFIX_BLOCK
    inner = jnp.cumsum(x.reshape(x.shape[:-1] + (rows, _PREFIX_BLOCK)),
                       axis=-1)
    totals = inner[..., -1]
    before = jnp.cumsum(totals, axis=-1) - totals
    return (inner + before[..., None]).reshape(x.shape)


def blocked_int_cumsum(x: jax.Array) -> jax.Array:
    """``jnp.cumsum`` of a 1-D array as int32, in the 1024-blocked form
    (``_blocked_int_cumsum``): the prefix for any new site over row or
    character lanes."""
    n = x.shape[0]
    pad = -n % _PREFIX_BLOCK
    return _blocked_int_cumsum(
        jnp.pad(x.astype(jnp.int32), (0, pad)))[:n]


def dense_run_counts(starts: jax.Array, n: int) -> jax.Array:
    """out[i] = how many of the non-negative `starts` are <= i, for the
    dense positions i = 0..n-1: what ``jnp.searchsorted(starts,
    arange(n), side="right")`` gives for sorted starts, so ``out - 1``
    is the run (row) that covers position i. The queries being every
    position in order, that is a prefix count of start flags: ONE
    scatter of len(starts) flags and ONE int32 prefix sum over n lanes,
    where the search lowers to a `while` of log2(len(starts)) gathers
    over n lanes (the eight loops that were 7.1 s of q6's 13.0
    device-busy seconds; ledger, PR 26). `add`, not `set`: zero-length
    runs share a start and each counts. A start >= n — a padding run at
    int32.max among them — counts for no position below n.

    The prefix is blocked (``_blocked_int_cumsum``); q6's decode program
    built on it runs 6 % faster than on the 1-D form (5.78 against
    6.13 s a query, PR 27: XLA places more of the run tables in fast
    memory beside it)."""
    rows = -(-n // _PREFIX_BLOCK)
    flags = jnp.zeros((rows * _PREFIX_BLOCK,), jnp.int32) \
        .at[starts.astype(jnp.int32)].add(1, mode="drop")
    return _blocked_int_cumsum(flags)[:n]


def dense_run_expand(starts: jax.Array, fields: jax.Array,
                     n: int) -> jax.Array:
    """out[k, i] = fields[k, r] for the run r that covers the dense
    position i (the last run of the SORTED `starts` with starts[r] <= i;
    0 below the first start): ``fields[:, searchsorted(starts,
    arange(n), "right") - 1]`` without a gather over n lanes. A per-run
    field is piecewise constant over the positions, so scatter-ADD its
    step ``fields[:, r] - fields[:, r - 1]`` at ``starts[r]`` and take
    the prefix that ``dense_run_counts`` takes to FIND the run: one
    scatter of len(starts) columns and one blocked int32 prefix for all
    the fields stacked, where each ``field[rid]`` was a gather of 9-24 ms
    over 2^20 lanes on the v5e whatever it read (PR 30). `add`:
    zero-length runs share a start and their steps telescope. `drop`:
    runs that start at or past n — padding runs at int32.max — fall
    away, and being the tail of a sorted table they take no step of a
    run below n with them. Every lane is int32 and exact modulo 2^32 (a
    telescoped sum needs no carry), so a 64-bit field rides as its two
    32-bit halves."""
    fields = fields.astype(jnp.int32)
    steps = fields - jnp.pad(fields[:, :-1], ((0, 0), (1, 0)))
    rows = -(-n // _PREFIX_BLOCK)
    flat = jnp.zeros((fields.shape[0], rows * _PREFIX_BLOCK), jnp.int32) \
        .at[:, starts.astype(jnp.int32)].add(steps, mode="drop")
    return _blocked_int_cumsum(flat)[:, :n]


def exclusive_cumsum(x: jax.Array) -> jax.Array:
    """Exclusive int32 prefix sum (see inclusive_int_cumsum)."""
    x = x.astype(jnp.int32)
    return inclusive_int_cumsum(x) - x


def invert_permutation(perm: jax.Array, values: jax.Array) -> jax.Array:
    """out[perm[i]] = values[i] without a scatter: sorting (perm, values)
    by perm reorders values back to original positions. lax.sort is fast
    on TPU where arbitrary scatters serialize."""
    _, out = jax.lax.sort((perm, values), num_keys=1)
    return out


def compaction_indices(keep: jax.Array):
    """(indices, count): indices[j] = source row of the j-th kept row, for
    j < count; rows >= count hold the non-kept rows (gather of them is
    masked by the caller's out_live).

    Sort-based: one stable sort of ONE packed word (keep-rank bit above
    the row index — sort_keys.lex_sort), no scatter, no int cumsum (both
    serialize on TPU). keep must already exclude padding rows.
    """
    from .sort_keys import lex_sort
    key = jnp.where(keep, jnp.int8(0), jnp.int8(1))
    indices, _ = lex_sort([key], one_bit=[0])
    count = jnp.sum(keep.astype(jnp.int32))
    return indices, count


def varlen_gather_plan(offsets: jax.Array, indices: jax.Array, out_live,
                       capacity: int):
    """The variable-length gather, shared by strings (uint8 chars) and
    arrays/maps (element columns): (new_offsets, src, live) where
    new_offsets is the int32 prefix sum of the gathered row lengths and,
    for each of the `capacity` output payload positions, src is the
    source payload position and live whether it lies under the total.

    Each output position needs the row that owns it; the positions being
    every lane in order, that is a prefix count of row-END flags
    (`dense_run_counts`), never a search per position:
    `jnp.searchsorted` is a `while` of log2(rows)+1 gathers over every
    payload lane on the TPU (six of them were 3.4 of q3's 7.1 device-busy
    seconds; ledger, PR 29). Every row's end counts, dead and zero-length
    rows included: they share an end with their predecessor and still
    advance the row id. Source minus output position is one number per
    row, so the payload lanes gather ONCE by row (36 ms a 2^22-lane
    gather on the v5e). out_live (if given; any mask, not only a prefix)
    zeroes the lengths of dead output rows so padding can't inflate the
    offsets. src is not clipped: past the total it is arbitrary."""
    n = indices.shape[0]
    new_lens = (offsets[1:] - offsets[:-1])[indices]
    if out_live is not None:
        new_lens = jnp.where(out_live, new_lens, 0)
    new_offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), inclusive_int_cumsum(new_lens)])
    delta = offsets[:-1][indices] - new_offsets[:-1]
    pos = jnp.arange(capacity, dtype=jnp.int32)
    row = dense_run_counts(new_offsets[1:], capacity)
    src = pos + delta[jnp.clip(row, 0, n - 1)]
    return new_offsets, src, pos < new_offsets[-1]


def gather_list(col: TpuColumnVector, indices: jax.Array,
                out_live: jax.Array) -> TpuColumnVector:
    """Reorder an array/map column by row indices (varlen_gather_plan):
    the element columns gather recursively by the resulting
    source-element indices (strings work the same way one level down —
    gather_strings is this kernel with uint8 chars).
    The element capacity stays the child's static capacity (each source
    element appears at most once per gathered row set; duplicates from
    repeated indices are bounded by the caller's semantics)."""
    n = indices.shape[0]
    validity = col.validity[indices]
    if out_live is not None:
        validity = validity & out_live
    ecap = col.children[0].capacity
    if ecap == 0:
        return col.with_arrays(validity=validity,
                               offsets=jnp.zeros((n + 1,), jnp.int32))
    new_offsets, src, elem_live = varlen_gather_plan(
        col.offsets, indices, out_live, ecap)
    src = jnp.clip(src, 0, ecap - 1)
    children = [gather_column(ch, src, elem_live) for ch in col.children]
    return col.with_arrays(validity=validity, offsets=new_offsets,
                           children=children)


def gather_column(col: TpuColumnVector, indices: jax.Array,
                  out_live: jax.Array,
                  char_capacity: int = None) -> TpuColumnVector:
    """Reorder a column by row indices; out_live masks validity of padding
    rows in the output so downstream null-aware kernels see them as null."""
    validity = col.validity[indices] & out_live
    if col.is_string_like:
        cap = char_capacity if char_capacity is not None \
            else col.chars.shape[0]
        out = gather_strings(col, indices, cap, out_live=out_live)
        return out.with_arrays(validity=validity)
    if col.offsets is not None and col.children is not None:  # array/map
        return gather_list(col, indices, out_live)
    if col.children is not None:  # struct
        children = [gather_column(ch, indices, out_live)
                    for ch in col.children]
        return col.with_arrays(validity=validity, children=children)
    if col.data is None:  # NullType
        return col.with_arrays(validity=validity)
    return col.with_arrays(data=col.data[indices], validity=validity)


def gather_batch(batch: TpuBatch, indices: jax.Array, count,
                 char_capacities=None) -> TpuBatch:
    """Reorder/compact a whole batch by row indices (count = live rows),
    prefix layout. See gather_columns for the packed-gather mechanics."""
    out_live = row_mask(indices.shape[0], count)
    cols = gather_columns(batch.columns, indices, out_live,
                          char_capacities)
    return TpuBatch(cols, batch.schema, count)


def gather_columns(columns, indices: jax.Array, out_live: jax.Array,
                   char_capacities=None):
    """Reorder a list of columns by row indices with an arbitrary
    live-output mask (need not be a prefix — the join fast path gathers
    build rows into match positions).

    All fixed-width data lanes are bitcast to int32 words and packed —
    together with the validity bits (one int32 bitfield lane per 32
    columns) — into a single (rows, words) matrix, so the whole set
    moves in ONE row gather: N separate 1-D gathers cost ~30ms each on
    TPU, a packed 2-D row gather is ~free."""
    n = columns[0].capacity if columns else 0  # input rows (packing side)

    lanes = []          # (n, w) int32 blocks to pack
    col_lanes = []      # per column: (kind, lane_offset, width)
    off = 0
    for c in columns:
        if c.is_string_like or c.data is None or c.children is not None:
            col_lanes.append(("special", 0, 0))
            continue
        if c.data.dtype == jnp.float64:
            # TPU has no native f64 (stored/computed as f32) and its X64
            # rewriter cannot implement bitcast f64<->s64; gather the
            # lane directly instead of packing it
            col_lanes.append(("direct", 0, 0))
            continue
        d = c.data
        if d.dtype == jnp.bool_:
            w = d.astype(jnp.int32)[:, None]
        elif d.dtype.itemsize < 4:
            w = d.astype(jnp.int32)[:, None]
        elif d.dtype.itemsize == 4:
            w = jax.lax.bitcast_convert_type(d, jnp.int32)[:, None]
        else:  # 8-byte lanes -> two int32 words: (n,) i64 -> (n, 2) i32
            w = jax.lax.bitcast_convert_type(
                jax.lax.bitcast_convert_type(d, jnp.int64), jnp.int32)
        lanes.append(w)
        col_lanes.append(("packed", off, w.shape[1]))
        off += w.shape[1]
    # validity bitfields: 32 columns per int32 lane
    ncols = len(columns)
    vwords = []
    for base in range(0, ncols, 32):
        word = jnp.zeros((n,), jnp.int32)
        for bit, c in enumerate(columns[base: base + 32]):
            word = word | (c.validity.astype(jnp.int32) << bit)
        vwords.append(word[:, None])
    vbase = off
    lanes.extend(vwords)
    off += len(vwords)

    packed = jnp.concatenate(lanes, axis=1) if lanes else None
    gathered = packed[indices] if packed is not None else None

    cols = []
    for i, c in enumerate(columns):
        word = gathered[:, vbase + i // 32]
        validity = (((word >> (i % 32)) & 1) != 0) & out_live
        kind, loff, width = col_lanes[i]
        if kind == "direct":
            cols.append(c.with_arrays(data=c.data[indices],
                                      validity=validity))
            continue
        if kind == "special":
            if c.is_string_like:
                cc = char_capacities[i] if char_capacities is not None \
                    else c.chars.shape[0]
                out = gather_strings(c, indices, cc, out_live=out_live)
                cols.append(out.with_arrays(validity=validity))
            elif c.children is not None:  # struct / array / map
                out = gather_column(c, indices, out_live)
                cols.append(out.with_arrays(validity=validity))
            else:  # NullType
                cols.append(c.with_arrays(validity=validity))
            continue
        d = c.data
        g = gathered[:, loff: loff + width]
        if d.dtype == jnp.bool_:
            data = g[:, 0] != 0
        elif d.dtype.itemsize < 4:
            data = g[:, 0].astype(d.dtype)
        elif d.dtype.itemsize == 4:
            data = jax.lax.bitcast_convert_type(g[:, 0], d.dtype)
        else:
            i64 = jax.lax.bitcast_convert_type(g, jnp.int64)  # (n_out,)
            data = i64 if d.dtype == jnp.int64 else \
                jax.lax.bitcast_convert_type(i64, d.dtype)
        cols.append(c.with_arrays(data=data, validity=validity))
    return cols


def compact_batch(batch: TpuBatch, keep: jax.Array) -> TpuBatch:
    """Stream compaction: keep rows where `keep` (padding excluded here)."""
    keep = keep & batch.live_mask()
    indices, count = compaction_indices(keep)
    return gather_batch(batch, indices, count)  # prefix layout, no selection


def _compact_selection(batch: TpuBatch) -> TpuBatch:
    return compact_batch(batch, batch.live_mask())


_compact_selection = named_jit("compact_selection", _compact_selection)


def _shrink_col(c: TpuColumnVector, new_cap: int) -> TpuColumnVector:
    if c.data is not None:
        return c.with_arrays(data=c.data[:new_cap],
                             validity=c.validity[:new_cap])
    if c.offsets is not None:  # strings / arrays: payload stays shared
        return c.with_arrays(offsets=c.offsets[:new_cap + 1],
                             validity=c.validity[:new_cap])
    if c.children is not None:  # struct: children align with rows
        return c.with_arrays(validity=c.validity[:new_cap],
                             children=[_shrink_col(ch, new_cap)
                                       for ch in c.children])
    return c.with_arrays(validity=c.validity[:new_cap])


def shrink_batch(batch: TpuBatch, new_cap: int) -> TpuBatch:
    """Slice a prefix-layout batch down to a smaller static capacity
    (row_count must be <= new_cap). Fixed-width lanes are static slices;
    string chars / array elements stay shared (offsets are absolute)."""
    assert batch.selection is None, "compact before shrinking"
    if new_cap >= batch.capacity:
        return batch
    cols = [_shrink_col(c, new_cap) for c in batch.columns]
    return TpuBatch(cols, batch.schema, batch.row_count)


def ensure_compacted(batch: TpuBatch) -> TpuBatch:
    """Materialize a lazy selection mask (TpuBatch docstring) into prefix
    layout; no-op (and no dispatch) when the batch has no selection.
    Callable from host code or inside traced code (the selection check is
    static; nested jit inlines)."""
    if batch.selection is None:
        return batch
    return _compact_selection(batch)
