"""Sort-key normalization and multi-key permutation kernels.

TPU replacement for libcudf's radix/merge sort (SURVEY.md §2.2-E, §7.1.3;
reference mount empty): every key column is normalized to one orderable
integer lane (floats via IEEE total-order bit tricks with Spark's NaN/-0.0
semantics; strings via iterative rank refinement), then `jax.lax.sort`
does one lexicographic sort over the lanes with the row index as the final
tiebreak key (= stable). The same machinery yields group-ids for the
sort-based aggregate.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence

import jax
import jax.numpy as jnp

from .. import datatypes as dt
from ..columnar.column import TpuColumnVector
from .strings import gather_window

__all__ = ["SortSpec", "orderable_int", "orderable_int_to_float",
           "canonicalize_floats",
           "string_order_ranks", "string_order_ranks_multi",
           "lex_sort", "sort_permutation", "segment_ids_for_keys",
           "key_lanes",
           "lex_leq", "lex_min_tuple"]

_RANK_WINDOW = 7  # bytes per refinement pass: 7 x 9 bits = 63 bits / int64


@dataclasses.dataclass(frozen=True)
class SortSpec:
    """Per-key direction/null placement (GpuSortOrder analog).
    Spark defaults: ascending nulls-first; descending nulls-last."""
    ascending: bool = True
    nulls_first: bool = True


def canonicalize_floats(d: jax.Array) -> jax.Array:
    """-0.0 -> 0.0, any NaN -> the canonical positive NaN (Spark's
    NormalizeFloatingNumbers semantics, shared by sort keys, group keys
    and min/max)."""
    d = jnp.where(d == 0, jnp.zeros_like(d), d)
    return jnp.where(jnp.isnan(d), jnp.full_like(d, jnp.nan), d)


def normalize_float_key_col(col: TpuColumnVector) -> TpuColumnVector:
    """Column-level float key normalization (Spark's
    NormalizeFloatingNumbers): shared by group-by keys, join keys and any
    other place key *values* are emitted, not just compared."""
    from .. import datatypes as _dt
    if not _dt.is_floating(col.dtype):
        return col
    return col.with_arrays(data=canonicalize_floats(col.data))


def _total_order(bits: jax.Array) -> jax.Array:
    """Signed total-order map over IEEE float bits (int32 or int64
    lanes): positives (incl. +0, +inf, NaN) keep their bits (already
    ascending); negatives map to ~bits + INT_MIN, a wrapping add that
    lands them ascending in the negative int range (-inf lowest,
    -0.0 -> -1 just below +0.0 -> 0)."""
    min_int = jnp.array(jnp.iinfo(bits.dtype).min, bits.dtype)
    return jnp.where(bits < 0, ~bits + min_int, bits)


def _total_order_inv(keys: jax.Array) -> jax.Array:
    """Inverse of ``_total_order``."""
    min_int = jnp.array(jnp.iinfo(keys.dtype).min, keys.dtype)
    return jnp.where(keys < 0, ~(keys - min_int), keys)


def _f64_is_f32_pair() -> bool:
    """Off the CPU a float64 lane is a PAIR of float32 (hi + lo, about
    49 significand bits, float32's exponent range): the TPU has no f64
    hardware and XLA's X64 rewriter expands every f64 op into arithmetic
    on the pair (v5e, jax 0.9 — the compiled HLO shows it, and
    chip_smoke.py's types phase measures it). The rewriter implements
    bitcast s64->f64 but NOT f64->s64 (UNIMPLEMENTED), so ordering keys
    are built from the pair instead of the IEEE bits."""
    return jax.default_backend() != "cpu"


_LO_BIAS = 1 << 31


def orderable_int(col: TpuColumnVector) -> jax.Array:
    """Map a fixed-width column's data lane to a signed integer lane whose
    ascending order is Spark's ascending order (nulls excluded — handled by
    a separate rank lane). Floats: -0.0 == 0.0, all NaNs equal and largest.
    ``orderable_int_to_float`` inverts the float maps."""
    t = col.dtype
    d = col.data
    if isinstance(t, dt.BooleanType):
        return d.astype(jnp.int8)
    if dt.is_floating(t):
        d = canonicalize_floats(d)
        if t.np_dtype == jnp.float32:
            return _total_order(jax.lax.bitcast_convert_type(d, jnp.int32))
        if not _f64_is_f32_pair():
            return _total_order(jax.lax.bitcast_convert_type(d, jnp.int64))
        # (hi, lo) as the device holds them: lexicographic order on the
        # pair is numeric order, packed into one int64 lane. A cast to
        # float32 alone would tie every pair of keys closer than 2^-24
        hi = d.astype(jnp.float32)
        lo = jnp.where(jnp.isfinite(hi),
                       (d - hi.astype(jnp.float64)).astype(jnp.float32),
                       jnp.float32(0))
        lo = jnp.where(lo == 0, jnp.zeros_like(lo), lo)  # -0.0
        hk = _total_order(jax.lax.bitcast_convert_type(hi, jnp.int32))
        lk = _total_order(jax.lax.bitcast_convert_type(lo, jnp.int32))
        return (hk.astype(jnp.int64) << 32) \
            | (lk.astype(jnp.int64) + _LO_BIAS)
    # ints / date / timestamp / decimal already compare as ints
    return d


def orderable_int_to_float(keys: jax.Array, np_dtype) -> jax.Array:
    """The float lane an ``orderable_int`` key lane came from (min/max
    reduce over keys, then map the winner back)."""
    bitcast = jax.lax.bitcast_convert_type
    if np_dtype == jnp.float32:
        return bitcast(_total_order_inv(keys), jnp.float32)
    if not _f64_is_f32_pair():
        return bitcast(_total_order_inv(keys), jnp.float64)
    hk = (keys >> 32).astype(jnp.int32)
    lk = ((keys & 0xFFFFFFFF) - _LO_BIAS).astype(jnp.int32)
    hi = bitcast(_total_order_inv(hk), jnp.float32)
    lo = bitcast(_total_order_inv(lk), jnp.float32)
    return hi.astype(jnp.float64) + lo.astype(jnp.float64)


def string_order_ranks_multi(cols: Sequence[TpuColumnVector],
                             lives: Sequence[jax.Array]) -> jax.Array:
    """Dense order ranks over the virtual concatenation of several string
    columns: rank[i] < rank[j] iff bytes(i) < bytes(j) lexicographically
    (unsigned); equal strings share a rank — also across columns, which is
    what makes this the join-key equality kernel. Non-live rows get
    INT32_MAX so they sort last. Returns one rank vector of length
    sum(capacities) in column order.

    Iterative refinement: stable-sort by (current-rank, next-7-byte window)
    and split ties; loops until the longest string is consumed or all ranks
    are distinct (dynamic trip count, static shapes per pass —
    SURVEY.md §7.3.1).
    """
    live = jnp.concatenate([jnp.asarray(lv) for lv in lives])
    n = live.shape[0]
    lens = jnp.concatenate([c.offsets[1:] - c.offsets[:-1] for c in cols])
    live_lens = jnp.where(live, lens, 0)
    max_len = jnp.max(live_lens, initial=0)
    num_live = jnp.sum(live.astype(jnp.int32))
    idx = jnp.arange(n, dtype=jnp.int32)

    def window_key(chunk):
        # pack 7 bytes into one int64, 9 bits each: past-end (-1) -> 0,
        # real bytes -> 1..256, so shorter strings sort first.
        parts = []
        for c in cols:
            w = gather_window(c.offsets, c.chars, chunk,
                              window=_RANK_WINDOW)
            parts.append((w + 1).astype(jnp.int64))
        w = jnp.concatenate(parts)
        key = jnp.zeros((n,), jnp.int64)
        for b in range(_RANK_WINDOW):
            key = (key << 9) | w[:, b]
        return key

    rank0 = jnp.where(live, jnp.int32(0), jnp.int32(1))

    def cond(state):
        chunk, rank, distinct = state
        return (chunk * _RANK_WINDOW < max_len) & (distinct < num_live)

    def body(state):
        chunk, rank, _ = state
        key = window_key(chunk)
        # idx as trailing sort key = stable within (rank, key) ties
        srank, skey, sidx = jax.lax.sort((rank, key, idx), num_keys=3)
        boundary = jnp.concatenate([
            jnp.ones((1,), jnp.bool_),
            (srank[1:] != srank[:-1]) | (skey[1:] != skey[:-1])])
        # log-depth int prefix + sort-based inversion: serial cumsum and
        # scatters both lose on TPU
        from .gather import inclusive_int_cumsum, invert_permutation
        new_rank_sorted = inclusive_int_cumsum(boundary) - 1
        new_rank = invert_permutation(sidx, new_rank_sorted)
        distinct = jnp.max(jnp.where(live, new_rank, -1), initial=-1) + 1
        return chunk + 1, new_rank, distinct

    _, rank, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), rank0, jnp.int32(0)))
    return jnp.where(live, rank, jnp.int32(2**31 - 1))


def string_order_ranks(col: TpuColumnVector, live: jax.Array) -> jax.Array:
    """Single-column case of string_order_ranks_multi."""
    return string_order_ranks_multi([col], [live])


def _null_rank_lane(validity: jax.Array, spec: SortSpec) -> jax.Array:
    """Null placement is independent of direction: the value lane handles
    direction, this lane handles where nulls land."""
    if spec.nulls_first:
        return jnp.where(validity, jnp.int8(1), jnp.int8(0))
    return jnp.where(validity, jnp.int8(0), jnp.int8(1))


def _key_lanes(key_cols: Sequence[TpuColumnVector],
               specs: Sequence[SortSpec], live: jax.Array):
    """(lanes, one_bit): orderable lanes, most-significant first — a
    live-rank lane (padding always last), then per key a null-placement
    lane and a value lane — and the positions of the lanes that only
    hold 0/1 (the rank lanes), which ``lex_sort`` packs into one bit."""
    lanes: List[jax.Array] = [jnp.where(live, jnp.int8(0), jnp.int8(1))]
    one_bit = [0]
    for col, spec in zip(key_cols, specs):
        if col.is_string_like:
            vals = string_order_ranks(col, live & col.validity)
        elif col.data is None:  # NullType: all rows equal
            vals = jnp.zeros((live.shape[0],), jnp.int8)
        else:
            # neutralize the lane under nulls: computed expressions leave
            # garbage in the data lane of null rows, and null==null must
            # hold for both ordering and grouping
            vals = orderable_int(col)
            vals = jnp.where(col.validity, vals, jnp.zeros_like(vals))
        if not spec.ascending:
            vals = ~vals  # total reversal of the signed int order
        one_bit.append(len(lanes))
        lanes.append(_null_rank_lane(col.validity, spec))
        lanes.append(vals)
    return lanes, one_bit


def key_lanes_vs_bounds(col: TpuColumnVector, bcol: TpuColumnVector,
                        spec: SortSpec):
    """((null_lane, value_lane) for rows, same for bounds) in ONE shared
    orderable space with the exact _key_lanes semantics — the single
    source of truth for direction/null/NaN placement, consumed by the
    range partitioner's row-vs-bound lexicographic compare. Strings rank
    jointly over the virtual concat; equal nulls share the rank space's
    top sentinel on both sides."""
    n = col.capacity
    if col.is_string_like:
        ranks = string_order_ranks_multi(
            [col, bcol], [col.validity, bcol.validity])
        vr = ranks[:n].astype(jnp.int64)
        vb = ranks[n:].astype(jnp.int64)
    elif col.data is None:  # NullType: all rows equal
        vr = jnp.zeros((n,), jnp.int64)
        vb = jnp.zeros((bcol.capacity,), jnp.int64)
    else:
        vr = jnp.where(col.validity, orderable_int(col).astype(jnp.int64),
                       jnp.int64(0))
        vb = jnp.where(bcol.validity,
                       orderable_int(bcol).astype(jnp.int64), jnp.int64(0))
    if not spec.ascending:
        vr, vb = ~vr, ~vb
    return ((_null_rank_lane(col.validity, spec), vr),
            (_null_rank_lane(bcol.validity, spec), vb))


def key_lanes(key_cols, specs, live):
    """Public name for the orderable lane stack (out-of-core merge uses it
    to compare rows against run boundaries in the same rank space)."""
    return _key_lanes(key_cols, specs, live)[0]


def lex_leq(lanes: Sequence[jax.Array],
            boundary: Sequence[jax.Array]) -> jax.Array:
    """Per-row mask: lane tuple <= boundary scalar tuple, lexicographic in
    lane order (= the sort order, since lanes encode direction and null
    placement)."""
    n = lanes[0].shape[0]
    lt = jnp.zeros((n,), jnp.bool_)
    eq = jnp.ones((n,), jnp.bool_)
    for lane, b in zip(lanes, boundary):
        lt = lt | (eq & (lane < b))
        eq = eq & (lane == b)
    return lt | eq


def lex_min_tuple(blanes: Sequence[jax.Array], bvalid: jax.Array):
    """Lexicographic minimum among k boundary tuples (blanes: each lane is
    shape (k,)); invalid entries never win. k is static and small."""
    k = bvalid.shape[0]
    best = [lane[0] for lane in blanes]
    best_valid = bvalid[0]
    for i in range(1, k):
        cand = [lane[i] for lane in blanes]
        lt = jnp.asarray(False)
        eq = jnp.asarray(True)
        for c, b in zip(cand, best):
            lt = lt | (eq & (c < b))
            eq = eq & (c == b)
        take = bvalid[i] & (lt | ~best_valid)
        best = [jnp.where(take, c, b) for c, b in zip(cand, best)]
        best_valid = best_valid | bvalid[i]
    return best


def _unsigned_pieces(lane: jax.Array, one_bit: bool):
    """A signed orderable lane as (uint32 array, bit width) pieces, most
    significant first, whose unsigned order is the lane's signed order."""
    if one_bit:  # a 0/1 rank lane
        return [(lane.astype(jnp.uint32), 1)]
    bits = jnp.iinfo(lane.dtype).bits  # signed ints only
    if bits == 64:
        u = jax.lax.bitcast_convert_type(lane, jnp.uint64) \
            ^ jnp.uint64(1 << 63)
        return [((u >> jnp.uint64(32)).astype(jnp.uint32), 32),
                (u.astype(jnp.uint32), 32)]
    bias = 1 << (bits - 1)
    return [((lane.astype(jnp.int32) + bias).astype(jnp.uint32), bits)]


def lex_sort(lanes: Sequence[jax.Array], one_bit: Sequence[int] = ()):
    """(perm, boundary): the STABLE permutation ordering rows
    lexicographically by the signed-int ``lanes`` (most significant
    first), and — in sorted order — whether each row's lane tuple differs
    from the row before it (row 0: True). ``one_bit`` names the lanes
    that only hold 0/1 (rank lanes).

    Same order as ``lax.sort(lanes + (row index,), num_keys=all)``, with
    fewer operands: the chip's compiler takes 30-40 s PER 32-BIT KEY
    OPERAND of a 2^21-row sort (v5e, PR 21), and an int8 rank lane costs
    as much as an int32 one. So the lanes' significant bits are
    concatenated, most significant first, into as few uint32 words as
    hold them, and the row index (the stability tiebreak; log2(n) bits)
    rides in the low bits of the last word — for one int32 key 2 words
    instead of 4 operands, for two int32 keys 3 instead of 6."""
    n = lanes[0].shape[0]
    idx_bits = max(1, (n - 1).bit_length())
    words: List[jax.Array] = []
    cur, free = jnp.zeros((n,), jnp.uint32), 32
    for li, lane in enumerate(lanes):
        for piece, w in _unsigned_pieces(lane, li in one_bit):
            while w > 0:
                take = min(w, free)
                part = piece if take == 32 else \
                    (piece >> (w - take)) & jnp.uint32((1 << take) - 1)
                cur = cur | (part << (free - take))
                w -= take
                free -= take
                if free == 0:
                    words.append(cur)
                    cur, free = jnp.zeros((n,), jnp.uint32), 32
    if free < idx_bits:  # no room left beside the key bits
        words.append(cur)
        cur, free = jnp.zeros((n,), jnp.uint32), 32
    key_bits_in_last = 32 - free
    words.append(cur | jnp.arange(n, dtype=jnp.uint32))
    swords = jax.lax.sort(tuple(words), num_keys=len(words))
    perm = (swords[-1] & jnp.uint32((1 << idx_bits) - 1)).astype(jnp.int32)
    keyed = list(swords[:-1])
    if key_bits_in_last:
        keyed.append(swords[-1] >> idx_bits)
    boundary = jnp.zeros((n,), jnp.bool_).at[0].set(True)
    for w_ in keyed:
        boundary = boundary | jnp.concatenate(
            [jnp.zeros((1,), jnp.bool_), w_[1:] != w_[:-1]])
    return perm, boundary


def sort_permutation(key_cols: Sequence[TpuColumnVector],
                     specs: Sequence[SortSpec],
                     live: jax.Array) -> jax.Array:
    """Stable permutation ordering rows by the keys, padding rows last."""
    perm, _ = lex_sort(*_key_lanes(key_cols, specs, live))
    return perm


def segment_ids_for_keys(key_cols: Sequence[TpuColumnVector],
                         live: jax.Array):
    """(perm, seg_ids_sorted, num_groups): rows permuted so equal keys are
    adjacent (live rows first), seg ids over the sorted order, and the
    group count among live rows. Grouping equality is Spark's: null==null,
    NaN==NaN, -0.0==0.0."""
    specs = [SortSpec()] * len(key_cols)
    perm, boundary = lex_sort(*_key_lanes(key_cols, specs, live))
    from .gather import inclusive_int_cumsum
    seg = inclusive_int_cumsum(boundary) - 1
    live_sorted = live[perm]
    num_groups = jnp.max(jnp.where(live_sorted, seg + 1, 0), initial=0)
    return perm, seg, num_groups
