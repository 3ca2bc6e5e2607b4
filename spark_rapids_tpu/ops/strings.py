"""Device string kernels over (offsets:int32, chars:uint8) columns.

TPU replacement for libcudf's strings kernels (SURVEY.md §2.2-E; mount
empty). Strings are Arrow-layout byte arrays; kernels are vectorized
gathers/compares over fixed-size byte windows so shapes stay static —
variable-length work is bounded by a while_loop with early exit, not
per-row dynamic control flow.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar.column import TpuColumnVector

__all__ = ["string_lengths", "string_compare_tpu", "gather_window",
           "substring_tpu", "upper_ascii_tpu", "lower_ascii_tpu",
           "concat_strings_tpu", "starts_with_tpu", "ends_with_tpu",
           "contains_tpu", "gather_strings"]

_WINDOW = 64  # bytes compared per loop step


def string_lengths(col: TpuColumnVector) -> jax.Array:
    """Byte length per row (int32)."""
    return col.offsets[1:] - col.offsets[:-1]


def gather_window(offsets, chars, chunk, window=_WINDOW):
    """(n, window) int16 byte matrix for window #chunk of each string.
    Past-end positions are -1 (sorts below any real byte)."""
    n = offsets.shape[0] - 1
    starts = offsets[:-1]
    lens = offsets[1:] - starts
    pos = chunk * window + jnp.arange(window, dtype=jnp.int32)[None, :]
    idx = starts[:, None] + pos
    in_range = pos < lens[:, None]
    limit = max(chars.shape[0] - 1, 0)
    idx = jnp.clip(idx, 0, limit)
    if chars.shape[0] == 0:
        b = jnp.zeros((n, window), jnp.int16)
    else:
        b = chars[idx].astype(jnp.int16)
    return jnp.where(in_range, b, jnp.int16(-1))


def string_compare_tpu(a: TpuColumnVector, b: TpuColumnVector) -> jax.Array:
    """Row-wise lexicographic compare (unsigned bytes): int8 -1/0/1."""
    max_len = jnp.maximum(
        jnp.max(string_lengths(a), initial=0),
        jnp.max(string_lengths(b), initial=0))

    def body(state):
        chunk, result, done = state
        wa = gather_window(a.offsets, a.chars, chunk)
        wb = gather_window(b.offsets, b.chars, chunk)
        diff = wa != wb
        any_diff = jnp.any(diff, axis=1)
        first = jnp.argmax(diff, axis=1)
        sa = jnp.take_along_axis(wa, first[:, None], axis=1)[:, 0]
        sb = jnp.take_along_axis(wb, first[:, None], axis=1)[:, 0]
        cmp = jnp.where(sa < sb, jnp.int8(-1), jnp.int8(1))
        new_result = jnp.where(done, result,
                               jnp.where(any_diff, cmp, jnp.int8(0)))
        # a row is finished if bytes differed, or both strings ended
        ended = (chunk + 1) * _WINDOW >= max_len
        new_done = done | any_diff | ended
        return chunk + 1, new_result, new_done

    def cond(state):
        chunk, _, done = state
        return ~jnp.all(done)

    n = a.offsets.shape[0] - 1
    init = (jnp.int32(0), jnp.zeros((n,), jnp.int8),
            jnp.zeros((n,), jnp.bool_))
    _, result, _ = jax.lax.while_loop(cond, body, init)
    return result


def gather_strings(col: TpuColumnVector, indices: jax.Array,
                   char_capacity: int, out_live=None) -> TpuColumnVector:
    """Reorder a string column by row indices: the variable-length gather
    of `ops.gather.varlen_gather_plan` (offsets by prefix sum, each char
    position's source by a prefix count of row ends), then the byte is
    a single gather from the source. out_live (if given; any mask)
    zeroes the lengths of dead output rows so padding can't inflate the
    offsets."""
    from .gather import varlen_gather_plan
    new_offsets, src, valid_pos = varlen_gather_plan(
        col.offsets, indices, out_live, char_capacity)
    if col.chars.shape[0]:
        limit = col.chars.shape[0] - 1
        out = jnp.where(valid_pos,
                        col.chars[jnp.clip(src, 0, limit)],
                        jnp.uint8(0))
    else:
        out = jnp.zeros((char_capacity,), jnp.uint8)
    validity = col.validity[indices]
    return TpuColumnVector(col.dtype, validity=validity,
                           offsets=new_offsets, chars=out)


def substring_tpu(col: TpuColumnVector, start: jax.Array, length: jax.Array,
                  char_capacity: int) -> TpuColumnVector:
    """Byte-substring (Spark SUBSTRING is char-based; exact for ASCII —
    the planner falls back for non-ASCII batches when configured)."""
    lens = string_lengths(col)
    # Spark 1-based start; negative counts from end; clamp like Spark.
    s = jnp.where(start > 0, start - 1,
                  jnp.where(start < 0, jnp.maximum(lens + start, 0), 0))
    s = jnp.minimum(s, lens)
    ln = jnp.clip(length, 0)
    e = jnp.minimum(s + ln, lens)
    new_lens = (e - s).astype(jnp.int32)
    new_offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(new_lens, dtype=jnp.int32)])
    src_starts = col.offsets[:-1] + s.astype(jnp.int32)
    n = lens.shape[0]

    def loop_body(state):
        chunk, out = state
        pos = chunk * _WINDOW + jnp.arange(_WINDOW, dtype=jnp.int32)[None, :]
        in_range = pos < new_lens[:, None]
        src_idx = jnp.clip(src_starts[:, None] + pos, 0,
                           max(col.chars.shape[0] - 1, 0))
        vals = col.chars[src_idx] if col.chars.shape[0] else \
            jnp.zeros((n, _WINDOW), jnp.uint8)
        dst_idx = jnp.where(in_range, new_offsets[:-1][:, None] + pos,
                            char_capacity)
        out = out.at[dst_idx.reshape(-1)].set(vals.reshape(-1), mode="drop")
        return chunk + 1, out

    max_chunks = jnp.int32(-(-jnp.max(new_lens, initial=0) // _WINDOW))
    out = jnp.zeros((char_capacity,), jnp.uint8)
    _, out = jax.lax.while_loop(lambda st: st[0] < max_chunks, loop_body,
                                (jnp.int32(0), out))
    return TpuColumnVector(col.dtype, validity=col.validity,
                           offsets=new_offsets, chars=out)


def _case_map_ascii(chars: jax.Array, to_upper: bool) -> jax.Array:
    if to_upper:
        is_lower = (chars >= ord("a")) & (chars <= ord("z"))
        return jnp.where(is_lower, chars - 32, chars)
    is_upper = (chars >= ord("A")) & (chars <= ord("Z"))
    return jnp.where(is_upper, chars + 32, chars)


def upper_ascii_tpu(col: TpuColumnVector) -> TpuColumnVector:
    return col.with_arrays(chars=_case_map_ascii(col.chars, True))


def lower_ascii_tpu(col: TpuColumnVector) -> TpuColumnVector:
    return col.with_arrays(chars=_case_map_ascii(col.chars, False))


def concat_strings_tpu(cols, char_capacity: int,
                       validity=None) -> TpuColumnVector:
    """Row-wise CONCAT of string columns (null if any input null — Spark)."""
    n = cols[0].offsets.shape[0] - 1
    lens = [string_lengths(c) for c in cols]
    total = sum(lens)
    new_offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(total, dtype=jnp.int32)])
    out = jnp.zeros((char_capacity,), jnp.uint8)
    base = new_offsets[:-1]
    for c, ln in zip(cols, lens):
        src_starts = c.offsets[:-1]

        def loop_body(state, c=c, ln=ln, base=base, src_starts=src_starts):
            chunk, acc = state
            pos = chunk * _WINDOW + \
                jnp.arange(_WINDOW, dtype=jnp.int32)[None, :]
            in_range = pos < ln[:, None]
            src_idx = jnp.clip(src_starts[:, None] + pos, 0,
                               max(c.chars.shape[0] - 1, 0))
            vals = c.chars[src_idx] if c.chars.shape[0] else \
                jnp.zeros((n, _WINDOW), jnp.uint8)
            dst_idx = jnp.where(in_range, base[:, None] + pos, char_capacity)
            acc = acc.at[dst_idx.reshape(-1)].set(vals.reshape(-1),
                                                  mode="drop")
            return chunk + 1, acc

        max_chunks = jnp.int32(-(-jnp.max(ln, initial=0) // _WINDOW))
        _, out = jax.lax.while_loop(lambda st: st[0] < max_chunks, loop_body,
                                    (jnp.int32(0), out))
        base = base + ln
    if validity is None:
        validity = cols[0].validity
        for c in cols[1:]:
            validity = validity & c.validity
    return TpuColumnVector(cols[0].dtype, validity=validity,
                           offsets=new_offsets, chars=out)


def _match_at(col: TpuColumnVector, pat: np.ndarray, starts) -> jax.Array:
    """True where pat matches at byte offset `starts` (per-row)."""
    k = len(pat)
    if k == 0:
        return jnp.ones((col.offsets.shape[0] - 1,), jnp.bool_)
    idx = starts[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
    idx = jnp.clip(idx, 0, max(col.chars.shape[0] - 1, 0))
    b = col.chars[idx] if col.chars.shape[0] else \
        jnp.zeros((col.offsets.shape[0] - 1, k), jnp.uint8)
    return jnp.all(b == jnp.asarray(pat)[None, :], axis=1)


def starts_with_tpu(col: TpuColumnVector, pattern: bytes) -> jax.Array:
    pat = np.frombuffer(pattern, np.uint8)
    lens = string_lengths(col)
    ok = lens >= len(pat)
    return ok & _match_at(col, pat, col.offsets[:-1])


def ends_with_tpu(col: TpuColumnVector, pattern: bytes) -> jax.Array:
    pat = np.frombuffer(pattern, np.uint8)
    lens = string_lengths(col)
    ok = lens >= len(pat)
    starts = col.offsets[:-1] + lens - len(pat)
    return ok & _match_at(col, pat, jnp.maximum(starts, 0))


def contains_tpu(col: TpuColumnVector, pattern: bytes) -> jax.Array:
    """Substring search: slide the pattern over every position (bounded by
    max row length via while_loop)."""
    pat = np.frombuffer(pattern, np.uint8)
    n = col.offsets.shape[0] - 1
    lens = string_lengths(col)
    if len(pat) == 0:
        return jnp.ones((n,), jnp.bool_)
    max_start = jnp.max(lens, initial=0) - len(pat)

    def loop_body(state):
        i, found = state
        starts = col.offsets[:-1] + i
        in_range = i <= lens - len(pat)
        m = _match_at(col, pat, starts) & in_range
        return i + 1, found | m

    _, found = jax.lax.while_loop(
        lambda st: (st[0] <= max_start) & ~jnp.all(st[1]),
        loop_body, (jnp.int32(0), jnp.zeros((n,), jnp.bool_)))
    return found
