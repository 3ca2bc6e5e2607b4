"""Sorted-segment reductions without scatters.

TPU replacement for the scatter-shaped `jax.ops.segment_*` family on the
aggregate path (SURVEY.md §7.1.3; reference mount empty). XLA lowers
`segment_sum`/`min`/`max` to scatter-adds that serialize on TPU
(~100 ms per 2M rows, measured); but the engine's sort-based group-by
always presents SORTED segment ids, where the same reductions are
scan/sort/gather shaped:

- **sum**: native `jnp.cumsum` (a dedicated cumulative HLO: the v5e's
  compiler takes 9.6 s for an int32 prefix over 2^21 rows where
  `lax.associative_scan` of the same thing takes 366 s — PR 21, asked
  without a chip; float sums use 1024-row blocked prefixes, whose
  float64 form compiles in 2.4 s against 331 s for one global float64
  cumsum), then per segment the difference of prefix values at its
  edges, found by `searchsorted` over the sorted ids. Exact for ints;
  for floats the
  rounding matches a running left-to-right sum (the order-variance the
  engine already declares via variableFloatAgg).
- **min/max**: one stable 2-lane sort by (segment, value) puts each
  segment's extreme at its edge — a gather, no scan at all.

Empty segments (ids past the live groups) read the op identity, matching
`jax.ops.segment_*` semantics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["seg_reduce_sorted", "segment_starts_sorted"]


def _identity(kind: str, dtype):
    if kind == "sum":
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        v = jnp.inf if kind == "min" else -jnp.inf
    else:
        info = jnp.iinfo(dtype)
        v = info.max if kind == "min" else info.min
    return jnp.array(v, dtype)


def seg_reduce_sorted(vals: jax.Array, seg: jax.Array, cap: int,
                      kind: str) -> jax.Array:
    """Reduce `vals` per segment for SORTED (non-decreasing) `seg` ids,
    output length `cap` indexed by segment id. kind: sum|min|max."""
    n = seg.shape[0]
    g = jnp.arange(cap, dtype=seg.dtype)
    right = jnp.searchsorted(seg, g, side="right").astype(jnp.int32)
    left = jnp.searchsorted(seg, g, side="left").astype(jnp.int32)
    empty = right == left

    def prefix_diff(v):
        # exact for ints (the only users): one global cumsum, edge diffs
        ps = jnp.cumsum(v)
        hi = ps[jnp.clip(right - 1, 0, n - 1)]
        lo = jnp.where(left > 0, ps[jnp.clip(left - 1, 0, n - 1)],
                       jnp.zeros((), ps.dtype))
        return hi - lo

    def blocked_float_sum(v):
        """Float segment sums from BLOCK-LOCAL prefixes: a plain global
        prefix-diff inherits the absolute rounding error of the whole
        running total, zeroing small segments that sit after a large
        prefix (observed: one 1.0-row segment after 16K rows of 2000.0
        read back as 0.0 in f32; TPU f64 is a PAIR of f32, ~49 bits,
        so it has the same failure further out). Here prefixes
        reset every K rows, so an in-block segment's error scales with
        its own block; only segments spanning >= K rows touch the
        block-total prefix, whose error is small relative to any
        segment that large."""
        K = min(1024, n)
        nb = -(-n // K)
        vp = jnp.pad(v, (0, nb * K - n))
        p2 = jnp.cumsum(vp.reshape(nb, K), axis=1)
        pflat = p2.reshape(-1)
        t = p2[:, -1]                       # per-block totals
        bt = jnp.cumsum(t)                  # block-total prefix
        l = jnp.clip(left, 0, n - 1)
        r_ = jnp.clip(right - 1, 0, n - 1)  # inclusive last row
        bl, br = l // K, r_ // K
        p_last = pflat[r_]
        p_before = jnp.where(l % K == 0, jnp.zeros((), pflat.dtype),
                             pflat[jnp.clip(l - 1, 0, n - 1)])
        same = bl == br
        head = t[bl] - p_before
        mid = jnp.where(br - bl >= 2,
                        bt[jnp.clip(br - 1, 0, nb - 1)] - bt[bl],
                        jnp.zeros((), bt.dtype))
        return jnp.where(same, p_last - p_before, head + mid + p_last)

    if kind == "sum":
        if jnp.issubdtype(vals.dtype, jnp.floating):
            # non-finite values would poison prefix differences for
            # every later segment (inf-inf = NaN); count them per
            # segment with exact int prefixes and recompose IEEE
            # semantics on top of the finite part
            finite = jnp.isfinite(vals)
            base = blocked_float_sum(jnp.where(finite, vals,
                                               jnp.zeros((), vals.dtype)))
            nan_c = prefix_diff(jnp.isnan(vals).astype(jnp.int32))
            pos_c = prefix_diff((vals == jnp.inf).astype(jnp.int32))
            neg_c = prefix_diff((vals == -jnp.inf).astype(jnp.int32))
            out = jnp.where(
                (nan_c > 0) | ((pos_c > 0) & (neg_c > 0)),
                jnp.array(jnp.nan, vals.dtype),
                jnp.where(pos_c > 0, jnp.array(jnp.inf, vals.dtype),
                          jnp.where(neg_c > 0,
                                    jnp.array(-jnp.inf, vals.dtype),
                                    base.astype(vals.dtype))))
        else:
            out = prefix_diff(vals).astype(vals.dtype)
    else:
        if vals.dtype == jnp.bool_:
            raise TypeError("sort-based min/max needs an orderable lane")
        _, sval = jax.lax.sort((seg, vals), num_keys=2)
        edge = left if kind == "min" else jnp.clip(right - 1, 0, n - 1)
        out = sval[jnp.clip(edge, 0, n - 1)]
    return jnp.where(empty, _identity(kind, vals.dtype), out)


def segment_starts_sorted(seg: jax.Array, cap: int) -> jax.Array:
    """starts[g] = first position of segment g in the sorted order (cap
    entries; empty/out-of-range segments clamp into [0, n-1]). A
    searchsorted, not a sort — the previous compaction-based
    implementation paid a full 2-lane sort per aggregate batch."""
    g = jnp.arange(cap, dtype=seg.dtype)
    n = seg.shape[0]
    return jnp.clip(jnp.searchsorted(seg, g, side="left"), 0,
                    n - 1).astype(jnp.int32)
