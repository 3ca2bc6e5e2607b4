"""Columnar batches on device.

The engine's unit of execution, analogous to the reference's Spark
`ColumnarBatch` of `GpuColumnVector`s (SURVEY.md §2.2-A L3). A batch is a
pytree so whole operator pipelines jit over it; `capacity` is static
(bucketed) while `row_count` is a traced device scalar, so batches of
different actual sizes share one compiled program.
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..datatypes import Schema
from .column import TpuColumnVector

__all__ = ["TpuBatch", "bucket_rows", "bucket_bytes", "bucket_fine",
           "bucket_fine_even", "row_mask"]

_MIN_CAPACITY = 128


def bucket_rows(n: int, minimum: int = _MIN_CAPACITY) -> int:
    """Static capacity bucket: next power of two >= n (>= minimum).

    Bounds XLA recompilation to O(log max_rows) program variants per
    pipeline — the TPU-side answer to cudf's exact-size allocations.
    """
    cap = minimum
    while cap < n:
        cap <<= 1
    return cap


def bucket_bytes(n: int, minimum: int = 1 << 10) -> int:
    cap = minimum
    while cap < n:
        cap <<= 1
    return cap


def bucket_fine(n: int) -> int:
    """Sub-octave bucket {1, 1.25, 1.5, 1.75}×2^k: upload padding
    averages ~11% instead of pow2's ~33% — used for arrays whose bytes
    cross the host→device link, where padding directly taxes the
    transfer. Still O(log) distinct shapes per octave for the jit cache."""
    if n <= 8:
        return 8
    p = 1
    while p < n:
        p <<= 1
    half = p >> 1
    for q in (5, 6, 7):  # 1.25×, 1.5×, 1.75× the lower octave
        cand = (half * q) // 4
        if cand >= n:
            return cand
    return p


def bucket_fine_even(n: int) -> int:
    """``bucket_fine`` rounded up to an even count — the shape the
    fused-decode arena quantizes its uint32 segment slots to (even
    words = 8-byte alignment, so PLAIN 64-bit regions and the widened
    envelope's string-store/delta-stream segments land word-pair
    aligned for the funnel-shift gather)."""
    b = max(8, bucket_fine(n))
    return b + (b & 1)


def row_mask(capacity: int, row_count) -> jax.Array:
    """Bool mask of live (non-padding) rows."""
    return jnp.arange(capacity, dtype=jnp.int32) < row_count


class TpuBatch:
    """Device batch. Live rows are the prefix below ``row_count`` further
    restricted by the optional ``selection`` mask — the lazy-filter
    representation: `TpuFilterExec` attaches a selection instead of paying
    a full sort-based compaction, and only consumers that need prefix
    layout (concat, sort gather, arrow download, exchange split) compact
    (`ops.gather.ensure_compacted`). Mask-aware consumers (aggregate,
    join, any `live_mask()` user) read through it for free."""

    __slots__ = ("columns", "schema", "row_count", "selection",
                 "_num_rows_cache")

    def __init__(self, columns: List[TpuColumnVector], schema: Schema,
                 row_count, selection=None):
        self.columns = list(columns)
        self.schema = schema
        self.selection = selection
        if isinstance(row_count, (int, np.integer)):
            self._num_rows_cache = int(row_count) if selection is None \
                else None
            # np scalar, NOT jnp: an eager device op here costs a full
            # host->device dispatch round-trip per batch construction
            row_count = np.int32(row_count)
        else:
            self._num_rows_cache = None
        self.row_count = row_count

    @property
    def capacity(self) -> int:
        if not self.columns:
            return 0
        return self.columns[0].capacity

    @property
    def num_rows(self) -> int:
        """Actual live row count; syncs device->host once and caches."""
        if self._num_rows_cache is None:
            if self.selection is None:
                self._num_rows_cache = int(jax.device_get(self.row_count))
            else:
                self._num_rows_cache = int(jax.device_get(
                    _live_count(self)))
        return self._num_rows_cache

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def column(self, i: int) -> TpuColumnVector:
        return self.columns[i]

    def live_mask(self) -> jax.Array:
        m = row_mask(self.capacity, self.row_count)
        if self.selection is not None:
            m = m & self.selection
        return m

    def with_selection(self, keep: jax.Array) -> "TpuBatch":
        """Restrict live rows by a bool mask (ANDed with any existing
        selection) without moving data."""
        sel = keep if self.selection is None else self.selection & keep
        return TpuBatch(self.columns, self.schema, self.row_count,
                        selection=sel)

    def device_size_bytes(self) -> int:
        return sum(c.device_size_bytes() for c in self.columns)

    def with_columns(self, columns, schema=None, row_count=None):
        return TpuBatch(columns,
                        self.schema if schema is None else schema,
                        self.row_count if row_count is None else row_count,
                        selection=self.selection)

    def block_until_ready(self):
        for c in self.columns:
            for a in c.arrays():
                a.block_until_ready()
        return self

    def __repr__(self):
        return (f"TpuBatch(rows~cap={self.capacity}, "
                f"cols={len(self.columns)}, schema={self.schema})")


def _live_count(b: TpuBatch):
    import jax.numpy as jnp
    return jnp.sum(b.live_mask().astype(jnp.int32))


def _flatten_batch(b: TpuBatch):
    return (b.columns, b.row_count, b.selection), b.schema


def _unflatten_batch(schema, children):
    columns, row_count, selection = children
    return TpuBatch(columns, schema, row_count, selection=selection)


jax.tree_util.register_pytree_node(TpuBatch, _flatten_batch, _unflatten_batch)
